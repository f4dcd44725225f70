"""The Ling-3.0-flash decoder family for the serving path.

A second decoder family beside ``models/decode.py``'s GPT-2 block:
RMSNorm, bias-free projections, and per layer one of two sequence
mixers -- Kimi Delta Attention (KDA, arXiv:2510.26692: a gated delta
rule over a fixed ``(d_k, d_v)`` float32 state a head, behind a causal
depthwise convolution of kernel 4) or multi-head latent attention (MLA,
DeepSeek-V2 form: the cache holds one 576-wide ``[latent | rotary key]``
row a token) -- followed by a dense SwiGLU or by a 512-expert sigmoid
router with a shared expert (``parallel/moe.py:moe_serve``).  The
equations are at the head of ``benchmark/reference/ling.py``, the plain
float32 reference the tests and the benchmark compare this file with.

One forward, one block function a layer kind
--------------------------------------------
:meth:`LingDecoder.forward` is the only forward pass: embedding, the
blocks, the head.  It runs over ``N`` tokens and a *cache view* that
``serving/paged_kv.py`` hands it, and the view is what differs between
the two programs that call it:

- the decode step: ``N`` = slots, one token each at its own position;
  the view reads and writes each slot's recurrent state, takes one
  latent row a slot into its pages and attends over them;
- an admission's prefill: ``N`` = the padded prompt of ONE sequence; the
  view starts from a zero state (so a reused slot never sees its
  predecessor), takes the state after the last real token into the
  slot's row and writes the prompt's latent rows a page at a time.

``view.step`` tells the two apart where the mathematics has two forms of
the same thing: the KDA recurrence (one step, or chunks of 16 with the
state carried between chunks: equal to the token-by-token recurrence,
tests pin it) and MLA (absorbed over each slot's latent pages, or
expanded over the prompt's own rows: equal, tests pin it).

What the decoder declares (:meth:`LingDecoder.paged_layout`)
-----------------------------------------------------------
``pages``: ``{"latent": (MLA layers, 576)}`` -- one row a token and MLA
layer, K and V the same bytes.  ``state``: per KDA layer
``layer<i>.kda_S`` ``(H, d_k, d_v)`` float32 and ``layer<i>.kda_conv``
``(3, 3 H d_k)``, the convolution's last three inputs.  A decoder with
per-slot state cannot reuse a cached prefix (nothing snapshots the state
at a block boundary), so ``prefix_reuse`` is False and ``PagedSlots``
shares no page.

The share of an expert-parallel deployment
------------------------------------------
``experts_held`` experts from ``expert_offset`` on live here, and
``vocab`` rows of the embedding and the head.  The router keeps its full
width; the MoE layer adds only the held experts' part (see
``moe_serve``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .blocks import lin as _lin, moe_block, rms_norm, swiglu as _swiglu
from .mla import (mla_absorbed, mla_absorbed_paged, mla_expanded,
                  rope as _rope, rope_freq)

__all__ = ["LingConfig", "LingDecoder", "kda_recurrent_step", "kda_chunked",
           "mla_absorbed", "mla_expanded"]

HIGHEST = jax.lax.Precision.HIGHEST
KDA_CHUNK = 16      # |g| <= 5 a token: exp(+-40) stays well inside float32


@dataclasses.dataclass(frozen=True)
class LingConfig:
    hidden: int
    heads: int
    head_dim: int
    conv: int
    kda_lower: float
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    rope_theta: float
    experts: int
    experts_held: int
    expert_offset: int
    top_k: int
    n_group: int
    topk_group: int
    scale: float
    eps: float
    mixers: tuple
    mlps: tuple

    @classmethod
    def from_dict(cls, config):
        """From a ``config.json``-style dict: the published keys, plus
        ``num_layers`` / ``layer_offset`` (layer ``i`` here is published
        layer ``i + layer_offset``; published layer ``l`` is MLA where
        ``(l + 1) % layer_group_size == 0``), ``num_experts`` = experts
        held here with the router's width under ``published``, and
        ``expert_offset``."""
        n = int(config["num_layers"])
        off = int(config.get("layer_offset", 0))
        period = int(config["layer_group_size"])
        dense = int(config["first_k_dense_replace"])
        held = int(config["num_experts"])
        return cls(
            hidden=int(config["hidden_size"]),
            heads=int(config["num_attention_heads"]),
            head_dim=int(config["head_dim"]),
            conv=int(config["short_conv_kernel_size"]),
            kda_lower=float(config["kda_lower_bound"]),
            kv_rank=int(config["kv_lora_rank"]),
            nope=int(config["qk_nope_head_dim"]),
            rope=int(config["qk_rope_head_dim"]),
            v_dim=int(config["v_head_dim"]),
            rope_theta=float(config["rope_theta"]),
            experts=int(config.get("published", {}).get("num_experts",
                                                        held)),
            experts_held=held,
            expert_offset=int(config.get("expert_offset", 0)),
            top_k=int(config["num_experts_per_tok"]),
            n_group=int(config["n_group"]),
            topk_group=int(config["topk_group"]),
            scale=float(config["routed_scaling_factor"]),
            eps=float(config["rms_norm_eps"]),
            mixers=tuple("mla" if (i + off + 1) % period == 0 else "kda"
                         for i in range(n)),
            mlps=tuple("dense" if i < dense else "moe" for i in range(n)))


# ------------------------------------------------------------- pieces
def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + 1e-6)


# ---------------------------------------------------------------- KDA
def kda_recurrent_step(q, k, v, g, beta, S):
    """One position for every row: ``q, k, g`` (B, H, d_k), ``v`` (B, H,
    d_v), ``beta`` (B, H), ``S`` (B, H, d_k, d_v), all float32.  The
    state is read twice and written once: both contractions with the
    decayed state (``k`` for the delta rule's prediction, ``q`` for the
    output) come from one pass, and ``o = S'^T q = (a S)^T q + (q.k) u``
    spares the third."""
    a = jnp.exp(g)
    pred = jnp.sum(S * (k * a)[..., None], axis=-2)
    out0 = jnp.sum(S * (q * a)[..., None], axis=-2)
    u = beta[..., None] * (v - pred)
    S = a[..., None] * S + k[..., None] * u[..., None, :]
    return out0 + jnp.sum(q * k, -1, keepdims=True) * u, S


def kda_chunked(q, k, v, g, beta, S0, chunk=KDA_CHUNK):
    """The same recurrence over one sequence in chunks: ``q, k, g`` (T,
    H, d_k), ``v`` (T, H, d_v), ``beta`` (T, H), ``S0`` (H, d_k, d_v);
    ``T`` a multiple of ``chunk``.  Returns ``(o (T, H, d_v), S_T)``.

    Inside a chunk, with ``G_t`` the running sum of ``g`` (a channel's
    log decay since the chunk began) and ``u_t = beta_t (v_t - S_{t-1}^T
    Diag(alpha_t) k_t)``:  ``(I + A) U = beta V - beta (K e^G) S_0`` with
    ``A_ti = beta_t (k_t e^{G_t}) . (k_i e^{-G_i})`` for ``i < t``;  ``O =
    (Q e^G) S_0 + tril(B) U`` with ``B_ti = (q_t e^{G_t}) . (k_i
    e^{-G_i})``;  ``S_C = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U``.
    Everything that does not hold the state is computed for all chunks
    at once; the scan carries the state alone.  The two factors of a
    pairwise decay are taken from the chunk's middle, so their exponents
    reach ``+-5 chunk / 2``, which float32 holds with room for chunks of
    16 (the "safe gate"'s lower bound of -5 a token); a position with
    ``beta = 0`` and ``g = 0`` leaves the state as it is (right
    padding)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    n = T // chunk

    def blocks(a):
        return a.reshape(n, chunk, H, -1).transpose(0, 2, 1, 3)

    q, k, v, g = blocks(q), blocks(k), blocks(v), blocks(g)  # (n, H, C, d)
    beta = beta.reshape(n, chunk, H).transpose(0, 2, 1)[..., None]
    G = jnp.cumsum(g, axis=2)
    k_in, q_in = k * jnp.exp(G), q * jnp.exp(G)
    mm = lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    # the pairwise decays e^{G_t - G_i} as a product of two factors taken
    # from the chunk's middle: exponents of at most +-5 chunk / 2
    Gm = G - G[:, :, chunk // 2 - 1:chunk // 2]
    k_out = k * jnp.exp(-Gm)
    A = jnp.tril(mm("nhtd,nhid->nhti", k * jnp.exp(Gm), k_out) * beta, -1)
    B = jnp.tril(mm("nhtd,nhid->nhti", q * jnp.exp(Gm), k_out))
    rhs = jnp.concatenate([beta * v, beta * k_in], -1)
    sol = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(chunk, dtype=A.dtype), rhs, lower=True,
        unit_diagonal=True)
    w_v, w_k = sol[..., :dv], sol[..., dv:]
    g_end = G[:, :, -1]                                       # (n, H, d_k)
    k_end = k * jnp.exp(g_end[:, :, None] - G)

    def body(S, c):
        w_v, w_k, q_in, B, k_end, g_end = c
        U = w_v - mm("hck,hkv->hcv", w_k, S)
        O = mm("hck,hkv->hcv", q_in, S) + mm("hti,hiv->htv", B, U)
        S = jnp.exp(g_end)[..., None] * S + mm("hck,hcv->hkv", k_end, U)
        return S, O

    S, O = jax.lax.scan(body, S0, (w_v, w_k, q_in, B, k_end, g_end))
    return O.transpose(0, 2, 1, 3).reshape(T, H, dv), S


# ------------------------------------------------------------ decoder
class LingDecoder:
    """Bound weights + the family's forward, for ``serve_decoder``.

    ``params``: a flat dict of leaves (``tok_embed_weight``,
    ``layer<i>_<x>``, ``final_norm_weight``, ``lm_head_weight``; shapes in
    ``benchmark/families/ling.py:param_specs``), served in ``dtype``.
    The scheduler needs ``max_len``, ``vocab``, ``mesh``, ``p``;
    ``PagedSlots`` needs ``family``, ``paged_layout`` and ``forward``.
    There is no contiguous-cache form: serve it paged (``kv_block``)."""

    family = "ling"
    mesh = None

    def __init__(self, params, config, max_len, dtype=jnp.bfloat16):
        self.cfg = config if isinstance(config, LingConfig) \
            else LingConfig.from_dict(config)
        keep32 = ("kda_A_log", "kda_dt_bias", "router_bias")
        self.p = {k: jnp.asarray(v, jnp.float32 if k.endswith(keep32)
                                 else dtype) for k, v in params.items()}
        self.max_len = int(max_len)
        self.vocab = self.p["lm_head_weight"].shape[0]
        self._cache_dtype = jnp.dtype(dtype)
        held = self.cfg.experts_held
        for i, kind in enumerate(self.cfg.mlps):
            if kind == "moe" and \
                    self.p[f"layer{i}_experts_gate_weight"].shape[0] != held:
                raise ValueError(
                    f"layer {i} holds "
                    f"{self.p[f'layer{i}_experts_gate_weight'].shape[0]} "
                    f"experts, the configuration says {held}")

    # ------------------------------------------------- what it declares
    def paged_layout(self):
        c = self.cfg
        state = {}
        for i, kind in enumerate(c.mixers):
            if kind == "kda":
                state[f"layer{i}.kda_S"] = (
                    (c.heads, c.head_dim, c.head_dim), jnp.float32)
                state[f"layer{i}.kda_conv"] = (
                    (c.conv - 1, 3 * c.heads * c.head_dim),
                    self._cache_dtype)
        n_mla = sum(kind == "mla" for kind in c.mixers)
        return {"pages": {"latent": (n_mla, c.kv_rank + c.rope,
                                     self._cache_dtype)},
                "state": state,
                # what moe_block counts (parallel/moe.py:moe_serve)
                "counters": ("expert_assignments_held",
                             "expert_assignments_absent",
                             "expert_distinct_hits",
                             "expert_kernel_calls"),
                # a hit would also need the state at that block boundary
                "prefix_reuse": not state}

    # ------------------------------------------------------ the blocks
    def kda_block(self, p, i, x, view):
        """KDA over ``x`` (N, D).  The state and the convolution's tail
        come from and go back to the view."""
        c = self.cfg
        N, H, d = x.shape[0], c.heads, c.head_dim
        pre = f"layer{i}_kda_"
        u = jnp.concatenate([_lin(x, p[pre + n + "_weight"])
                             for n in ("q", "k", "v")], -1)      # (N, 3Hd)
        kernel = jnp.concatenate([p[pre + n + "_conv"]
                                  for n in ("q", "k", "v")])     # (3Hd, K)
        tail_name, K = f"layer{i}.kda_conv", c.conv
        if view.step:
            win = jnp.concatenate([view.state(tail_name), u[:, None]], 1)
            view.set_state(tail_name, win[:, 1:])
            y = jnp.einsum("nkc,ck->nc", win.astype(jnp.float32),
                           kernel.astype(jnp.float32))
        else:
            pad = jnp.concatenate(
                [jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
            # inputs at positions length-3 .. length-1
            view.set_state(tail_name, jax.lax.dynamic_slice_in_dim(
                pad, view.length, K - 1))
            y = sum(pad[j:j + N].astype(jnp.float32)
                    * kernel[:, j].astype(jnp.float32) for j in range(K))
        y = jax.nn.silu(y).reshape(N, 3, H, d)
        q, k, v = _l2(y[:, 0]) * d ** -0.5, _l2(y[:, 1]), y[:, 2]
        a = (_lin(x, p[pre + "a_weight"]).astype(jnp.float32)
             + p[pre + "dt_bias"]).reshape(N, H, d)
        g = c.kda_lower * jax.nn.sigmoid(
            jnp.exp(p[pre + "A_log"])[None, :, None] * a)
        beta = jax.nn.sigmoid(
            _lin(x, p[pre + "beta_weight"]).astype(jnp.float32))
        S_name = f"layer{i}.kda_S"
        if view.step:
            o, S = kda_recurrent_step(q, k, v, g, beta, view.state(S_name))
        else:
            real = view.valid
            o, S = kda_chunked(
                q, k, v, jnp.where(real[:, None, None], g, 0.0),
                jnp.where(real[:, None], beta, 0.0), view.state(S_name))
        view.set_state(S_name, S)
        gate = jax.nn.sigmoid(
            _lin(x, p[pre + "g_weight"]).astype(jnp.float32))
        o = rms_norm(o, p[pre + "onorm_weight"], c.eps) * gate[..., None]
        return _lin(o.reshape(N, H * d).astype(x.dtype), p[pre + "o_weight"])

    def mla_block(self, p, i, x, view):
        """MLA over ``x`` (N, D); the view takes each token's ``[latent |
        rotary key]`` row and, in the step, attends for the absorbed
        form over each slot's pages."""
        c = self.cfg
        N, H = x.shape[0], c.heads
        pre = f"layer{i}_mla_"
        page_layer = sum(kind == "mla" for kind in c.mixers[:i])
        q = _lin(x, p[pre + "q_weight"]).reshape(N, H, c.nope + c.rope)
        q_nope = q[..., :c.nope]
        q_rope = _rope(q[..., c.nope:].astype(jnp.float32), view.positions,
                       rope_freq(c.rope_theta, c.rope)).astype(x.dtype)
        kva = _lin(x, p[pre + "kva_weight"])
        lat = rms_norm(kva[:, :c.kv_rank], p[pre + "kv_norm_weight"], c.eps)
        k_rope = _rope(
            kva[:, c.kv_rank:].astype(jnp.float32), view.positions,
            rope_freq(c.rope_theta, c.rope)).astype(x.dtype)
        rows = jnp.concatenate([lat, k_rope], -1)              # (N, 576)
        if view.step:
            o = mla_absorbed_paged(q_nope, q_rope, rows, view, "latent",
                                   page_layer, p[pre + "kvb_weight"], c)
        else:
            view.append("latent", page_layer, rows)
            o = mla_expanded(q_nope, q_rope, rows, p[pre + "kvb_weight"], c)
        return _lin(o, p[pre + "o_weight"])

    # ------------------------------------------------------ the forward
    def forward(self, p, tokens, view):
        """``tokens`` (N,) at ``view.positions`` -> logits.  In the step
        (B, V), a row a slot; in a prefill (V,), the row of the last
        real token (``view.length - 1``)."""
        c = self.cfg
        h = jnp.take(p["tok_embed_weight"], tokens.astype(jnp.int32),
                     axis=0)
        for i, (mixer, mlp) in enumerate(zip(c.mixers, c.mlps)):
            with jax.named_scope(f"layer{i}"):
                x = rms_norm(h, p[f"layer{i}_norm1_weight"], c.eps)
                if mixer == "kda":
                    with jax.named_scope("kda"):
                        h = h + self.kda_block(p, i, x, view)
                else:
                    with jax.named_scope("mla_attn"):
                        h = h + self.mla_block(p, i, x, view)
                x = rms_norm(h, p[f"layer{i}_norm2_weight"], c.eps)
                if mlp == "dense":
                    with jax.named_scope("mlp.dense"):
                        h = h + _swiglu(
                            x, p[f"layer{i}_mlp_gate_weight"],
                            p[f"layer{i}_mlp_up_weight"],
                            p[f"layer{i}_mlp_down_weight"])
                else:
                    h = h + moe_block(p, f"layer{i}_", x, view, c)
        if not view.step:
            h = jax.lax.dynamic_slice_in_dim(h, view.length - 1, 1)
        with jax.named_scope("head"):
            h = rms_norm(h, p["final_norm_weight"], c.eps)
            logits = _lin(h, p["lm_head_weight"])
        return logits if view.step else logits[0]
