"""The Olmo-Hybrid decoder family (``model_type`` ``olmo_hybrid``) for the
serving path: gated DeltaNet layers beside plain multi-head attention.

A sixth decoder family.  Published layers come in periods of four,
three gated-DeltaNet layers (``linear_attention``) then one attention
layer (``full_attention``); the block is OLMo's, a norm AFTER each
sublayer and none before it (``h = x + n_a(Mix(x))``, ``y = h +
n_b(SwiGLU(h))``).

- **Gated DeltaNet** (arXiv:2412.06464, negative eigenvalues
  arXiv:2411.12537): ``q, k, v`` through a causal depthwise convolution
  of 4 taps and SiLU, L2-normed ``q`` (times ``d_k^-1/2``) and ``k``;
  one decay a head and token, ``g = -exp(A_log) softplus(x W_a +
  dt_bias)``, unbounded below; ``beta = 2 sigmoid(x W_b)`` in (0, 2);
  the delta rule over a ``(d_k, d_v)`` = ``(96, 192)`` float32 state a
  head (``models/delta_rule.py``: the one-token step, and the chunked
  form for a gate a head, which no gate overflows -- in a prefill on a
  TPU the kernel ``ops/gdn_scan.py``, where the view's scan schedule
  and the kernel's gate allow); the output
  RMS-normed a head and gated by ``SiLU(x W_g)``.
- **Attention**: 30 heads, each with K/V of its own, QK-norm over the
  whole projection, no rotary, over the K/V pages (``view.attend``: the
  paged kernel in the step; in a prefill over a window as long as
  this cell's, an online softmax over the tail's own keys and the
  history's pages, a key block at a time).

The equations are at the head of ``benchmark/reference/olmo_hybrid.py``,
the plain float32 reference the tests and the benchmark compare this
file with.

What the decoder declares (:meth:`OlmoHybridDecoder.paged_layout`): K/V
pages for the attention layers; a state per gated-DeltaNet layer,
``layer<i>.gdn_S`` ``(H, d_k, d_v)`` float32 and ``layer<i>.gdn_conv``
``(3, 2 H d_k + H d_v)``, the convolution's last three inputs; no
counter.  ``chunks`` True: a long prompt goes in chunks, each behind the
K/V pages and the state the chunks before it left in the slot's row.
``prefix_reuse`` False: another request's prefix would need the state at
its block boundary, which nothing snapshots.

Named scopes: ``gdn`` (a gated-DeltaNet mixer), ``gdn.state`` (its
recurrence, or the chunked scan), ``attn.pages`` (an attention mixer),
``mlp.dense``, ``head``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops import gdn_scan as _gs
from .blocks import lin as _lin, rms_norm, swiglu as _swiglu
from .delta_rule import gdn_chunked, kda_recurrent_step, l2norm, short_conv

__all__ = ["OlmoHybridConfig", "OlmoHybridDecoder", "gdn_beta"]

# kept float32 whatever the serving type: they steer exponentials
KEPT_FLOAT32 = ("gdn_A_log", "gdn_dt_bias")


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    hidden: int
    heads: int
    head_dim: int
    lin_heads: int
    d_k: int
    d_v: int
    conv: int
    neg_eigval: bool
    eps: float
    mixers: tuple               # "gdn" | "attn" a layer

    @classmethod
    def from_dict(cls, config):
        """From a ``config.json``-style dict: the published keys, plus
        ``num_layers`` / ``layer_offset`` (layer ``i`` here is published
        layer ``i + layer_offset`` of ``layer_types``)."""
        n = int(config["num_layers"])
        off = int(config.get("layer_offset", 0))
        kinds = {"linear_attention": "gdn", "full_attention": "attn"}
        D, H = int(config["hidden_size"]), int(config["num_attention_heads"])
        if int(config["num_key_value_heads"]) != H:
            raise ValueError("the attention layers are multi-head: "
                             "num_key_value_heads must equal the heads")
        if int(config["linear_num_key_heads"]) != \
                int(config["linear_num_value_heads"]):
            raise ValueError("one value head a key head")
        return cls(
            hidden=D, heads=H,
            head_dim=int(config.get("head_dim") or D // H),
            lin_heads=int(config["linear_num_key_heads"]),
            d_k=int(config["linear_key_head_dim"]),
            d_v=int(config["linear_value_head_dim"]),
            conv=int(config["linear_conv_kernel_dim"]),
            neg_eigval=bool(config["linear_allow_neg_eigval"]),
            eps=float(config["rms_norm_eps"]),
            mixers=tuple(kinds[t] for t in
                         config["layer_types"][off:off + n]))


def gdn_beta(b, neg_eigval):
    """The delta rule's step size from ``b = x W_b`` (float32): in (0, 2)
    where negative eigenvalues are allowed, else in (0, 1)."""
    return (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(b)


class OlmoHybridDecoder:
    """Bound weights + the family's forward, for ``serve_decoder``.

    ``params``: a flat dict of leaves (``tok_embed_weight``,
    ``layer<i>_<x>``, ``final_norm_weight``, ``lm_head_weight``; shapes
    in ``benchmark/families/olmo_hybrid.py:param_specs``), served in
    ``dtype`` (the decay's ``A_log`` and ``dt_bias`` in float32).  There
    is no contiguous-cache form: serve it paged (``kv_block``)."""

    family = "olmo_hybrid"
    mesh = None

    def __init__(self, params, config, max_len, dtype=jnp.bfloat16):
        self.cfg = config if isinstance(config, OlmoHybridConfig) \
            else OlmoHybridConfig.from_dict(config)
        self.p = {k: jnp.asarray(v, jnp.float32 if k.endswith(KEPT_FLOAT32)
                                 else dtype) for k, v in params.items()}
        self.max_len = int(max_len)
        self.vocab = self.p["lm_head_weight"].shape[0]
        self._cache_dtype = jnp.dtype(dtype)
        # the attention layers' places among the K/V pages
        self._kv_layer = {}
        for i, kind in enumerate(self.cfg.mixers):
            if kind == "attn":
                self._kv_layer[i] = len(self._kv_layer)

    # ------------------------------------------------- what it declares
    def paged_layout(self):
        c = self.cfg
        width = c.lin_heads * (2 * c.d_k + c.d_v)
        state = {}
        for i, kind in enumerate(c.mixers):
            if kind == "gdn":
                state[f"layer{i}.gdn_S"] = (
                    (c.lin_heads, c.d_k, c.d_v), jnp.float32)
                state[f"layer{i}.gdn_conv"] = (
                    (c.conv - 1, width), self._cache_dtype)
        out = {"pages": {}, "state": state, "counters": (),
               # the same prompt's earlier chunks: their pages and state
               # are the slot's own
               "chunks": True,
               # another request's prefix would need the state at its
               # block boundary, which nothing snapshots
               "prefix_reuse": False}
        if self._kv_layer:
            out["kv_pages"] = (len(self._kv_layer), c.heads, c.head_dim,
                               self._cache_dtype)
        return out

    # ------------------------------------------------------ the blocks
    def gdn_block(self, p, i, x, view):
        """Gated DeltaNet over ``x`` (N, D).  The state and the
        convolution's tail come from and go back to the view: in a
        prefill the tail is the last inputs of the chunk before (zeros
        for a prompt's first), so the convolution runs on across a
        chunk's edge."""
        c = self.cfg
        N, H, dk, dv = x.shape[0], c.lin_heads, c.d_k, c.d_v
        pre = f"layer{i}_gdn_"
        u = jnp.concatenate([_lin(x, p[pre + n + "_weight"])
                             for n in ("q", "k", "v")], -1)
        kernel = jnp.concatenate([p[pre + n + "_conv"]
                                  for n in ("q", "k", "v")])     # (C, K)
        y = short_conv(u, kernel, view, f"layer{i}.gdn_conv")
        y = jax.nn.silu(y)
        q = l2norm(y[:, :H * dk].reshape(N, H, dk)) * dk ** -0.5
        k = l2norm(y[:, H * dk:2 * H * dk].reshape(N, H, dk))
        v = y[:, 2 * H * dk:].reshape(N, H, dv)
        g = -jnp.exp(p[pre + "A_log"]) * jax.nn.softplus(
            _lin(x, p[pre + "a_weight"]).astype(jnp.float32)
            + p[pre + "dt_bias"])                                # (N, H)
        beta = gdn_beta(_lin(x, p[pre + "b_weight"]).astype(jnp.float32),
                        c.neg_eigval)
        S_name = f"layer{i}.gdn_S"
        with jax.named_scope("gdn.state"):
            if view.step:
                o, S = kda_recurrent_step(
                    q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta,
                    view.state(S_name))
            else:
                real = view.valid[:, None]
                args = (q, k, v, jnp.where(real, g, 0.0),
                        jnp.where(real, beta, 0.0), view.state(S_name))
                sched = view.scan_schedule
                if _gs.on_kernel(sched, N, H, dk, dv):
                    view.scanned_on_kernel()
                    o, S = _gs.gdn_scan(
                        *args, interpret=bool(sched.get("interpret")))
                else:
                    o, S = gdn_chunked(*args)
            view.set_state(S_name, S)
        gate = jax.nn.silu(_lin(x, p[pre + "g_weight"]).astype(jnp.float32))
        o = rms_norm(o, p[pre + "onorm_weight"], c.eps) \
            * gate.reshape(N, H, dv)
        return _lin(o.reshape(N, H * dv).astype(x.dtype), p[pre + "o_weight"])

    def attn_block(self, p, i, x, view):
        """Multi-head attention over ``x`` (N, D): QK-norm over the
        whole projection, no rotary; the view writes K/V and attends
        (the step: a slot's one query row over its pages; a prefill:
        the tail behind its history)."""
        c = self.cfg
        N, H, dh = x.shape[0], c.heads, c.head_dim
        pre = f"layer{i}_attn_"
        q = rms_norm(_lin(x, p[pre + "q_weight"]), p[pre + "q_norm_weight"],
                     c.eps)
        k = rms_norm(_lin(x, p[pre + "k_weight"]), p[pre + "k_norm_weight"],
                     c.eps)
        v = _lin(x, p[pre + "v_weight"])
        # (S, H, n, dh): a slot a row in the step, one sequence in a
        # prefill
        heads = (lambda a: a.reshape(N, H, 1, dh)) if view.step else \
            (lambda a: a.reshape(N, H, dh).transpose(1, 0, 2)[None])
        q, k, v = heads(q), heads(k), heads(v)
        layer = self._kv_layer[i]
        o = view.attend(layer, q, k, v)
        o = o[:, :, 0] if view.step else o[0].transpose(1, 0, 2)  # (N, H, dh)
        return _lin(o.reshape(N, H * dh), p[pre + "o_weight"])

    # ------------------------------------------------------ the forward
    def forward(self, p, tokens, view):
        """``tokens`` (N,) at ``view.positions`` -> logits.  In the step
        (B, V), a row a slot; in a prefill (V,), the row of the last
        real token (``view.length - 1``)."""
        c = self.cfg
        h = jnp.take(p["tok_embed_weight"], tokens.astype(jnp.int32),
                     axis=0)
        for i, mixer in enumerate(c.mixers):
            pre = f"layer{i}_"
            with jax.named_scope(f"layer{i}"):
                if mixer == "gdn":
                    with jax.named_scope("gdn"):
                        y = self.gdn_block(p, i, h, view)
                else:
                    with jax.named_scope("attn.pages"):
                        y = self.attn_block(p, i, h, view)
                h = h + rms_norm(y, p[pre + "mix_norm_weight"], c.eps)
                with jax.named_scope("mlp.dense"):
                    y = _swiglu(h, p[pre + "mlp_gate_weight"],
                                p[pre + "mlp_up_weight"],
                                p[pre + "mlp_down_weight"])
                    h = h + rms_norm(y, p[pre + "mlp_norm_weight"], c.eps)
        if not view.step:
            h = jax.lax.dynamic_slice_in_dim(h, view.length - 1, 1)
        with jax.named_scope("head"):
            h = rms_norm(h, p["final_norm_weight"], c.eps)
            logits = _lin(h, p["lm_head_weight"])
        return logits if view.step else logits[0]
