"""Plain reference: the Kimi-K2 language model (the DeepSeek-V3 block,
``model_type`` ``kimi_k2``) in float32 ``jax.numpy``.

Written from the published ``config.json`` and the papers its layers
come from: multi-head latent attention (MLA, arXiv:2405.04434), the
sigmoid router with a correction bias and a shared expert
(arXiv:2412.19437, ``noaux_tc``), YaRN (arXiv:2309.00071).  One sequence
at a time, expanded attention only: no cache, no chunks, no absorbed
form, no kernel, no batching; every matrix product at ``highest``
precision.  It imports nothing of the program.

The equations.  ``x`` is a token's hidden vector (7168), RMSNorm
``n(x; w) = x / sqrt(mean(x^2) + 1e-6) * w``, and layer ``l`` is

    h = x + Attn(n(x; w_in))          y = h + MLP(n(h; w_post))

**Attention** (64 heads, every layer).  ``c_q = n(x W_qa; w_q)`` (1536),
``q = c_q W_qb`` -> per head ``[q_nope 128 | q_rot 64]``.  ``[c_kv 512 |
k_rot 64] = x W_kva``, ``c_kv <- n(c_kv; w_kv)``; ``k_rot`` is one head
shared by all 64.  ``q_rot`` and ``k_rot`` are turned by rotary
positions (interleaved pairs ``(x[2j], x[2j+1])``) with YaRN's
frequencies: ``f_i = theta^(-2i/d)``, ``i = 0..31``, ``d`` = 64, ``theta``
= 50000; ``dim(r) = d ln(4096 / (2 pi r)) / (2 ln theta)``; ``low =
floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``, clipped to
``[0, d - 1]`` (19 and 20 here); ``ramp_i = clip((i - low) / (high -
low), 0, 1)``; ``inv_freq_i = f_i (1 - ramp_i) + (f_i / 32) ramp_i``.
Cosine and sine carry ``m(mscale) / m(mscale_all_dim)`` = 1, ``m(s) = 0.1
s ln(32) + 1``.  Per head ``[k_nope 128 | v 128] = c_kv W_kvb``; scores
``(q_nope . k_nope + q_rot . k_rot) * 192^-1/2 * m(1)^2`` (``m(1)`` =
1.34657), causal, softmax; ``Attn = concat_h(P v) W_o``.

**MLP.**  Published layer 0 (``first_k_dense_replace`` 1): one SwiGLU of
width 18432, ``W_d(silu(W_g x) * W_u x)``.  Every other layer: ``s =
sigmoid(x W_r^T)`` over 384 experts; the choice is the 8 largest of ``s +
b`` (``b`` the correction bias; ``n_group`` 1 and ``topk_group`` 1: the
group stage keeps everything); their weights are ``s`` (without ``b``)
normalised to sum 1 (``norm_topk_prob``), times 2.827; an expert is a
SwiGLU of width 2048, and one shared expert of the same form acts on
every token.

**The share.**  A chip of the stated deployment holds experts
``[expert_offset, expert_offset + experts_held)`` of each layer and a
slice of the vocabulary.  The router still scores all 384 experts; only
the chosen experts that are held add to the result, what the absent
ones would add is left out, and that partial result goes on to the next
layer.  The head gives logits over the held rows of the vocabulary.

Departures and readings are in the configuration file's ``assumed``:
the rotary pairing, the seeded initialisation, weights stored ``(out,
in)`` and the experts ``(expert, in, out)``, no multi-token prediction
(``num_nextn_predict_layers`` 0).

``compute="fp8"`` is the control of "How correct is decided": the same
mathematics with both operands of every matrix product rounded to
float8 e4m3 under one scale a tensor, the precision below the bfloat16
the configuration states; ``"bf16"`` rounds them to bfloat16, the
stated precision itself, and decides nothing.

Attention runs a block of ``QUERY_BLOCK`` queries at a time over all
the keys (masked), and the callers go layer by layer, so that 17k
positions in float32 fit one chip.
"""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the equations need, from the configuration file's keys."""
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    rope_theta: float
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    experts: int                # the router's width
    experts_held: int
    expert_offset: int
    top_k: int
    n_group: int
    topk_group: int
    scale: float
    eps: float
    mlps: tuple                 # "dense" | "moe" a layer


def sizes_of(config):
    """``Sizes`` of a configuration file (as ``harness.resolve`` hands it
    over).  Layer ``i`` kept here is published layer ``i +
    layer_offset``, with the dense MLP where that is below
    ``first_k_dense_replace``."""
    n = int(config["num_layers"])
    off = int(config.get("layer_offset", 0))
    dense = int(config["first_k_dense_replace"])
    held = int(config["n_routed_experts"])
    y = config["rope_scaling"]
    return Sizes(
        hidden=int(config["hidden_size"]),
        heads=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]),
        kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]),
        rope_theta=float(config["rope_theta"]),
        yarn_factor=float(y["factor"]),
        yarn_original=int(y["original_max_position_embeddings"]),
        beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
        mscale=float(y["mscale"]),
        mscale_all_dim=float(y["mscale_all_dim"]),
        experts=int(config.get("published", {}).get("n_routed_experts",
                                                    held)),
        experts_held=held,
        expert_offset=int(config.get("expert_offset", 0)),
        top_k=int(config["num_experts_per_tok"]),
        n_group=int(config["n_group"]),
        topk_group=int(config["topk_group"]),
        scale=float(config["routed_scaling_factor"]),
        eps=float(config["rms_norm_eps"]),
        mlps=tuple("dense" if i + off < dense else "moe"
                   for i in range(n)))


# ------------------------------------------------------------- pieces
def _q8(x):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _q16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_ROUNDED = {"f32": lambda x: x, "bf16": _q16, "fp8": _q8}


def _mm(spec, a, b, compute):
    if compute not in _ROUNDED:
        raise ValueError(f"unknown compute {compute!r}")
    q = _ROUNDED[compute]
    return jnp.einsum(spec, q(a), q(b), precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def yarn_range(c):
    """``(low, high)`` of the blend, before the ramp."""
    d = c.rope
    dim = lambda turns: d * math.log(
        c.yarn_original / (2 * math.pi * turns)) / (2 * math.log(
            c.rope_theta))
    return (max(math.floor(dim(c.beta_fast)), 0),
            min(math.ceil(dim(c.beta_slow)), d - 1))


def mscale_of(factor, s):
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(c):
    d = c.rope
    f = c.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    low, high = yarn_range(c)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / c.yarn_factor * ramp


def rope(x, pos, c):
    """Rotary positions on the last axis of ``x`` (T, ..., d), pairs
    ``(x[2j], x[2j+1])`` turned by ``pos * inv_freq_j``; cosine and sine
    times ``m(mscale) / m(mscale_all_dim)``."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(c)  # (T, d/2)
    m = mscale_of(c.yarn_factor, c.mscale) \
        / mscale_of(c.yarn_factor, c.mscale_all_dim)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), \
        (jnp.sin(ang) * m).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def swiglu(x, gate, up, down, compute):
    """``gate``, ``up`` (F, D), ``down`` (D, F)."""
    h = jax.nn.silu(_mm("td,fd->tf", x, gate, compute)) \
        * _mm("td,fd->tf", x, up, compute)
    return _mm("tf,df->td", h, down, compute)


# ---------------------------------------------------------------- MLA
def softmax_scale(c):
    return (c.nope + c.rope) ** -0.5 \
        * mscale_of(c.yarn_factor, c.mscale_all_dim) ** 2


def mla(x, w, c, compute="f32"):
    """``x`` (T, D) -> (T, D); positions 0 .. T-1, causal."""
    T, H = x.shape[0], c.heads
    pos = jnp.arange(T)
    c_q = rms_norm(_mm("td,rd->tr", x, w["mla_qa_weight"], compute),
                   w["mla_q_norm_weight"], c.eps)
    q = _mm("tr,er->te", c_q, w["mla_qb_weight"], compute).reshape(
        T, H, c.nope + c.rope)
    q_nope, q_rot = q[..., :c.nope], rope(q[..., c.nope:], pos, c)
    kva = _mm("td,ed->te", x, w["mla_kva_weight"], compute)
    c_kv = rms_norm(kva[:, :c.kv_rank], w["mla_kv_norm_weight"], c.eps)
    k_rot = rope(kva[:, c.kv_rank:], pos, c)                    # (T, 64)
    kv = _mm("tr,er->te", c_kv, w["mla_kvb_weight"], compute).reshape(
        T, H, c.nope + c.v_dim)
    k_nope, v = kv[..., :c.nope], kv[..., c.nope:]
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def some_queries(lo):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, lo, block)
        qr = jax.lax.dynamic_slice_in_dim(q_rot, lo, block)
        s = (_mm("thd,shd->hts", qn, k_nope, compute)
             + _mm("thd,sd->hts", qr, k_rot, compute)) * softmax_scale(c)
        causal = (lo + jnp.arange(block))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm("hts,shd->thd", p, v, compute)

    o = jax.lax.map(some_queries, jnp.arange(0, T, block))
    return _mm("te,de->td", o.reshape(T, H * c.v_dim), w["mla_o_weight"],
               compute)


# ---------------------------------------------------------------- MoE
def route(scores, bias, c):
    """The chosen experts (T, top_k) and their weights: group-limited
    top-k on ``scores + bias`` (a group scores the sum of its two best,
    the ``topk_group`` best groups stay; with one group everything
    stays), weights from ``scores`` alone."""
    T, E = scores.shape
    sel = scores + bias
    groups = sel.reshape(T, c.n_group, E // c.n_group)
    best2 = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)            # (T, G)
    _, kept = jax.lax.top_k(best2, c.topk_group)
    keep = jnp.zeros((T, c.n_group), bool).at[
        jnp.arange(T)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(keep, E // c.n_group, axis=1), sel,
                       -jnp.inf)
    _, idx = jax.lax.top_k(masked, c.top_k)
    wts = jnp.take_along_axis(scores, idx, axis=1)
    wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-20) * c.scale
    return idx, wts


def moe(x, w, c, compute="f32"):
    """Routed part of the experts held + the shared expert; also the
    choices (T, top_k)."""
    T = x.shape[0]
    scores = jax.nn.sigmoid(_mm("td,ed->te", x, w["router_weight"],
                                compute))
    idx, wts = route(scores, w["router_bias"], c)
    comb = jnp.zeros((T, c.experts), jnp.float32).at[
        jnp.arange(T)[:, None], idx].set(wts)
    comb = comb[:, c.expert_offset:c.expert_offset + c.experts_held]

    def one(acc, e):
        gate, up, down, cw = e                     # (D, F), (D, F), (F, D)
        h = jax.nn.silu(_mm("td,df->tf", x, gate, compute)) \
            * _mm("td,df->tf", x, up, compute)
        return acc + cw[:, None] * _mm("tf,fd->td", h, down, compute), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w["experts_gate_weight"], w["experts_up_weight"],
         w["experts_down_weight"], comb.T))
    shared = swiglu(x, w["shared_gate_weight"], w["shared_up_weight"],
                    w["shared_down_weight"], compute)
    return routed + shared, idx


# -------------------------------------------------------------- layers
@functools.partial(jax.jit, static_argnames=("c", "mlp", "compute"))
def layer(h, w, c, mlp, compute="f32"):
    """One block over one sequence ``h`` (T, D); ``w`` holds this
    layer's leaves without the ``layer<i>_`` prefix.  Returns the new
    hidden states and the router's choices (or None)."""
    h = h + mla(rms_norm(h, w["norm1_weight"], c.eps), w, c, compute)
    x = rms_norm(h, w["norm2_weight"], c.eps)
    if mlp == "dense":
        return h + swiglu(x, w["mlp_gate_weight"], w["mlp_up_weight"],
                          w["mlp_down_weight"], compute), None
    y, idx = moe(x, w, c, compute)
    return h + y, idx


def embed(params, tokens):
    return jnp.take(params["tok_embed_weight"], tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("c", "compute"))
def head(h, params, c, compute="f32"):
    x = rms_norm(h, params["final_norm_weight"], c.eps)
    return _mm("td,vd->tv", x, params["lm_head_weight"], compute)


def layer_leaves(params, i):
    p = f"layer{i}_"
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}


def logits(params, tokens, c, compute="f32"):
    """``tokens`` (T,) int32 -> logits (T, V_held) float32, from a flat
    dict of leaves under the program's names (``layer<i>_<x>``)."""
    h = embed(params, tokens)
    for i, mlp in enumerate(c.mlps):
        h, _ = layer(h, layer_leaves(params, i), c, mlp, compute)
    return head(h, params, c, compute)


# ------------------------------------------------------------- serving
def gaps_from_logits(lg, tokens, first, count, picks=None):
    """Teacher-forced over one request: ``lg`` (T, V) are the logits at
    every position of ``tokens`` (prompt, then the served tokens, then
    padding); positions ``first .. first+count-1`` are the served ones.
    Returns, for each position after the first, by how much the logit
    of the token there (or of ``picks`` there) lies below the best
    logit -- nought outside the served range -- and the best token."""
    pred = lg[:-1]
    target = tokens[1:] if picks is None else picks
    best = jnp.max(pred, axis=-1)
    got = jnp.take_along_axis(pred, target[:, None], axis=1)[:, 0]
    pos = jnp.arange(1, tokens.shape[0])
    served = (pos >= first) & (pos < first + count)
    return jnp.where(served, best - got, 0.0), jnp.argmax(pred, axis=-1)
