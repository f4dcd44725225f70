"""Plain reference: the Olmo-Hybrid language model in float32
``jax.numpy``.

Written from the published ``config.json`` (``model_type``
``olmo_hybrid``) and the papers its layers come from: gated DeltaNet
(arXiv:2412.06464) with negative eigenvalues (arXiv:2411.12537), and
OLMo 2's block (arXiv:2501.00656).  One sequence at a time, no cache, no
chunks, no kernel, no batching; every matrix product at ``highest``
precision.  It imports nothing of the program.

The equations.  ``x`` is a token's hidden vector (3840), ``n(x) = x /
sqrt(mean(x^2) + 1e-6) * w`` an RMSNorm, and a block is OLMo 2's, a norm
AFTER each sublayer and none before it:

    h = x + n_a(Mix(x))          y = h + n_b(W_down(SiLU(W_gate h) * W_up h))

(SwiGLU 11,008 wide).  After the last layer a final RMSNorm and the
untied head over the 100,352 ids.

**Gated DeltaNet** (``linear_attention``; ``H`` = 30 heads, ``d_k`` =
96, ``d_v`` = 192).  With ``Conv`` the causal depthwise convolution of 4
taps over positions, a channel at a time, no bias (``Conv(u)_t = sum_j
c_j u_{t-3+j}``, zeros before the start):

    q, k, v = SiLU(Conv(x W_q)), SiLU(Conv(x W_k)), SiLU(Conv(x W_v))
    q_h <- q_h / |q_h| * d_k^-1/2,  k_h <- k_h / |k_h|   (1e-6 under the root)
    beta_h = 2 sigmoid(x w_b,h)                             in (0, 2)
    g_h = -exp(A_log_h) softplus(x w_a,h + dt_bias_h)        unbounded below
    S_t = e^{g_t} (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                      (S in R^{96x192} a head, S_0 = 0)
    Mix = concat_h( n_192(o_h) * SiLU(x W_g)_h ) W_o

``n_192`` one weight of 192 the heads share; ``W_g`` 3840 -> 5760.  The
recurrence is computed token by token with ``lax.scan``.

**Attention** (``full_attention``; 30 heads of 128, each with K/V of
its own).  ``q = n_q(x W_q)``, ``k = n_k(x W_k)`` (QK-norm over the
whole 3840-wide projection), ``v = x W_v``; causal ``softmax(q k^T /
sqrt(128)) v`` a head, NO rotary (``rope_theta`` null); ``Mix = concat_h
o W_o``.  Computed in blocks of query rows, each over every key.

Departures and readings, all stated in the configuration file's
``assumed``: the norm placement and the whole-projection QK-norm are
OLMo 2/3's (the published row has no key for either); ``beta``'s factor
2 and the SiLU-gated output norm are the gated-DeltaNet reference
code's form with ``allow_neg_eigval``; weights are stored ``(out, in)``
as the program's linear layers store them; no bias anywhere.

``compute="fp8"`` is the control of "How correct is decided": the same
mathematics with both operands of every matrix product rounded to
float8 e4m3 under one scale a tensor, the precision below the bfloat16
that the configuration states.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmark.reference.ling import (HIGHEST, _l2, _mm, embed,
                                      gaps_from_logits, layer_leaves,
                                      rms_norm, short_conv, swiglu)

__all__ = ["Sizes", "sizes_of", "gdn", "attention", "layer", "embed",
           "head", "layer_leaves", "gaps_from_logits"]

# query rows a block of the attention's scores
ROWS = 512


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the equations need, from the configuration file's keys."""
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


def sizes_of(config):
    """``Sizes`` of a configuration file (as ``harness.resolve`` hands it
    over).  Layer ``i`` kept here is published layer ``i +
    layer_offset`` of ``layer_types``."""
    n = int(config["num_layers"])
    off = int(config.get("layer_offset", 0))
    kinds = {"linear_attention": "gdn", "full_attention": "attn"}
    D, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    return Sizes(
        hidden=D, heads=H, head_dim=int(config.get("head_dim") or D // H),
        lin_heads=int(config["linear_num_key_heads"]),
        d_k=int(config["linear_key_head_dim"]),
        d_v=int(config["linear_value_head_dim"]),
        conv=int(config["linear_conv_kernel_dim"]),
        neg_eigval=bool(config["linear_allow_neg_eigval"]),
        eps=float(config["rms_norm_eps"]),
        mixers=tuple(kinds[t] for t in config["layer_types"][off:off + n]))


# ----------------------------------------------------------- the mixers
def gdn(x, w, c, compute="f32"):
    """``x`` (T, D) -> (T, D) and the state after the last token,
    (H, d_k, d_v)."""
    T, H, dk, dv = x.shape[0], c.lin_heads, c.d_k, c.d_v

    def branch(name, d):
        u = _mm("td,ed->te", x, w[f"gdn_{name}_weight"], compute)
        return jax.nn.silu(short_conv(u, w[f"gdn_{name}_conv"])
                           ).reshape(T, H, d)

    q = _l2(branch("q", dk)) * dk ** -0.5
    k = _l2(branch("k", dk))
    v = branch("v", dv)
    beta = (2.0 if c.neg_eigval else 1.0) * jax.nn.sigmoid(
        _mm("td,hd->th", x, w["gdn_b_weight"], compute))
    g = -jnp.exp(w["gdn_A_log"]) * jax.nn.softplus(
        _mm("td,hd->th", x, w["gdn_a_weight"], compute) + w["gdn_dt_bias"])

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        S = jnp.exp(g_t)[:, None, None] * S
        pred = jnp.einsum("hk,hkv->hv", k_t, S, precision=HIGHEST)
        S = S + (b_t[:, None] * k_t)[..., None] * (v_t - pred)[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S, precision=HIGHEST)

    S, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    gate = jax.nn.silu(_mm("td,ed->te", x, w["gdn_g_weight"], compute))
    o = rms_norm(o, w["gdn_onorm_weight"], c.eps) * gate.reshape(T, H, dv)
    return _mm("te,de->td", o.reshape(T, H * dv), w["gdn_o_weight"],
               compute), S


def attention(x, w, c, compute="f32"):
    """``x`` (T, D) -> (T, D); positions 0 .. T-1, causal, no rotary.
    ``ROWS`` query rows at a time where ``T`` is a multiple of it, else
    all at once."""
    T, H, dh = x.shape[0], c.heads, c.head_dim
    q = rms_norm(_mm("td,ed->te", x, w["attn_q_weight"], compute),
                 w["attn_q_norm_weight"], c.eps).reshape(T, H, dh)
    k = rms_norm(_mm("td,ed->te", x, w["attn_k_weight"], compute),
                 w["attn_k_norm_weight"], c.eps).reshape(T, H, dh)
    v = _mm("td,ed->te", x, w["attn_v_weight"], compute).reshape(T, H, dh)
    rows = T if T % ROWS else ROWS

    def block(r0):
        qb = jax.lax.dynamic_slice_in_dim(q, r0, rows)
        s = _mm("thd,shd->hts", qb, k, compute) / jnp.sqrt(jnp.float32(dh))
        seen = (r0 + jnp.arange(rows))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm("hts,shd->thd", p, v, compute)

    o = jax.lax.map(block, jnp.arange(0, T, rows))       # (T/rows, rows..)
    return _mm("te,de->td", o.reshape(T, H * dh), w["attn_o_weight"],
               compute)


# -------------------------------------------------------------- layers
@functools.partial(jax.jit, static_argnames=("c", "mixer", "compute"))
def layer(h, w, c, mixer, compute="f32"):
    """One block over one sequence ``h`` (T, D); ``w`` holds this
    layer's leaves without the ``layer<i>_`` prefix."""
    y = gdn(h, w, c, compute)[0] if mixer == "gdn" \
        else attention(h, w, c, compute)
    h = h + rms_norm(y, w["mix_norm_weight"], c.eps)
    y = swiglu(h, w["mlp_gate_weight"], w["mlp_up_weight"],
               w["mlp_down_weight"], compute)
    return h + rms_norm(y, w["mlp_norm_weight"], c.eps)


@functools.partial(jax.jit, static_argnames=("c", "compute"))
def head(h, params, c, compute="f32"):
    x = rms_norm(h, params["final_norm_weight"], c.eps)
    return _mm("td,vd->tv", x, params["lm_head_weight"], compute)


def logits(params, tokens, c, compute="f32"):
    """``tokens`` (T,) int32 -> logits (T, V) float32, from a flat dict
    of leaves under the program's names (``layer<i>_<x>``)."""
    h = embed(params, tokens)
    for i, mixer in enumerate(c.mixers):
        h = layer(h, layer_leaves(params, i), c, mixer, compute)
    return head(h, params, c, compute)
