"""Plain reference: the Ling-3.0-flash language model in float32
``jax.numpy``.

Written from the published ``config.json`` (``model_type``
``bailing_hybrid``) and the papers its layers come from: Kimi Delta
Attention (KDA, arXiv:2510.26692), multi-head latent attention in the
DeepSeek-V2 form (arXiv:2405.04434), and the DeepSeek-V3 group-limited
sigmoid router with an expert bias (arXiv:2412.19437).  One sequence at
a time, no cache, no kernel, no batching; every matrix product at
``highest`` precision.  It imports nothing of the program.

The equations.  ``x`` is a token's hidden vector (2560), RMSNorm
``n(x) = x / sqrt(mean(x^2) + 1e-6) * w``, and a block is

    h = x + Mix(n1(x))          y = h + MLP(n2(h))

**KDA layer** (32 heads, ``d_k = d_v = 128``).  With ``Conv`` the causal
depthwise convolution of kernel 4 over the sequence (``Conv(u)_t =
sum_j c_j u_{t-3+j}``, zeros before the start) and ``SiLU`` after it
(``linear_silu``):

    q, k, v = SiLU(Conv(x W_q)), SiLU(Conv(x W_k)), SiLU(Conv(x W_v))
    q <- q / |q| * d_k^-1/2,  k <- k / |k|          (per head, L2)
    g = -5 * sigmoid(exp(A_log) * (x W_a + dt_bias))   (per channel; the
        "safe gate" with kda_lower_bound -5), alpha = exp(g) in (e^-5, 1)
    beta = sigmoid(x W_beta)                           (one a head)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                      (S in R^{128x128} a head, S_0 = 0)
    Mix = concat_h( RMSNorm_head(o_t) * sigmoid(x W_g)_h ) W_o

``W_a`` is full rank, 2560 -> 4096 (``no_kda_lora``); ``W_g`` is 2560 ->
32, one gate a head (``head_wise``).  The recurrence is computed token
by token with ``lax.scan``.

**MLA layer** (``q_lora_rank`` null).  ``q = x W_q`` -> per head
``[q_nope 128 | q_rope 64]``; ``[c | k_rope] = x W_kva`` (512 | 64),
``c <- RMSNorm(c)``; per head ``[k_nope 128 | v 128] = c W_kvb``; rotary
positions (theta 6e6, interleaved pairs) on ``q_rope`` and on the one
``k_rope`` all heads share; causal softmax of ``(q_nope . k_nope +
q_rope . k_rope) / sqrt(192)``; ``Mix = concat_h(o) W_o``.

**MoE MLP**.  ``s = sigmoid(x W_r)``, 512 scores; the choice is made on
``s + b`` (``b`` the expert bias): 8 groups of 64, a group's score the
sum of its two best, the 4 best groups kept, the 8 best experts among
them chosen; their weights are ``s`` (without ``b``), normalised to sum
1, times 2.5.  An expert is ``W_down(SiLU(x W_gate) * (x W_up))``, width
768; one shared expert of the same form acts on every token.  The
leading layer's MLP is one dense SwiGLU of width 6144.

**The share.**  A chip of the stated deployment holds experts
``[expert_offset, expert_offset + experts_held)`` of each layer and a
slice of the vocabulary.  The router still scores all 512 experts; only
the chosen experts that are held add to the result, what the absent
ones would add is left out, and that partial result goes on to the next
layer.  The head gives logits over the held rows of the vocabulary.

Departures and readings, all stated in the configuration file's
``assumed``: ``use_qk_norm`` is read as the RMSNorm on the latent ``c``
(there is no per-head norm after the up-projection); ``A_log`` is one
number a head; the L2 norms add 1e-6 under the root; weights are stored
``(out, in)`` as the program's linear layers store them, the experts as
``(expert, in, out)``; no vision tower, no multi-token prediction.

``compute="fp8"`` is the control of "How correct is decided": the same
mathematics with both operands of every matrix product rounded to
float8 e4m3 under one scale a tensor, the precision below the bfloat16
that the configuration states.  ``compute="bf16"`` rounds both operands
to bfloat16 instead, the stated precision itself: it decides nothing,
and is there to count how often that precision alone changes a token's
choice of experts (``families/ling.py:served``).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the equations need, from the configuration file's keys."""
    hidden: int
    heads: int
    head_dim: int               # d_k = d_v of KDA
    conv: int
    kda_lower: float
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    rope_theta: float
    experts: int                # the router's width
    experts_held: int
    expert_offset: int
    top_k: int
    n_group: int
    topk_group: int
    scale: float
    eps: float
    mixers: tuple               # "kda" | "mla" a layer
    mlps: tuple                 # "dense" | "moe" a layer


def sizes_of(config):
    """``Sizes`` of a configuration file (as ``harness.resolve`` hands it
    over).  Layer ``i`` kept here is published layer ``i +
    layer_offset``; a published layer ``l`` is MLA where ``(l + 1) %
    layer_group_size == 0``, else KDA; the first
    ``first_k_dense_replace`` layers kept have the dense MLP."""
    n = int(config["num_layers"])
    off = int(config.get("layer_offset", 0))
    period = int(config["layer_group_size"])
    mixers = tuple("mla" if (i + off + 1) % period == 0 else "kda"
                   for i in range(n))
    dense = int(config["first_k_dense_replace"])
    mlps = tuple("dense" if i < dense else "moe" for i in range(n))
    held = int(config["num_experts"])
    return Sizes(
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
        experts=int(config.get("published", {}).get("num_experts", held)),
        experts_held=held,
        expert_offset=int(config.get("expert_offset", 0)),
        top_k=int(config["num_experts_per_tok"]),
        n_group=int(config["n_group"]),
        topk_group=int(config["topk_group"]),
        scale=float(config["routed_scaling_factor"]),
        eps=float(config["rms_norm_eps"]),
        mixers=mixers, mlps=mlps)


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


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + 1e-6)


def short_conv(u, kernel):
    """Causal depthwise convolution: ``u`` (T, C), ``kernel`` (C, K);
    ``y_t = sum_j kernel[:, j] * u_{t-(K-1)+j}``, zeros before 0."""
    T, K = u.shape[0], kernel.shape[1]
    pad = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    return sum(pad[j:j + T] * kernel[:, j] for j in range(K))


def rope(x, pos, theta):
    """Rotary positions on the last axis of ``x`` (T, ..., d), pairs
    ``(x[2j], x[2j+1])`` turned by ``pos * theta^(-2j/d)``."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freq          # (T, d/2)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def swiglu(x, gate, up, down, compute):
    """``gate``, ``up`` (F, D), ``down`` (D, F)."""
    h = jax.nn.silu(_mm("td,fd->tf", x, gate, compute)) \
        * _mm("td,fd->tf", x, up, compute)
    return _mm("tf,df->td", h, down, compute)


# ---------------------------------------------------------------- KDA
def kda(x, w, c, compute="f32"):
    """``x`` (T, D) -> (T, D) and the state after the last token,
    (H, d_k, d_v)."""
    T = x.shape[0]
    H, d = c.heads, c.head_dim

    def branch(name):
        u = _mm("td,ed->te", x, w[f"kda_{name}_weight"], compute)
        return jax.nn.silu(short_conv(u, w[f"kda_{name}_conv"])
                           ).reshape(T, H, d)

    q, k, v = branch("q"), branch("k"), branch("v")
    q = _l2(q) * d ** -0.5
    k = _l2(k)
    a = (_mm("td,ed->te", x, w["kda_a_weight"], compute)
         + w["kda_dt_bias"]).reshape(T, H, d)
    g = c.kda_lower * jax.nn.sigmoid(
        jnp.exp(w["kda_A_log"])[None, :, None] * a)
    alpha = jnp.exp(g)
    beta = jax.nn.sigmoid(_mm("td,hd->th", x, w["kda_beta_weight"],
                              compute))

    def step(S, inp):
        q_t, k_t, v_t, a_t, b_t = inp
        S = a_t[..., None] * S
        pred = jnp.einsum("hk,hkv->hv", k_t, S, precision=HIGHEST)
        S = S + (b_t[:, None] * k_t)[..., None] * (v_t - pred)[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S, precision=HIGHEST)

    S, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32),
                        (q, k, v, alpha, beta))
    gate = jax.nn.sigmoid(_mm("td,hd->th", x, w["kda_g_weight"], compute))
    o = rms_norm(o, w["kda_onorm_weight"], c.eps) * gate[..., None]
    return _mm("te,de->td", o.reshape(T, H * d), w["kda_o_weight"],
               compute), S


# ---------------------------------------------------------------- MLA
def mla(x, w, c, compute="f32"):
    """``x`` (T, D) -> (T, D); positions 0 .. T-1, causal."""
    T, H = x.shape[0], c.heads
    pos = jnp.arange(T)
    q = _mm("td,ed->te", x, w["mla_q_weight"], compute).reshape(
        T, H, c.nope + c.rope)
    q_nope, q_rope = q[..., :c.nope], q[..., c.nope:]
    kva = _mm("td,ed->te", x, w["mla_kva_weight"], compute)
    lat = rms_norm(kva[:, :c.kv_rank], w["mla_kv_norm_weight"], c.eps)
    k_rope = rope(kva[:, c.kv_rank:], pos, c.rope_theta)        # (T, 64)
    q_rope = rope(q_rope, pos, c.rope_theta)
    kv = _mm("tr,er->te", lat, w["mla_kvb_weight"], compute).reshape(
        T, H, c.nope + c.v_dim)
    k_nope, v = kv[..., :c.nope], kv[..., c.nope:]
    s = (_mm("thd,shd->hts", q_nope, k_nope, compute)
         + _mm("thd,sd->hts", q_rope, k_rope, compute)) \
        / jnp.sqrt(jnp.float32(c.nope + c.rope))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("hts,shd->thd", p, v, compute).reshape(T, H * c.v_dim)
    return _mm("te,de->td", o, w["mla_o_weight"], compute)


# ---------------------------------------------------------------- MoE
def route(scores, bias, c):
    """The chosen experts (T, top_k) and their weights: group-limited
    top-k on ``scores + bias``, weights from ``scores`` alone."""
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
    choices (T, top_k), for whoever counts flips."""
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
@functools.partial(jax.jit, static_argnames=("c", "mixer", "mlp",
                                             "compute"))
def layer(h, w, c, mixer, mlp, compute="f32"):
    """One block over one sequence ``h`` (T, D); ``w`` holds this
    layer's leaves without the ``layer<i>_`` prefix.  Returns the new
    hidden states and the router's choices (or None)."""
    x = rms_norm(h, w["norm1_weight"], c.eps)
    h = h + (kda(x, w, c, compute)[0] if mixer == "kda"
             else mla(x, w, c, compute))
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
    for i, (mixer, mlp) in enumerate(zip(c.mixers, c.mlps)):
        h, _ = layer(h, layer_leaves(params, i), c, mixer, mlp, compute)
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
