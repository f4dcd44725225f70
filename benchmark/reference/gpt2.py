"""Plain reference: a GPT-2 style decoder in float32 ``jax.numpy``.

Written from the published description (Radford et al. 2019; the
Cerebras-GPT models keep the architecture, arXiv:2304.03208): learned
token and position embeddings, pre-LayerNorm blocks of full multi-head
causal attention and a two-matrix feed-forward, a final LayerNorm and a
linear head.  No kernel, no cache, no batching tricks; matrix products
at ``highest`` precision.  It imports nothing of the program.

Departures, all stated in the configuration files: the head is a matrix
of its own (published: tied to the token embedding); the activation is
the tanh form of gelu (what the program computes); weights are
``(out, in)`` as the program's linear layers store them.

``compute="fp8"`` is the control of "How correct is decided": the same
mathematics with both operands of every matrix product rounded to
float8 under one scale a tensor, the precision below the bfloat16 that
the configurations state -- e4m3 for the operands of the forward pass
and e5m2 for the gradient that flows back into each product, the split
float8 training recipes use (Micikevicius et al., arXiv:2209.05433).
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def _round8(x, dtype, top):
    """``x`` rounded to a float8 type under one scale for the tensor
    (its largest magnitude lands on ``top``, the type's largest)."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _q8(x):
    """Round ``x`` to float8 e4m3, with the gradient passed straight
    through."""
    q = _round8(x, jnp.float8_e4m3fn, 448.0)
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _q8_grad(y):
    """``y`` as it is; the gradient that flows back through it is
    rounded to float8 e5m2."""
    return y


_q8_grad.defvjp(lambda y: (y, None),
                lambda _res, g: (_round8(g, jnp.float8_e5m2, 57344.0),))


def _mm(spec, a, b, compute):
    if compute == "f32":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if compute != "fp8":
        raise ValueError(f"unknown compute {compute!r}")
    return _q8_grad(jnp.einsum(spec, _q8(a), _q8(b), precision=HIGHEST))


def _ln(x, g, b):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + LN_EPS) * g + b


def _block(h, w, n_head, compute):
    """One block over ``h`` (B, T, D); ``w`` holds this layer's leaves."""
    B, T, D = h.shape
    dh = D // n_head
    x = _ln(h, w["ln1_gamma"], w["ln1_beta"])

    def heads(name):
        y = _mm("btd,ed->bte", x, w[name + "_weight"], compute) \
            + w[name + "_bias"]
        return y.reshape(B, T, n_head, dh).transpose(0, 2, 1, 3)

    q, k, v = heads("q"), heads("k"), heads("v")
    s = _mm("bhqd,bhkd->bhqk", q, k, compute) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = _mm("bhqk,bhkd->bhqd", p, v, compute)
    a = a.transpose(0, 2, 1, 3).reshape(B, T, D)
    h = h + _mm("btd,ed->bte", a, w["proj_weight"], compute) \
        + w["proj_bias"]
    x = _ln(h, w["ln2_gamma"], w["ln2_beta"])
    f = _mm("btd,fd->btf", x, w["ffn_in_weight"], compute) \
        + w["ffn_in_bias"]
    f = jax.nn.gelu(f, approximate=True)
    return h + _mm("btf,df->btd", f, w["ffn_out_weight"], compute) \
        + w["ffn_out_bias"]


def logits(params, tokens, n_head, compute="f32"):
    """``tokens`` (B, T) int32 -> logits (B, T, V) float32.  ``params``
    is the stacked layout of ``weights.make(..., stack_layers=L)``."""
    T = tokens.shape[1]
    h = jnp.take(params["tok_embed_weight"], tokens, axis=0) \
        + params["pos_embed"][:, :T]

    @jax.checkpoint
    def body(h, w):
        return _block(h, w, n_head, compute), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    h = _ln(h, params["final_ln_gamma"], params["final_ln_beta"])
    return _mm("btd,vd->btv", h, params["lm_head_weight"], compute) \
        + params["lm_head_bias"]


# ------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("n_head", "compute"))
def served_gaps(params, tokens, first, count, n_head, compute="f32"):
    """Teacher-forced over one request.  ``tokens`` (T,) holds the
    prompt followed by the served tokens, padded on the right;
    positions ``first .. first+count-1`` are the served ones.  Returns,
    for each position of ``tokens`` (nought outside the served range),
    by how much the logit of the token at that position lies below the
    best logit the model gives there -- and the token ``compute`` itself
    puts first at each position."""
    lg = logits(params, tokens[None], n_head, compute)[0]      # (T, V)
    pred = lg[:-1]                       # row p predicts token p+1
    target = tokens[1:]
    best = jnp.max(pred, axis=-1)
    got = jnp.take_along_axis(pred, target[:, None], axis=1)[:, 0]
    pos = jnp.arange(1, tokens.shape[0])
    served = (pos >= first) & (pos < first + count)
    return jnp.where(served, best - got, 0.0), jnp.argmax(pred, axis=-1)


@functools.partial(jax.jit, static_argnames=("n_head",))
def gaps_of(params, tokens, picks, first, count, n_head):
    """The float32 model's gap for ``picks`` (T-1,), the tokens some
    other computation put first at each position after the same prefix
    (the control: it need not decode)."""
    lg = logits(params, tokens[None], n_head, "f32")[0][:-1]
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, picks[:, None], axis=1)[:, 0]
    pos = jnp.arange(1, tokens.shape[0])
    served = (pos >= first) & (pos < first + count)
    return jnp.where(served, best - got, 0.0)


# ------------------------------------------------------------ training
def _sum_ce(params, tokens, labels, n_head, compute):
    lg = logits(params, tokens, n_head, compute)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


@functools.partial(jax.jit, static_argnames=("n_head", "compute"),
                   donate_argnums=(1,))
def accumulate_grads(params, acc, tokens, labels, n_head, compute="f32"):
    """Add this block of rows' gradient of the SUMMED cross-entropy to
    ``acc``; returns (acc, the block's summed cross-entropy)."""
    loss, g = jax.value_and_grad(_sum_ce)(params, tokens, labels, n_head,
                                          compute)
    return jax.tree_util.tree_map(jnp.add, acc, g), loss
