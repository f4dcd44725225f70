"""The gated delta rule's forms, shared by the decoder families that
hold a recurrent state a head (``models/ling.py``: Kimi Delta
Attention, a gate a channel; ``models/olmo_hybrid.py``: gated DeltaNet,
a gate a head).

One recurrence, ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} +
beta_t k_t v_t^T`` and ``o_t = S_t^T q_t``, ``S`` a ``(d_k, d_v)``
float32 matrix a head, ``alpha_t = e^{g_t}``; a gate a head is the
channel gate broadcast over ``d_k`` (``Diag(alpha) = alpha I`` commutes
with the update), and ``beta`` may run up to 2 (negative eigenvalues of
the transition):

- :func:`kda_recurrent_step`: one position for every row (the decode
  step);
- :func:`kda_chunked`: a sequence in chunks of 16, a gate a channel
  bounded below (KDA's "safe gate", -5 a token);
- :func:`gdn_chunked`: a sequence in chunks of 64, a gate a head and
  no bound below (on a TPU the same algebra runs as one Pallas kernel,
  ``ops/gdn_scan.py``; this XLA form runs everywhere else and is the
  kernel's reference).

And what both families put before it: the L2 norm of ``q`` and ``k``
(:func:`l2norm`) and the causal depthwise convolution whose last inputs
a slot keeps as state (:func:`short_conv`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["kda_recurrent_step", "kda_chunked", "gdn_chunked", "l2norm",
           "short_conv", "KDA_CHUNK", "GDN_CHUNK"]

HIGHEST = jax.lax.Precision.HIGHEST
KDA_CHUNK = 16      # |g| <= 5 a token: exp(+-40) stays well inside float32
GDN_CHUNK = 64      # every exponent <= 0: any chunk is safe


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def l2norm(x):
    """``x`` over its last axis to unit length, 1e-6 under the root."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + 1e-6)


def short_conv(u, kernel, view, name):
    """The causal depthwise convolution of ``u`` (N, C) by ``kernel``
    (C, K), in float32, with the inputs before ``u`` from the view's
    state ``name``, ``(K - 1, C)`` (the step: a slot's last inputs; a
    prefill: the chunk before's, or zeros), which takes the last ``K -
    1`` real inputs in their place."""
    K = kernel.shape[1]
    N = u.shape[0]
    if view.step:
        win = jnp.concatenate([view.state(name), u[:, None]], 1)
        view.set_state(name, win[:, 1:])
        return jnp.einsum("nkc,ck->nc", win.astype(jnp.float32),
                          kernel.astype(jnp.float32))
    pad = jnp.concatenate([view.state(name).astype(u.dtype), u])
    # inputs at positions length-3 .. length-1
    view.set_state(name, jax.lax.dynamic_slice_in_dim(
        pad, view.length, K - 1))
    return sum(pad[j:j + N].astype(jnp.float32)
               * kernel[:, j].astype(jnp.float32) for j in range(K))


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
    # the pairwise decays e^{G_t - G_i} as a product of two factors taken
    # from the chunk's middle: exponents of at most +-5 chunk / 2
    Gm = G - G[:, :, chunk // 2 - 1:chunk // 2]
    k_out = k * jnp.exp(-Gm)
    A = jnp.tril(_mm("nhtd,nhid->nhti", k * jnp.exp(Gm), k_out) * beta, -1)
    B = jnp.tril(_mm("nhtd,nhid->nhti", q * jnp.exp(Gm), k_out))
    rhs = jnp.concatenate([beta * v, beta * k_in], -1)
    sol = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(chunk, dtype=A.dtype), rhs, lower=True,
        unit_diagonal=True)
    w_v, w_k = sol[..., :dv], sol[..., dv:]
    g_end = G[:, :, -1]                                       # (n, H, d_k)
    k_end = k * jnp.exp(g_end[:, :, None] - G)

    def body(S, c):
        w_v, w_k, q_in, B, k_end, g_end = c
        U = w_v - _mm("hck,hkv->hcv", w_k, S)
        O = _mm("hck,hkv->hcv", q_in, S) + _mm("hti,hiv->htv", B, U)
        S = jnp.exp(g_end)[..., None] * S + _mm("hck,hcv->hkv", k_end, U)
        return S, O

    S, O = jax.lax.scan(body, S0, (w_v, w_k, q_in, B, k_end, g_end))
    return O.transpose(0, 2, 1, 3).reshape(T, H, dv), S


def gdn_chunked(q, k, v, g, beta, S0, chunk=GDN_CHUNK):
    """The recurrence with ONE gate a head, over one sequence in chunks:
    ``q, k`` (T, H, d_k), ``v`` (T, H, d_v), ``g, beta`` (T, H), ``S0``
    (H, d_k, d_v).  Returns ``(o (T, H, d_v), S_T)``.  ``T`` need not be
    a multiple of ``chunk``: the tail is padded with ``beta = g = 0``,
    which leaves the state as it is (so does the caller's right
    padding).

    Inside a chunk, ``G_t`` the running sum of ``g`` and ``D_ti =
    e^{G_t - G_i}`` for ``i <= t`` (0 above the diagonal), built whole:
    ``(I + A) U = beta V - beta (e^G K) S_0`` with ``A_ti = beta_t D_ti
    (k_t . k_i)`` for ``i < t``;  ``O = (e^G Q) S_0 + (D * Q K^T) U``;
    ``S_C = e^{G_C} S_0 + (e^{G_C - G} K)^T U``.  Every exponent is at
    most 0, so no gate, however negative, overflows float32 (KDA's form
    splits a decay into two factors and needs a bound below).  What
    does not hold the state is computed for all chunks at once; the
    scan carries the state alone."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    pad = -T % chunk
    if pad:
        zeros = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        q, k, v, g, beta = (zeros(a) for a in (q, k, v, g, beta))
    n = (T + pad) // chunk

    def blocks(a):
        return a.reshape(n, chunk, H, -1).transpose(0, 2, 1, 3)

    q, k, v = blocks(q), blocks(k), blocks(v)                # (n, H, C, d)
    g = g.reshape(n, chunk, H).transpose(0, 2, 1)            # (n, H, C)
    beta = beta.reshape(n, chunk, H).transpose(0, 2, 1)[..., None]
    G = jnp.cumsum(g, axis=-1)
    t = jnp.arange(chunk)
    D = jnp.exp(jnp.where(t[:, None] >= t[None, :],
                          G[..., :, None] - G[..., None, :], -jnp.inf))
    A = jnp.tril(_mm("nhtd,nhid->nhti", k, k) * D, -1) * beta
    B = _mm("nhtd,nhid->nhti", q, k) * D
    eG = jnp.exp(G)[..., None]                               # (n, H, C, 1)
    rhs = jnp.concatenate([beta * v, beta * eG * k], -1)
    sol = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(chunk, dtype=A.dtype), rhs, lower=True,
        unit_diagonal=True)
    w_v, w_k = sol[..., :dv], sol[..., dv:]
    g_end = G[..., -1]                                       # (n, H)
    k_end = k * jnp.exp(g_end[..., None] - G)[..., None]

    def body(S, c):
        w_v, w_k, q_in, B, k_end, g_end = c
        U = w_v - _mm("hck,hkv->hcv", w_k, S)
        O = _mm("hck,hkv->hcv", q_in, S) + _mm("hti,hiv->htv", B, U)
        S = jnp.exp(g_end)[:, None, None] * S \
            + _mm("hck,hcv->hkv", k_end, U)
        return S, O

    S, O = jax.lax.scan(body, S0, (w_v, w_k, q * eG, B, k_end, g_end))
    return O.transpose(0, 2, 1, 3).reshape(n * chunk, H, dv)[:T], S
