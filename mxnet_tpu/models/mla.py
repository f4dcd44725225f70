"""Multi-head latent attention (MLA, arXiv:2405.04434) as the served
decoder families share it (``models/ling.py``: one layer in six;
``models/kimi.py``: every layer): rotary positions with plain or
YaRN-scaled frequencies, and the two forms of the attention itself over
the 576-wide cache rows ``[latent | rotary key]``.

- :func:`mla_absorbed` -- the decode form: ``W_kvb`` folded into the
  query and into the output, so that attention is multi-query attention
  over the latent rows themselves (``ops/latent_attention.py``).  Over
  a contiguous table it is the reference form; in the served step
  (:func:`mla_absorbed_paged`) the cache view attends for it, over each
  slot's live pages where they lie in the pool -- the Pallas kernel, or
  a lookup of the slot's pages and the reference form over that -- and
  no table reaches this module.
- :func:`mla_expanded` -- the prefill form: keys and values expanded
  from the latent rows of one sequence, causal.  With ``history`` the
  sequence is a *tail* that stands behind rows already in the slot's
  pages (a cached prefix, or the chunks of the same prompt that went
  before): the tail attends over ``[history | own rows]``, the history
  a block of keys at a time for as many blocks as it has, joined by a
  running maximum and sum.

A configuration object ``c`` gives ``heads``, ``kv_rank``, ``nope``,
``rope``, ``v_dim`` and, where its rotary part is YaRN-scaled,
``attn_mscale`` (the scores then carry its square).  The equal-function
claims -- absorbed is expanded, a tail behind its history is the whole
sequence -- are pinned by ``tests/test_ling_serving.py`` and
``tests/test_kimi_model.py``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import latent_attention as _la
from .blocks import lin as _lin

__all__ = ["rope_freq", "yarn_freq", "yarn_mscale", "rope",
           "mla_absorbed", "mla_absorbed_paged", "mla_expanded"]

NEG_INF = -1e30
MLA_QUERY_BLOCK = 512
MLA_KEY_BLOCK = 512     # history keys expanded and attended over at once


# ------------------------------------------------------------- rotary
def rope_freq(theta, d):
    """``theta^(-2i/d)``, ``i = 0 .. d/2 - 1``, float32."""
    return theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)


def yarn_correction_range(theta, d, original, beta_fast, beta_slow):
    """``(low, high)``: the pair indices between which YaRN
    (arXiv:2309.00071) blends from the published frequencies to the
    interpolated ones.  ``dim(r) = d ln(original / (2 pi r)) / (2 ln
    theta)`` is the pair that makes ``r`` turns over the original
    context."""
    dim = lambda turns: d * math.log(original / (2 * math.pi * turns)) \
        / (2 * math.log(theta))
    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), d - 1)
    return low, high


def yarn_freq(theta, d, *, factor, original, beta_fast, beta_slow):
    """YaRN's frequencies: pair ``i`` keeps ``f_i = theta^(-2i/d)``
    below ``low``, turns ``factor`` times slower from ``high`` on, and
    is blended linearly between."""
    low, high = yarn_correction_range(theta, d, original, beta_fast,
                                      beta_slow)
    f = rope_freq(theta, d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / factor * ramp


def yarn_mscale(factor, mscale):
    """``m(s) = 0.1 s ln(factor) + 1`` (1 for a factor of at most 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope(x, pos, freq):
    """Rotary positions on the last axis of ``x`` (N, ..., d), float32;
    interleaved pairs, ``pos`` (N,) absolute positions, ``freq`` (d/2,)
    radians a position and pair."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * freq
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _denominator(c):
    """What the scores are divided by: ``sqrt(d_qk)``, over the square
    of the configuration's ``attn_mscale`` where it has one.  A host
    float32: a kernel takes it as a constant."""
    root = np.sqrt(np.float32(c.nope + c.rope))
    m = getattr(c, "attn_mscale", 1.0)
    return root if m == 1.0 else root / np.float32(m * m)


# ---------------------------------------------------------- the forms
def _absorbed(q_nope, q_rope, w_kvb, c, attend):
    """``W_kvb``'s key half into the query, ``attend(q (B, H, rank +
    rope), rank, denominator) -> (B, H, rank)`` over the latent rows,
    its value half onto the result.  Returns (B, H * 128)."""
    H = c.heads
    wb = w_kvb.reshape(H, c.nope + c.v_dim, c.kv_rank)
    q_lat = jnp.einsum("bhd,hdr->bhr", q_nope, wb[:, :c.nope])
    o_lat = attend(jnp.concatenate([q_lat, q_rope], -1), c.kv_rank,
                   _denominator(c))
    o = jnp.einsum("bhr,hdr->bhd", o_lat, wb[:, c.nope:])
    return o.reshape(o.shape[0], H * c.v_dim)


def mla_absorbed(q_nope, q_rope, table, valid, w_kvb, c):
    """Decode form: ``q_nope`` (B, H, 128), ``q_rope`` (B, H, 64) turned
    already; ``table`` (B, S, 576) the rows ``[latent | rotary key]`` of
    each slot and ``valid`` (B, S) which of them exist.  ``W_kvb`` is
    absorbed into the query and into the output, so attention runs over
    the latent rows themselves.  Returns (B, H * 128)."""
    return _absorbed(
        q_nope, q_rope, w_kvb, c, lambda q, rank, denominator:
        _la.dense_attention(q, table, valid, rank, denominator))


def mla_absorbed_paged(q_nope, q_rope, rows, view, name, layer, w_kvb, c):
    """The decode form in the served step: ``rows`` (B, 576), each
    slot's new ``[latent | rotary key]`` row, go into the view's page
    rows ``name`` of ``layer`` at the slot's cursor, and the view
    attends over the slot's pages, the new row among them
    (``view.attend_pages``).  Returns (B, H * 128)."""
    return _absorbed(
        q_nope, q_rope, w_kvb, c, lambda q, rank, denominator:
        view.attend_pages(name, layer, rows, q, rank=rank,
                          denominator=denominator))


def _expand(rows, w_kvb, c):
    """``rows`` (S, 576) -> ``k_nope`` (S, H, 128), ``v`` (S, H, 128),
    ``k_rope`` (S, 64)."""
    lat, k_rope = rows[:, :c.kv_rank], rows[:, c.kv_rank:]
    kv = _lin(lat, w_kvb).reshape(rows.shape[0], c.heads,
                                  c.nope + c.v_dim)
    return kv[..., :c.nope], kv[..., c.nope:], k_rope


def _scores(q_nope, q_rope, k_nope, k_rope, c):
    return (jnp.einsum("thd,shd->hts", q_nope, k_nope,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("thd,sd->hts", q_rope, k_rope,
                         preferred_element_type=jnp.float32)) \
        / _denominator(c)


def _over_history(q_nope, q_rope, history, w_kvb, c, block):
    """The running softmax of every query over the history alone:
    ``history`` = ``(table (S, 576), hist)``, the slot's rows of this
    layer and how many of them, from position 0 on, stand before the
    tail.  ``ceil(hist / block)`` blocks of keys are expanded and
    attended over, each by all the queries at once (the expansion is
    then paid once a block): a loop whose trip count rides on ``hist``,
    so a short history costs a short loop whatever ``S``.  Returns
    ``(m (H, T), l (H, T), acc (T, H, 128))``: maximum, sum of
    exponentials and weighted values, float32; ``l`` = 0 where
    ``hist`` = 0."""
    table, hist = history
    T, H = q_nope.shape[0], c.heads
    table = jnp.pad(table, ((0, -table.shape[0] % block), (0, 0)))

    def body(j, carry):
        m, l, acc = carry
        k_nope, v, k_rope = _expand(
            jax.lax.dynamic_slice_in_dim(table, j * block, block), w_kvb, c)
        seen = j * block + jnp.arange(block) < hist
        s = jnp.where(seen, _scores(q_nope, q_rope, k_nope, k_rope, c),
                      NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a block the loop visits holds a seen key, so m_new is finite
        # and an unseen key's weight is exactly 0
        p = jnp.exp(s - m_new[..., None])
        keep = jnp.exp(m - m_new)
        acc = acc * keep.T[..., None] + jnp.einsum(
            "hts,shd->thd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l * keep + jnp.sum(p, axis=-1), acc

    return jax.lax.fori_loop(
        0, (hist + block - 1) // block, body,
        (jnp.full((H, T), NEG_INF, jnp.float32),
         jnp.zeros((H, T), jnp.float32),
         jnp.zeros((T, H, c.v_dim), jnp.float32)))


def mla_expanded(q_nope, q_rope, rows, w_kvb, c, history=None,
                 block=MLA_QUERY_BLOCK, key_block=MLA_KEY_BLOCK):
    """Prefill form over one sequence: ``q_nope`` (T, H, 128),
    ``q_rope`` (T, H, 64), ``rows`` (T, 576).  Keys and values are
    expanded from the latent rows; causal, a block of queries at a time
    over the keys up to its end.  Without ``history`` the sequence
    starts at position 0; with it (see :func:`_over_history`) the
    sequence stands behind ``hist`` rows of the slot's pages and every
    query attends over them too, ``key_block`` of them at a time, in
    one softmax with its own rows.
    Returns (T, H * 128)."""
    T, H = q_nope.shape[0], c.heads
    k_nope, v, k_rope = _expand(rows, w_kvb, c)
    if history is not None:
        m_h, l_h, acc_h = _over_history(q_nope, q_rope, history, w_kvb, c,
                                        key_block)
    out = []
    for lo in range(0, T, block):
        hi = min(T, lo + block)
        s = _scores(q_nope[lo:hi], q_rope[lo:hi], k_nope[:hi], k_rope[:hi],
                    c)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        s = jnp.where(causal[None], s, NEG_INF)
        if history is None:
            p = jax.nn.softmax(s, axis=-1)
            out.append(jnp.einsum("hts,shd->thd", p.astype(v.dtype),
                                  v[:hi]))
            continue
        m = jnp.maximum(m_h[:, lo:hi], jnp.max(s, axis=-1))
        p = jnp.exp(s - m[..., None])
        keep = jnp.exp(m_h[:, lo:hi] - m)
        o = acc_h[lo:hi] * keep.T[..., None] + jnp.einsum(
            "hts,shd->thd", p.astype(v.dtype), v[:hi],
            preferred_element_type=jnp.float32)
        l = l_h[:, lo:hi] * keep + jnp.sum(p, axis=-1)
        out.append((o / l.T[..., None]).astype(v.dtype))
    return jnp.concatenate(out).reshape(T, H * c.v_dim)
