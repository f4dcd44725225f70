"""Plain reference: the SDAR-30B-A3B-Chat language model and its
generation by diffusion over blocks, in float32 ``jax.numpy``.

Written from the published ``config.json`` (``model_type`` ``sdar_moe``:
the Qwen3-MoE block) and the papers the generation comes from (SDAR,
arXiv:2510.06303; block diffusion BD3-LM, arXiv:2503.09573).  One
sequence at a time, no cache, no kernel, no batching; every matrix
product at ``highest`` precision.  It imports nothing of the program.

The equations.  ``x`` (T, 2048) hidden states, RMSNorm ``n(x; w) = x /
sqrt(mean(x^2) + 1e-6) * w``.  Layer ``l``:

1. ``h = n(x; w_in)``.  ``q = h Wq`` -> (T, 32, 128), ``k = h Wk`` ->
   (T, 4, 128), ``v = h Wv`` -> (T, 4, 128), no biases.  ``q <- n_128(q;
   w_qn)``, ``k <- n_128(k; w_kn)`` per head; rotary positions on all 128
   dims in the half-split form (the pair ``(x[j], x[j + 64])`` turned
   by ``pos * theta^(-j/64)``, theta 1e6), absolute positions.
2. Attention, scale ``128^-1/2``; query head ``i`` reads K/V head ``i
   // 8``.  **Mask: block-causal with block length n** -- the query at
   position ``a`` sees the key at ``b`` iff ``b // n <= a // n``.  ``x
   <- x + att Wo``.
3. ``h = n(x; w_post)``.  Router ``s = softmax(h Wr^T)`` over 128
   experts in float32; the 8 most probable; weights ``s_top /
   sum(s_top)``; ``y = sum_e w_e Wd_e(silu(Wg_e h) * Wu_e h)``, expert
   width 768.  ``x <- x + y``.  No shared expert.
4. After the last layer ``logits = n(x; w_f) W_head`` (untied).  The
   token at position ``a`` is predicted from the hidden state AT ``a``
   (no shift).

Generation (:func:`generate`), the family's published loop with
``remasking`` ``low_confidence_static``: ``x = [prompt ; MASK ...]``
padded to whole blocks of ``n``.  For each block after the prompt's
whole blocks: its ``n`` ids (the prompt's remainder fixed, the rest
MASK) go forward behind everything before them; at each masked
position take the most probable token and its probability; unmask the
``k_s`` masked positions of highest probability (``k_s = n // steps``,
plus one for the first ``n % steps`` forwards; ties to the lower
position), and repeat until no MASK is left.  A request of ``N`` tokens
generates ``ceil((P mod n + N) / n)`` blocks and returns the first
``N``; ``eos`` is looked for when a block is whole.

**Several streams in one forward** (:func:`visible`).  The family trains
on ``[x_noised ; x_clean]``: a noised block sees itself and the clean
blocks strictly before it, the clean half is block-causal.  The same
identity gives, from ONE forward, the logits of every forward
``generate`` made for a request: the clean sequence (stream 0) and, for
each ordinal ``s`` of a denoising forward, a copy of the generated
blocks as they stood before their forward ``s`` (stream ``s + 1``), each
at its own positions.  Key ``j`` is visible to query ``i`` iff ``j`` is
clean and in an earlier block, or ``j`` is of ``i``'s stream and block.
:func:`teacher_streams` builds the streams from a served trajectory
(``tokens`` and the ordinal ``unmask_step`` at which each was fixed),
:func:`trajectory_gaps` compares.

Departures and readings, stated in the configuration file's
``assumed``: block length 4, mask id 151669, 4 denoising steps by
default; weights stored ``(out, in)``, the experts ``(expert, in,
out)``.

``compute="fp8"`` is the control of "How correct is decided": the same
mathematics with both operands of every matrix product rounded to
float8 e4m3 under one scale a tensor, the precision below the bfloat16
the configuration states.  ``compute="bf16"`` rounds them to bfloat16.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CLEAN, PAD = 0, -1


@dataclasses.dataclass(frozen=True)
class Sizes:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    experts: int
    top_k: int
    eps: float
    layers: int
    block_length: int
    mask_id: int
    denoising_steps: int


def sizes_of(config):
    gen = config["generation"]
    return Sizes(
        hidden=int(config["hidden_size"]),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        rope_theta=float(config["rope_theta"]),
        experts=int(config["num_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        eps=float(config["rms_norm_eps"]),
        layers=int(config["num_layers"]),
        block_length=int(gen["block_length"]),
        mask_id=int(gen["mask_token_id"]),
        denoising_steps=int(gen["denoising_steps"]))


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


def rope(x, pos, theta):
    """``x`` (T, H, d), ``pos`` (T,): ``x cos + rotate_half(x) sin``."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freq              # (T, d/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def visible(stream, pos, n):
    """(T, T) bool: may query ``i`` see key ``j``?  ``stream`` (T,):
    ``CLEAN`` for the sequence itself, a positive number for a noised
    copy, ``PAD`` for padding (which only its like see); ``pos`` (T,)
    absolute positions; blocks of ``n``."""
    blk = pos // n
    earlier = (stream[None, :] == CLEAN) & (blk[None, :] < blk[:, None])
    own = (stream[None, :] == stream[:, None]) & (blk[None, :] == blk[:, None])
    return earlier | own


def attention(x, w, c, pos, mask, compute):
    T, H, Hkv, dh = x.shape[0], c.heads, c.kv_heads, c.head_dim
    q = _mm("td,ed->te", x, w["q_weight"], compute).reshape(T, H, dh)
    k = _mm("td,ed->te", x, w["k_weight"], compute).reshape(T, Hkv, dh)
    v = _mm("td,ed->te", x, w["v_weight"], compute).reshape(T, Hkv, dh)
    q = rope(rms_norm(q, w["q_norm_weight"], c.eps), pos, c.rope_theta)
    k = rope(rms_norm(k, w["k_norm_weight"], c.eps), pos, c.rope_theta)
    out = []
    for g in range(Hkv):            # a K/V head and its query heads
        qs = q[:, g * (H // Hkv):(g + 1) * (H // Hkv)]
        s = _mm("thd,sd->hts", qs, k[:, g], compute) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        out.append(_mm("hts,sd->thd", p, v[:, g], compute))
    o = jnp.concatenate(out, axis=1).reshape(T, H * dh)
    return _mm("te,de->td", o, w["o_weight"], compute)


def route(logits, c):
    """Softmax over all experts, the ``top_k`` most probable, their
    probabilities normalised to sum 1."""
    wts, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), c.top_k)
    return idx, wts / jnp.sum(wts, -1, keepdims=True)


def moe(x, w, c, compute):
    T = x.shape[0]
    idx, wts = route(_mm("td,ed->te", x, w["router_weight"], compute), c)
    comb = jnp.zeros((T, c.experts), jnp.float32).at[
        jnp.arange(T)[:, None], idx].set(wts)

    def one(acc, e):
        gate, up, down, cw = e                     # (D, F), (D, F), (F, D)
        h = jax.nn.silu(_mm("td,df->tf", x, gate, compute)) \
            * _mm("td,df->tf", x, up, compute)
        return acc + cw[:, None] * _mm("tf,fd->td", h, down, compute), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w["experts_gate_weight"], w["experts_up_weight"],
         w["experts_down_weight"], comb.T))
    return y


@functools.partial(jax.jit, static_argnames=("c", "compute"))
def layer(h, w, c, pos, mask, compute="f32"):
    """One block over ``h`` (T, D) at positions ``pos`` under ``mask``
    (T, T); ``w`` holds this layer's leaves without ``layer<i>_``."""
    h = h + attention(rms_norm(h, w["norm1_weight"], c.eps), w, c, pos,
                      mask, compute)
    return h + moe(rms_norm(h, w["norm2_weight"], c.eps), w, c, compute)


def embed(params, ids):
    return jnp.take(params["tok_embed_weight"], ids, axis=0)


@functools.partial(jax.jit, static_argnames=("c", "compute"))
def head(h, params, c, compute="f32"):
    x = rms_norm(h, params["final_norm_weight"], c.eps)
    return _mm("td,vd->tv", x, params["lm_head_weight"], compute)


def layer_leaves(params, i):
    p = f"layer{i}_"
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}


def logits_of(params, ids, pos, stream, c, compute="f32"):
    """Logits (T, V) of ``ids`` at ``pos`` in streams ``stream``, from a
    flat dict of leaves under the program's names."""
    ids, pos, stream = (jnp.asarray(a, jnp.int32) for a in (ids, pos, stream))
    mask = visible(stream, pos, c.block_length)
    h = embed(params, ids)
    for i in range(c.layers):
        h = layer(h, layer_leaves(params, i), c, pos, mask, compute)
    return head(h, params, c, compute)


def forward(params, ids, c, compute="f32"):
    """The full sequence ``ids`` (T,) under the block-causal mask."""
    T = len(ids)
    return logits_of(params, ids, np.arange(T), np.full(T, CLEAN), c,
                     compute)


# ---------------------------------------------------------- generation
def unmask_count(n, steps, s):
    """``k_s``: how many positions forward ``s`` of a block fixes."""
    return n // steps + (1 if s < n % steps else 0)


def pick(conf, masked, k):
    """The ``k`` positions of ``masked`` with the highest ``conf``, ties
    to the lower position."""
    return sorted(masked, key=lambda j: (-float(conf[j]), j))[:k]


def generate(params, prompt, max_tokens, c, steps=None, eos=None,
             compute="f32"):
    """The family's loop, everything recomputed at each forward.
    Returns ``(tokens, unmask_step, forwards)``: the first
    ``max_tokens`` generated ids (cut behind ``eos``), per token the
    ordinal of the forward of its block that fixed it, and per forward
    ``(start of the block, ordinal, logits (n, V), positions fixed)``."""
    n, steps = c.block_length, steps or c.denoising_steps
    prompt = [int(t) for t in prompt]
    r = len(prompt) % n
    done = prompt[:len(prompt) - r]
    block = prompt[len(prompt) - r:] + [c.mask_id] * (n - r)
    fixed = [True] * r + [False] * (n - r)
    tokens, unmask, forwards = [], [], []
    while True:
        at, s = [-1] * n, 0
        while not all(fixed):
            lg = forward(params, done + block, c, compute)[len(done):]
            lp = jax.nn.log_softmax(lg, axis=-1)
            best = np.asarray(jnp.argmax(lg, axis=-1))
            conf = np.asarray(jnp.max(lp, axis=-1))
            masked = [j for j in range(n) if not fixed[j]]
            now = pick(conf, masked, unmask_count(n, steps, s))
            for j in now:
                block[j], fixed[j], at[j] = int(best[j]), True, s
            forwards.append((len(done), s, lg, sorted(now)))
            s += 1
        tokens += block[r:]
        unmask += at[r:]
        tokens, unmask = tokens[:max_tokens], unmask[:max_tokens]
        if eos is not None and eos in block[r:]:
            cut = tokens.index(eos) + 1 if eos in tokens else len(tokens)
            return tokens[:cut], unmask[:cut], forwards
        if len(tokens) >= max_tokens:
            return tokens, unmask, forwards
        done, block, fixed, r = done + block, [c.mask_id] * n, [False] * n, 0


# ------------------------------------------------------ teacher forcing
def _layout(prompt, tokens, unmask, n):
    """``fill, whole, at`` of a trajectory: its generated blocks stand
    at ``[fill, whole)`` (a last block the reply holds only part of is
    left out), and ``at[p]`` is the forward that fixed position ``p``
    (-1: the prompt's)."""
    P = len(prompt)
    whole = (P + len(tokens)) // n * n
    at = np.concatenate([np.full(P, -1), np.asarray(unmask, np.int64)])
    return P - P % n, whole, at[:whole]


def teacher_streams(prompt, tokens, unmask, c, length, noised, steps,
                    commit=True):
    """A served trajectory as the streams of one forward.  Stream 0:
    ``prompt + tokens`` cut to whole blocks (a last block the reply
    holds only part of cannot be rebuilt and is left out), padded to
    ``length``.  Stream ``s + 1`` for ``s < steps``: the generated
    blocks as they stood before their forward ``s`` -- a position is
    its final id if it is the prompt's or was fixed by an earlier
    forward, else MASK -- padded to ``noised``.  ``commit=False`` plants
    the fault "the commit left out": the clean stream holds a block as
    its LAST denoising forward saw it.  Returns ``ids, pos, stream``
    (each ``length + steps * noised``) and ``fill, whole``: the
    generated blocks stand at ``[fill, whole)``."""
    n = c.block_length
    fill, whole, at = _layout(prompt, tokens, unmask, n)
    seq = np.array(list(prompt) + list(tokens), np.int64)[:whole]
    if whole - fill > noised or whole > length:
        raise ValueError("trajectory longer than the padding")
    last = np.zeros(whole, np.int64)     # a block's last forward
    for b in range(fill, whole, n):
        last[b:b + n] = at[b:b + n].max()

    def stood(s):
        """Generated positions before forward ``s`` of their block."""
        return np.where(at[fill:] < s, seq[fill:], c.mask_id)

    ids = np.zeros(length + steps * noised, np.int64)
    pos = np.zeros_like(ids)
    stream = np.full_like(ids, PAD)
    ids[:whole], pos[:whole], stream[:whole] = seq, np.arange(whole), CLEAN
    if not commit:
        ids[fill:whole] = np.where(at[fill:] < last[fill:], seq[fill:],
                                   c.mask_id)
    for s in range(steps):
        lo = length + s * noised
        hi = lo + whole - fill
        ids[lo:hi], pos[lo:hi] = stood(s), np.arange(fill, whole)
        stream[lo:hi] = s + 1
    return ids, pos, stream, fill, whole


def reduce_rows(lg, target):
    """What a comparison needs of logits ``lg`` (R, V): per row the best
    logit, its log-probability (the confidence) and the logit of
    ``target`` (R,) -- so that (R, V) never has to leave the device."""
    lg = lg.astype(jnp.float32)
    top = jnp.max(lg, axis=-1)
    conf = -jnp.log(jnp.sum(jnp.exp(lg - top[:, None]), axis=-1))
    got = jnp.take_along_axis(
        lg, jnp.asarray(target, jnp.int32)[:, None], axis=1)[:, 0]
    return tuple(np.asarray(a) for a in (top, conf, got))


def trajectory_gaps(top, conf, got, prompt, tokens, unmask, c, noised,
                    own_conf=None):
    """``served_logit_gap`` and ``served_order_gap`` of one trajectory.
    ``top``, ``conf``, ``got`` (``reduce_rows``) are over the NOISED rows
    of its streams (``teacher_streams`` with the same ``noised``; row
    ``s * noised + p - fill`` is position ``p`` before forward ``s``),
    from the float32 reference; ``got`` is the logit of the token
    compared: the served one, or a control's pick.

    Over every denoising forward ``s`` of every generated block and
    every position it fixed: by how much the compared token's logit
    lies below the best logit there; and by how much the reference's
    confidence at a fixed position lies below its ``k``-th best
    confidence among the positions masked before that forward, ``k``
    the number the forward fixed (0 where it fixed them all).  With
    ``own_conf``, a control's confidences over the same rows, the
    positions "fixed" are the ``k`` the control would have fixed.
    Returns ``(logit gap, order gap, positions compared)``."""
    n = c.block_length
    fill, whole, at = _layout(prompt, tokens, unmask, n)
    logit_gap = order_gap = 0.0
    compared = 0
    for b in range(fill, whole, n):
        for s in range(int(at[b:b + n].max()) + 1):
            rows = [s * noised + p - fill for p in range(b, b + n)
                    if at[p] >= s]                  # masked before s
            k = sum(at[p] == s for p in range(b, b + n))
            if own_conf is None:
                fixed = [s * noised + p - fill for p in range(b, b + n)
                         if at[p] == s]
            else:
                fixed = pick(own_conf, rows, k)
            for row in fixed:
                logit_gap = max(logit_gap, float(top[row] - got[row]))
            compared += len(fixed)
            if k < len(rows):
                kth = sorted((conf[row] for row in rows), reverse=True)[k - 1]
                order_gap = max(order_gap, max(
                    float(kth - conf[row]) for row in fixed))
    return logit_gap, order_gap, compared
