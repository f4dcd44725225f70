"""Operations and bytes of the SDAR stage, from shapes and the
program's own counts.

As in ``work.py`` and ``work_ling.py``: *model* work, what the
mathematics needs, 2 FLOPs a multiply-add, nothing measured here.  ``s``
is ``families/sdar.py:sizes(config)``.  The unit of decoding is a
token-forward: one position of a block in one forward.  A block of
``n`` takes its denoising forwards and, unless it is the request's
last, one commit -- whether the commit has a forward of its own or
rides in the next block's first forward, so a fused commit moves the
speed and not this count.  The head is counted where the mathematics
needs it: at the positions still masked before a denoising forward.
"""
import math

from benchmark import work_ling

BF16 = work_ling.BF16


def attn_params(s):
    """q and o (H dh D each), k and v (Hkv dh D each)."""
    return 2 * s["hidden"] * s["head_dim"] * (s["heads"] + s["kv_heads"])


def body_params(s):
    """Parameters in a matrix product of one token's pass through the
    layers: attention, the router, ``top_k`` experts."""
    return s["n_layer"] * (
        attn_params(s) + s["experts"] * s["hidden"]
        + s["top_k"] * work_ling.expert_params(s))


def head_params(s):
    return s["hidden"] * s["vocab_size_full"]


def attn_flops(s, context):
    """One query position over ``context`` keys: scores and values,
    every query head, all layers."""
    return 4.0 * s["n_layer"] * s["heads"] * s["head_dim"] * context


def unmask_counts(n, steps, masked):
    """How many positions each denoising forward of a block with
    ``masked`` masked positions fixes: ``n // steps``, one more in the
    first ``n % steps``, until none is left."""
    out, s = [], 0
    while masked > 0:
        k = min(masked, n // steps + (1 if s < n % steps else 0))
        out.append(k)
        masked -= k
        s += 1
    return out


def request_forwards(s, prompt, n_tokens, steps):
    """The forwards of one request as ``[(context, masked before it or
    None for a commit)]``: ``context`` = the keys a position of the
    block sees (everything up to its block's end)."""
    n = s["block_length"]
    r = prompt % n
    blocks = math.ceil((r + n_tokens) / n)
    out = []
    for b in range(blocks):
        context = prompt - r + (b + 1) * n
        masked = n - r if b == 0 else n
        for k in unmask_counts(n, steps, masked):
            out.append((context, masked))
            masked -= k
        if b + 1 < blocks:
            out.append((context, None))
    return out


def prefill_flops(s, prompt):
    """The prompt's whole blocks under the block-causal mask: every
    layer over every token, no head."""
    n = s["block_length"]
    fill = prompt - prompt % n
    seen = sum(b + n for b in range(0, fill, n)) * n     # keys, all queries
    return 2.0 * body_params(s) * fill + attn_flops(s, 1) * seen


def request_flops(s, prompt, n_tokens, steps):
    n = s["block_length"]
    total = prefill_flops(s, prompt)
    for context, masked in request_forwards(s, prompt, n_tokens, steps):
        total += n * (2.0 * body_params(s) + attn_flops(s, context))
        total += 2.0 * head_params(s) * (masked or 0)
    return total


# ------------------------------------------------------------ the scopes
def kernel_work(s, *, block, ticks, slot_ticks, contexts, prompts,
                pairs_held, distinct_hits):
    """``moe.experts`` as ``work_ling`` reckons it, from the program's
    counters over the window: the weights of the distinct experts hit,
    each read once a call and layer, and the routed rows in and out.
    (The other arguments are the closed-loop driver's, reckoned for a
    decoder of one token a tick; ``block_attn_work`` has its own.)"""
    e = work_ling.expert_params(s)
    return {"moe_experts": [{
        "flops": 2.0 * pairs_held * e,
        "bytes": (distinct_hits * e * BF16
                  + pairs_held * 2 * s["hidden"] * BF16)}]}


def block_attn_work(s, block, requests, slot_ticks):
    """Scope ``attn.pages`` over a window of ``slot_ticks``
    slot-forwards: what one slot-forward needs on average over the
    forwards of the finished ``requests`` ``[(prompt, n_tokens,
    steps)]`` -- the live K and V pages (whole pages, ``Hkv`` heads)
    once a layer, the block's queries in and out, the dot products of
    ``H`` heads x ``n`` rows over the context -- times the window's
    slot-forwards."""
    n, H, Hkv, dh = (s["block_length"], s["heads"], s["kv_heads"],
                     s["head_dim"])
    contexts = [c for p, t, k in requests
                for c, _m in request_forwards(s, p, t, k)]
    if not contexts or not slot_ticks:
        return None
    pages = sum(math.ceil(c / block) * block for c in contexts)
    scale = slot_ticks / len(contexts) * s["n_layer"]
    return [{
        "flops": scale * 4.0 * H * dh * n * sum(contexts),
        "bytes": scale * (2 * Hkv * dh * BF16 * pages
                          + len(contexts) * 2 * n * H * dh * BF16)}]
