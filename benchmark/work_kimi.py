"""Operations and bytes of the Kimi-K2 share, from shapes and the
program's own counts.

As in ``work.py`` and ``work_ling.py``: *model* work, what the
mathematics of a call needs, 2 FLOPs a multiply-add, nothing measured
here.  ``s`` is ``families/kimi.py:sizes(config)``.  A prompt token that
came from shared pages (a prefix hit) is no work: a prefill counts its
tail alone, attending over the history and its own rows.  A traced
scope (``mla_attn``, ``moe.experts``) holds the whole of that part of a
layer, its projections too, so each work function counts them as well:
the weights of the scope once a program call, the per-token traffic on
top.
"""
import math

from benchmark import work_ling

BF16 = work_ling.BF16
least_seconds = work_ling.least_seconds
scope_roofline = work_ling.scope_roofline
expert_params = work_ling.expert_params
pairs_held_per_token = work_ling.pairs_held_per_token


def mla_params(s):
    """``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o``."""
    H, D = s["heads"], s["hidden"]
    return (s["q_rank"] * D + H * (s["nope"] + s["rope"]) * s["q_rank"]
            + (s["kv_rank"] + s["rope"]) * D
            + H * (s["nope"] + s["v_dim"]) * s["kv_rank"]
            + D * H * s["v_dim"])


def body_params(s):
    """Parameters in a matrix product of one token's forward pass
    through the blocks (embedding lookups are gathers)."""
    total = 0.0
    for mlp in s["mlps"]:
        total += mla_params(s)
        if mlp == "dense":
            total += 3 * s["hidden"] * s["dense_width"]
        else:
            total += (s["experts"] * s["hidden"]
                      + pairs_held_per_token(s) * expert_params(s)
                      + 3 * s["hidden"] * s["shared_width"])
    return total


def active_params(s):
    return body_params(s) + s["hidden"] * s["vocab_size"]


def mla_attn_flops(s, context):
    """One query over ``context`` positions in one layer: scores over
    192, values over 128, a head."""
    return 2.0 * s["heads"] * context * (s["nope"] + s["rope"] + s["v_dim"])


def decode_flops(s, context):
    """One decoded token whose attention covers ``context`` positions."""
    return 2 * active_params(s) + s["n_layer"] * mla_attn_flops(s, context)


def tail_attn_flops(s, hist, tokens):
    """``tokens`` queries behind ``hist`` positions, causal among
    themselves, one layer."""
    return mla_attn_flops(s, 1) * tokens * (hist + tokens / 2.0)


def prefill_flops(s, prompt, hist=0):
    """A prompt of ``prompt`` tokens of which the first ``hist`` came
    from shared pages: every block over the tail's tokens, their
    attention over history and tail, the head once."""
    tokens = prompt - hist
    return (2 * body_params(s) * tokens
            + 2 * s["hidden"] * s["vocab_size"]
            + s["n_layer"] * tail_attn_flops(s, hist, tokens))


def chunks_of(hist, tokens, chunk):
    """The prefill programs of an admission whose tail of ``tokens``
    stands behind ``hist``: ``[(hist, tokens)]`` a program, whole chunks
    while more than a chunk is left."""
    out = []
    while tokens > chunk:
        out.append((hist, chunk))
        hist, tokens = hist + chunk, tokens - chunk
    return out + [(hist, tokens)]


# ------------------------------------------------------------ the scopes
def kernel_work(s, *, block, ticks, slot_ticks, contexts, prompts,
                pairs_held, distinct_hits):
    """Work of the traced scopes over a window, by the name of the
    roofline metric that reads it; each a list of parts as
    ``work_ling.kernel_work`` gives them (the least time of the whole
    is the sum of the parts').

    ``ticks``/``slot_ticks``: the scheduler's counts; ``contexts``: the
    positions each decoded token of the finished requests attended
    over; ``prompts``: the prefill PROGRAMS of the window, each
    ``(hist, tokens)`` (a chunk, or a whole tail) or a bare length (a
    whole prompt with no history); ``pairs_held`` / ``distinct_hits``:
    the program's counters over the window."""
    L = s["n_layer"]
    prefills = [p if isinstance(p, tuple) else (0, p) for p in prompts]
    out = {"moe_experts": [{
        "flops": 2.0 * pairs_held * expert_params(s),
        "bytes": (distinct_hits * expert_params(s) * BF16
                  + pairs_held * 2 * s["hidden"] * BF16)}]}
    # mla_attn: a step reads the live latent pages (whole pages) of each
    # slot-tick, scaled from the finished requests' positions, and the
    # scope's weights once; a prefill program is its projections, the
    # tail's attention over history and tail, and the history's rows read
    row = (s["kv_rank"] + s["rope"]) * BF16
    live = sum(math.ceil(c / block) * block * row for c in contexts)
    attn = sum(mla_attn_flops(s, c) for c in contexts)
    scale = slot_ticks / len(contexts) if contexts else 0.0
    tokens = float(sum(t for _h, t in prefills))
    out["mla_attn"] = [
        {"flops": L * (scale * attn + slot_ticks * 2.0 * mla_params(s)),
         "bytes": L * (scale * live + ticks * mla_params(s) * BF16)},
        {"flops": L * sum(2.0 * mla_params(s) * t
                          + tail_attn_flops(s, h, t) for h, t in prefills),
         "bytes": L * (len(prefills) * mla_params(s) * BF16
                       + tokens * (2 * s["hidden"] * BF16 + row)
                       + sum(h for h, _t in prefills) * row)}]
    return out
