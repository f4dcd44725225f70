"""Operations and bytes of the Ling-3.0-flash share, from shapes and
the program's own counts.

As in ``work.py``: *model* work, what the mathematics of a call needs,
2 FLOPs a multiply-add, nothing measured here.  ``s`` is
``families/ling.py:sizes(config)``.  A traced scope (``kda``,
``mla_attn``, ``moe.experts``) holds the whole of that part of a layer,
its projections too, so each work function counts them as well: the
weights of the scope once a program call, the per-token traffic on top.
"""
import math

BF16 = 2
F32 = 4


def kda_params(s):
    """q, k, v, the decay's projection and the output (5 H d D), beta
    and the head-wise gate (2 H D)."""
    Hd = s["heads"] * s["head_dim"]
    return 5 * Hd * s["hidden"] + 2 * s["heads"] * s["hidden"]


def mla_params(s):
    H, D = s["heads"], s["hidden"]
    return (H * (s["nope"] + s["rope"]) * D + (s["kv_rank"] + s["rope"]) * D
            + H * (s["nope"] + s["v_dim"]) * s["kv_rank"]
            + D * H * s["v_dim"])


def expert_params(s):
    return 3 * s["hidden"] * s["moe_width"]


def pairs_held_per_token(s):
    """Expected token-expert assignments that fall on the experts held
    here, under uniform routing."""
    return s["top_k"] * s["experts_held"] / s["experts"]


def body_params(s):
    """Parameters in a matrix product of one token's forward pass
    through the blocks (embedding lookups are gathers)."""
    total = 0.0
    for mixer, mlp in zip(s["mixers"], s["mlps"]):
        total += kda_params(s) if mixer == "kda" else mla_params(s)
        if mlp == "dense":
            total += 3 * s["hidden"] * s["dense_width"]
        else:
            total += (s["experts"] * s["hidden"]
                      + pairs_held_per_token(s) * expert_params(s)
                      + 3 * s["hidden"] * s["shared_width"])
    return total


def active_params(s):
    return body_params(s) + s["hidden"] * s["vocab_size"]


def kda_state_flops(s):
    """One token of one KDA layer on the state: the prediction ``k^T
    S``, the rank-one update and the output ``q^T S`` (each 2 d_k d_v a
    head)."""
    return 6 * s["heads"] * s["head_dim"] * s["head_dim"]


def mla_attn_flops(s, context):
    """One query over ``context`` positions: scores over 192, values
    over 128, a head."""
    return 2 * s["heads"] * context * (s["nope"] + s["rope"] + s["v_dim"])


def _count(s, kind):
    return sum(m == kind for m in s["mixers"])


def decode_flops(s, context):
    """One decoded token whose latent attention covers ``context``
    positions."""
    return (2 * active_params(s) + _count(s, "kda") * kda_state_flops(s)
            + _count(s, "mla") * mla_attn_flops(s, context))


def prefill_flops(s, prompt):
    """A prompt of ``prompt`` real tokens: every block over every token,
    causal latent attention, the head once."""
    return (2 * body_params(s) * prompt
            + 2 * s["hidden"] * s["vocab_size"]
            + _count(s, "kda") * kda_state_flops(s) * prompt
            + _count(s, "mla") * mla_attn_flops(s, prompt) * prompt / 2)


# ------------------------------------------------------------ the scopes
def kernel_work(s, *, block, ticks, slot_ticks, contexts, prompts,
                pairs_held, distinct_hits):
    """Work of the three traced scopes over a window, by the name of the
    roofline metric that reads it.  Each is a list of parts; a part is
    ``{"flops", "bytes"}`` and the least time of the whole is the sum
    of the parts' (a decode step is bound by bytes, a prefill by
    operations: one sum over both would hide the larger).

    ``ticks``/``slot_ticks``: the scheduler's counts; ``contexts``: the
    positions each decoded token of the finished requests attended
    over; ``prompts``: the prompt lengths prefilled; ``pairs_held`` /
    ``distinct_hits``: the program's counters over the window
    (assignments on held experts; distinct held experts hit, summed
    over layers and program calls)."""
    n_kda, n_mla = _count(s, "kda"), _count(s, "mla")
    H, d = s["heads"], s["head_dim"]
    out = {}
    # moe.experts: the weights of the distinct experts hit, each read
    # once a call and layer, and the routed rows in and out
    out["moe_experts"] = [{
        "flops": 2.0 * pairs_held * expert_params(s),
        "bytes": (distinct_hits * expert_params(s) * BF16
                  + pairs_held * 2 * s["hidden"] * BF16)}]
    # kda: a step reads and writes each occupied slot's state once a
    # layer (and the convolution's tail), and the scope's weights once;
    # a prefill is its projections and the recurrence's operations
    state = H * d * d * F32 + (s["conv"] - 1) * 3 * H * d * BF16
    tokens = float(sum(prompts))
    out["kda_state"] = [
        {"flops": n_kda * slot_ticks * (2.0 * kda_params(s)
                                        + kda_state_flops(s)),
         "bytes": n_kda * (slot_ticks * 2.0 * state
                           + ticks * kda_params(s) * BF16)},
        {"flops": n_kda * tokens * (2.0 * kda_params(s)
                                    + kda_state_flops(s)),
         "bytes": n_kda * (len(prompts) * (kda_params(s) * BF16 + state)
                           + tokens * 2 * s["hidden"] * BF16)}]
    # mla_attn: a step reads the live latent pages (whole pages) of each
    # slot-tick, scaled from the finished requests' positions
    row = (s["kv_rank"] + s["rope"]) * BF16
    live = sum(math.ceil(c / block) * block * row for c in contexts)
    attn = sum(mla_attn_flops(s, c) for c in contexts)
    scale = slot_ticks / len(contexts) if contexts else 0.0
    out["mla_attn"] = [
        {"flops": n_mla * (scale * attn
                           + slot_ticks * 2.0 * mla_params(s)),
         "bytes": n_mla * (scale * live + ticks * mla_params(s) * BF16)},
        {"flops": n_mla * sum(2.0 * mla_params(s) * p
                              + mla_attn_flops(s, p) * p / 2
                              for p in prompts),
         "bytes": n_mla * (len(prompts) * mla_params(s) * BF16
                           + tokens * (2 * s["hidden"] * BF16 + row))}]
    return out


def least_seconds(parts, peak):
    """Sum over ``parts`` of the larger of operations over peak FLOP/s
    and bytes over peak bytes/s."""
    return sum(max(p["flops"] / peak["bf16_flops_per_s"],
                   p["bytes"] / peak["hbm_bytes_per_s"]) for p in parts)


def scope_roofline(ctx, scope, work):
    """A roofline reader's body: least seconds of ``kernel_work[work]``
    over the traced device seconds in ``scope`` (``record["scope_s"]``),
    in percent; None where the run has no peak (a rehearsal), no work
    or no time in the scope (a program without it)."""
    r, peak = ctx["record"], ctx["peak"]
    parts = (r.get("kernel_work") or {}).get(work)
    secs = (r.get("scope_s") or {}).get(scope)
    if peak is None or not parts or not secs:
        return None
    return 100.0 * least_seconds(parts, peak) / secs
