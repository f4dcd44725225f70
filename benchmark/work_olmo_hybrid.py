"""Operations and bytes of the Olmo-Hybrid stage, from shapes and the
window's counts.

As in ``work.py`` and ``work_ling.py``: *model* work, what the
mathematics of a call needs, 2 FLOPs a multiply-add, nothing measured
here.  ``s`` is ``families/olmo_hybrid.py:sizes(config)``.  A traced
scope (``gdn``, with ``gdn.state`` inside it) holds the whole of a
gated-DeltaNet mixer, its projections too, so its work counts them as
well: the scope's weights once a program call, the per-token traffic on
top.  No prompt token comes from another request's pages (the decoder
reuses no prefix): a prefill chunk counts its own tokens, attending
over the chunks before it.
"""
from benchmark import work, work_ling
from benchmark.work_kimi import chunks_of

BF16 = work_ling.BF16
F32 = work_ling.F32
least_seconds = work_ling.least_seconds

__all__ = ["gdn_params", "attn_params", "mlp_params", "body_params",
           "param_count", "decode_flops", "prefill_flops", "kernel_work",
           "scope_roofline", "chunks_of", "state_bytes", "kv_bytes_per_token"]


def gdn_params(s):
    """q and k (H d_k D each), v, the output gate and the output (H d_v
    D each), the decay's and beta's projections (H D each): the
    convolution's taps are no matrix product."""
    H, D = s["lin_heads"], s["hidden"]
    return 2 * H * s["d_k"] * D + 3 * H * s["d_v"] * D + 2 * H * D


def attn_params(s):
    return 4 * s["hidden"] * s["heads"] * s["head_dim"]


def mlp_params(s):
    return 3 * s["hidden"] * s["mlp_width"]


def _count(s, kind):
    return sum(m == kind for m in s["mixers"])


def body_params(s):
    """Parameters in a matrix product of one token's forward pass
    through the blocks (the embedding lookup is a gather)."""
    return (_count(s, "gdn") * gdn_params(s) + _count(s, "attn")
            * attn_params(s) + len(s["mixers"]) * mlp_params(s))


def param_count(s):
    """Every number the stage holds: the matrices, the embedding and the
    head, the convolution's taps, the norms and the decay's two vectors
    a gated-DeltaNet layer."""
    D, H = s["hidden"], s["lin_heads"]
    conv = H * (2 * s["d_k"] + s["d_v"]) * s["conv"]
    gdn = gdn_params(s) + conv + 2 * H + s["d_v"]
    attn = attn_params(s) + 2 * D
    per_layer = mlp_params(s) + 2 * D
    return (_count(s, "gdn") * gdn + _count(s, "attn") * attn
            + len(s["mixers"]) * per_layer + 2 * s["vocab_size"] * D + D)


def gdn_state_flops(s):
    """One token of one gated-DeltaNet layer on the state: the
    prediction ``k^T S``, the rank-one update and the output ``q^T S``
    (each 2 d_k d_v a head), and the convolution (2 taps a channel)."""
    H = s["lin_heads"]
    return 6 * H * s["d_k"] * s["d_v"] \
        + 2 * s["conv"] * H * (2 * s["d_k"] + s["d_v"])


def gdn_chunk_flops(s):
    """One token of one gated-DeltaNet layer in the chunked form
    (``models/delta_rule.py:gdn_chunked``, chunks of ``C`` = 64): its
    row of ``K K^T`` and ``Q K^T`` (2 C d_k), of the triangular solve (C
    (d_v + d_k)), of ``D Q K^T U`` (C d_v), and its share of the three
    products with the state (3 d_k d_v), a head; and the
    convolution."""
    H, dk, dv, C = s["lin_heads"], s["d_k"], s["d_v"], 64
    return 2 * H * (2 * C * dk + C * (dk + dv) + C * dv + 3 * dk * dv) \
        + 2 * s["conv"] * H * (2 * dk + dv)


def attn_flops(s, context):
    """One query over ``context`` positions: scores and values, a
    head."""
    return 4 * s["heads"] * s["head_dim"] * context


def state_bytes(s):
    """A slot's state of one gated-DeltaNet layer: ``S`` in float32 and
    the convolution's last three inputs in bfloat16."""
    H = s["lin_heads"]
    return (H * s["d_k"] * s["d_v"] * F32
            + (s["conv"] - 1) * H * (2 * s["d_k"] + s["d_v"]) * BF16)


def kv_bytes_per_token(s):
    return _count(s, "attn") * 2 * s["heads"] * s["head_dim"] * BF16


def decode_flops(s, context):
    """One decoded token whose attention covers ``context`` positions."""
    return (2 * body_params(s) + 2 * s["hidden"] * s["vocab_size"]
            + _count(s, "gdn") * gdn_state_flops(s)
            + _count(s, "attn") * attn_flops(s, context))


def prefill_flops(s, prompt, hist=0):
    """``prompt`` tokens of which the first ``hist`` were prefilled by
    another program: every block over the others, causal attention
    behind the history, the head once."""
    t = prompt - hist
    return (2 * body_params(s) * t + 2 * s["hidden"] * s["vocab_size"]
            + _count(s, "gdn") * gdn_state_flops(s) * t
            + _count(s, "attn") * 4 * s["heads"] * s["head_dim"]
            * (hist * t + t * t / 2))


# ------------------------------------------------------------ the scopes
def kernel_work(s, *, block, ticks, slot_ticks, contexts, prompts):
    """Work of the traced scopes over a window, by the name of the
    metric that reads it.  ``gdn_state`` is a list of parts, each
    ``{"flops", "bytes"}`` (the least time of the whole is the sum of
    the parts'): the step reads and writes each occupied slot's state
    once a layer and the scope's weights once a tick; a prefill program
    is its projections and chunked scan over its tokens, the weights
    and a state read and written once.  ``paged_attn`` is the step's
    attention over the live K/V pages, as ``work.py`` counts it for the
    paged kernel.

    ``ticks`` / ``slot_ticks``: the scheduler's counts; ``contexts``:
    the positions each decoded token of the finished requests attended
    over; ``prompts``: the window's prefill programs, ``(hist,
    tokens)`` each."""
    n_gdn, n_attn = _count(s, "gdn"), _count(s, "attn")
    weights = gdn_params(s) * BF16
    tokens = float(sum(t for _h, t in prompts))
    out = {"gdn_state": [
        {"flops": n_gdn * slot_ticks * (2.0 * gdn_params(s)
                                        + gdn_state_flops(s)),
         "bytes": n_gdn * (slot_ticks * 2.0 * state_bytes(s)
                           + ticks * weights)},
        {"flops": n_gdn * tokens * (2.0 * gdn_params(s)
                                    + gdn_chunk_flops(s)),
         "bytes": n_gdn * (len(prompts) * (weights + 2.0 * state_bytes(s))
                           + tokens * 2 * s["hidden"] * BF16)}]}
    if contexts and slot_ticks:
        one = work.paged_attention_step_work(contexts, s["heads"],
                                             s["head_dim"], block)
        scale = slot_ticks / len(contexts) * n_attn
        out["paged_attn"] = {"flops": one["flops"] * scale,
                             "bytes": one["bytes"] * scale,
                             "calls": ticks * n_attn}
    return out


def scope_roofline(ctx):
    """``gdn_state_roofline``'s body: least seconds of
    ``kernel_work["gdn_state"]`` over the traced device seconds in the
    scopes ``gdn`` and ``gdn.state`` (``record["scope_s"]``), in
    percent; None where the run has no peak (a rehearsal), no work or no
    time in the scopes (a program without them)."""
    r, peak = ctx["record"], ctx["peak"]
    parts = (r.get("kernel_work") or {}).get("gdn_state")
    scopes = r.get("scope_s") or {}
    secs = scopes.get("gdn", 0.0) + scopes.get("gdn.state", 0.0)
    if peak is None or not parts or not secs:
        return None
    return 100.0 * least_seconds(parts, peak) / secs
