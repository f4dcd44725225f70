"""Share of a block decoder's slot-forwards that were commit forwards
(they yield no token: they write a whole block's final K/V and move the
cursor): ``stats["commit_forwards"]`` over ``stats["slot_ticks"]`` of
the traced window.  20% at 4 denoising steps a block, 33% at 2; a
commit fused into the next block's first forward would make it 0.
None from a program whose scheduler does not count them."""


def read(ctx):
    moved = ctx["record"].get("counters") or {}
    if not moved.get("slot_ticks") or "commit_forwards" not in moved:
        return None
    return 100.0 * moved["commit_forwards"] / moved["slot_ticks"]
