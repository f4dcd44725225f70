"""Tokens a slot-forward of a block decoder yields: positions unmasked
over the traced window (``stats["tokens_unmasked"]``) over its
slot-forwards (``stats["slot_ticks"]``), commit forwards among them.
4 / 5 = 0.8 at 4 denoising steps a 4-token block, 4 / 3 = 1.33 at 2;
what unmasking by a confidence threshold or a fused commit would
raise.  None from a program whose scheduler does not count them."""


def read(ctx):
    moved = ctx["record"].get("counters") or {}
    if not moved.get("slot_ticks") or "tokens_unmasked" not in moved:
        return None
    return moved["tokens_unmasked"] / moved["slot_ticks"]
