"""Share of the prompt tokens admitted in the traced window that came
from shared pages instead of being prefilled: delta
``prefix_tokens_hit`` over delta ``prompt_tokens`` of
``PagedSlots.stats()`` (program_counter).  0 when the prefix index
misses everything; nothing where the program has no such counters."""


def read(ctx):
    moved = ctx["record"].get("counters") or {}
    if not moved.get("prompt_tokens") or "prefix_tokens_hit" not in moved:
        return None
    return 100.0 * moved["prefix_tokens_hit"] / moved["prompt_tokens"]
