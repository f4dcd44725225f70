"""Seconds of the window over the scheduler's ticks in it
(``SlotScheduler.stats["ticks"]``): one decode step over all slots,
with whatever admissions came between two of them."""


def read(ctx):
    r = ctx["record"]
    if not r.get("ticks"):
        return None
    return 1e3 * r["window_s"] / r["ticks"]
