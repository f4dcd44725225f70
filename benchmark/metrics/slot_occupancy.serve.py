"""Share of the slot-ticks of the window that decoded a request:
delta ``slot_ticks`` over delta ``ticks`` x slots."""


def read(ctx):
    r = ctx["record"]
    if not r.get("ticks"):
        return None
    return 100.0 * r["slot_ticks"] / (r["ticks"] * r["slots"])
