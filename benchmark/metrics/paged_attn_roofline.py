"""The paged decode-attention kernel's share of its roofline: least
time the chip could take to read the live K and V pages of the window's
slot-ticks (whole pages, never the pool) and do their dot products, over
the device time of the step program's Mosaic custom calls."""
from benchmark import work


def read(ctx):
    t, peak, r = ctx["trace"], ctx["peak"], ctx["record"]
    kw = (r.get("kernel_work") or {}).get("paged_attn")
    if t is None or peak is None or not kw or not t["custom_call_s"]:
        return None
    least, _bound = work.roofline_seconds(kw, peak)
    return 100.0 * least / t["custom_call_s"]
