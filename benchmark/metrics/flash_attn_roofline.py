"""The flash-attention kernels' share of their roofline in the train
step: least time the chip could take for the attention of the traced
steps (forward and backward, causal-halved, from shapes) over the
device time of the step program's Mosaic custom calls."""
from benchmark import work


def read(ctx):
    t, peak, r = ctx["trace"], ctx["peak"], ctx["record"]
    kw = (r.get("kernel_work") or {}).get("flash_attn")
    if t is None or peak is None or not kw or not t["custom_call_s"]:
        return None
    steps = t["custom_calls"] / kw["calls_per_step"]
    least, _bound = work.roofline_seconds(
        {"flops": kw["flops"] * steps, "bytes": kw["bytes"] * steps}, peak)
    return 100.0 * least / t["custom_call_s"]
