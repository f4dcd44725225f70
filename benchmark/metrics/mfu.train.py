"""The whole training step's share of the chip's peak: model FLOPs of
the window's steps (from shapes, recomputation not counted) over the
window's wall time, the bf16 peak and the chips."""


def read(ctx):
    r, peak = ctx["record"], ctx["peak"]
    if peak is None or not r.get("steps"):
        return None
    done = r["flops_per_step"] * r["steps"]
    return 100.0 * done / (r["window_s"] * peak["bf16_flops_per_s"]
                           * ctx["chips"])
