"""The whole serving path's share of the chip's peak: model FLOPs of
the requests finished in the window (their prompts prefilled, their
tokens decoded, from shapes) over the window and the bf16 peak.  Small
by nature -- decoding is bound by bytes -- but it is what bounds a gain
once a kernel has left the path."""


def read(ctx):
    r, peak = ctx["record"], ctx["peak"]
    if peak is None or not r.get("model_flops"):
        return None
    return 100.0 * r["model_flops"] / (
        r["window_s"] * peak["bf16_flops_per_s"] * ctx["chips"])
