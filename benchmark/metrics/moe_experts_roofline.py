"""The routed experts' share of their roofline: least time to read the
weights of the distinct held experts actually hit (the program's
counter, summed over layers and program calls of the window) and to
move the routed rows in and out, over the device time in scope
``moe.experts`` of the step and prefill programs (the TPU compiler's
grouped-matmul kernels among it)."""
from benchmark import work_ling


def read(ctx):
    return work_ling.scope_roofline(ctx, "moe.experts", "moe_experts")
