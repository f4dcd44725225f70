"""The latent attention's share of its roofline: least time to read the
live latent pages of the window's slot-ticks (whole pages, never the
pool; and the scope's weights once a call), plus the prefills' expanded
attention from its operations, over the device time in scope
``mla_attn`` of the step and prefill programs."""
from benchmark import work_ling


def read(ctx):
    return work_ling.scope_roofline(ctx, "mla_attn", "mla_attn")
