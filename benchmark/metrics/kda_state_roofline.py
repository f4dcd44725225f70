"""The KDA layers' share of their roofline: least time to read and
write each occupied slot's recurrent state once a KDA layer a tick (and
the scope's weights once a call), plus the prefills' projections and
recurrence from their operations, over the device time in scope
``kda`` of the step and prefill programs."""
from benchmark import work_ling


def read(ctx):
    return work_ling.scope_roofline(ctx, "kda", "kda_state")
