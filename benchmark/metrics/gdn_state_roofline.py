"""The gated-DeltaNet layers' share of their roofline: least time to
read and write each occupied slot's recurrent state once a layer a tick
(and the scope's weights once a call), plus the prefills' projections,
convolution and chunked scan from their operations, over the device time
in scopes ``gdn`` and ``gdn.state`` of the step and prefill programs."""
from benchmark import work_olmo_hybrid


def read(ctx):
    return work_olmo_hybrid.scope_roofline(ctx)
