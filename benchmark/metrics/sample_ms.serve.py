"""The host's sampling loop for each tick: seconds in ``engine.sample``
(sampling every occupied slot, delivering tokens, finishing requests)
over the number of ``engine.tick`` spans (program_span)."""
from benchmark import span_reduce

NAME = "sample_ms.serve"


def read(ctx):
    records = span_reduce.ring(NAME)
    if records is None:
        return None
    ticks = len(span_reduce.named(records, "engine.tick"))
    samples = len(span_reduce.named(records, "engine.sample"))
    span_reduce.say(f"{NAME}: {samples} engine.sample over {ticks} ticks")
    if not ticks or not samples:
        return None
    return 1e3 * span_reduce.total_s(records, "engine.sample") / ticks
