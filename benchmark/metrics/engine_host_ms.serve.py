"""The engine thread's own host time for each tick: what
``engine.step`` and ``engine.prefill``, which hold the waits for the
device, leave of ``engine.tick`` and ``engine.admit`` -- their self
time and ``engine.sample``: sampling, finishing requests, bookkeeping
-- over the number of ``engine.tick`` spans (program_span)."""
from benchmark import span_reduce

NAME = "engine_host_ms.serve"


def read(ctx):
    records = span_reduce.ring(NAME)
    if records is None:
        return None
    ticks = len(span_reduce.named(records, "engine.tick"))
    span_reduce.say(f"{NAME}: {ticks} ticks")
    if not ticks:
        return None
    host = span_reduce.self_s(records, "engine.tick", "engine.admit",
                              "engine.sample")
    return 1e3 * host / ticks
