"""Mean duration of ``engine.fetch``: the conversion of what a
program returned to the host array the sampler reads, after the device
is done (program_span)."""
from benchmark import edge_reduce, span_reduce

NAME = "fetch_ms.serve"
SPAN = "engine.fetch"


def read(ctx):
    records = span_reduce.ring(NAME)
    if records is None:
        return None
    n = len(span_reduce.named(records, SPAN))
    span_reduce.say(f"{NAME}: {n} {SPAN}: "
                    f"{edge_reduce.by_program(records, SPAN)}; wait: "
                    f"{edge_reduce.by_program(records, 'engine.wait')}")
    return 1e3 * span_reduce.total_s(records, SPAN) / n if n else None
