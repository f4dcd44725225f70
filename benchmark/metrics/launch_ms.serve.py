"""Mean duration of ``engine.launch``: a backend's ``step`` or
``admit_chunk`` up to its return -- the allocator, the snapshots, the
arguments' transfers and the jitted call, all before the device has
anything to do (program_span)."""
from benchmark import edge_reduce, span_reduce

NAME = "launch_ms.serve"
SPAN = "engine.launch"


def read(ctx):
    records = span_reduce.ring(NAME)
    if records is None:
        return None
    n = len(span_reduce.named(records, SPAN))
    span_reduce.say(f"{NAME}: {n} {SPAN}: "
                    f"{edge_reduce.by_program(records, SPAN)}")
    return 1e3 * span_reduce.total_s(records, SPAN) / n if n else None
