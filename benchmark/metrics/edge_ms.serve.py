"""The host's critical path between two programs: from the end of an
``engine.wait`` (the device has finished) to the end of the next
``engine.launch`` (it has the next program), less any ``engine.idle``
inside; mean over the pairs that closed inside the traced window
(program_span; ``benchmark/edge_reduce.py``).  What sampling on the
device, host values handed to the launch, or a launch ahead of the
fetch would shorten."""
from benchmark import edge_reduce, span_reduce

NAME = "edge_ms.serve"


def read(ctx):
    records = span_reduce.ring(NAME)
    if records is None:
        return None
    found = edge_reduce.edges(records)
    if not found:
        span_reduce.say(f"{NAME}: no engine.wait followed by an "
                        "engine.launch; no value")
        return None
    span_reduce.say(f"{NAME}: {edge_reduce.describe(found)}")
    return 1e3 * sum(e["s"] for e in found) / len(found)
