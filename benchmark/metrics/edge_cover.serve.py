"""How much of the device's idle time the host's path between programs
accounts for: the sum of the edges (``edge_ms.serve``'s) over the idle
seconds of the same traced window, ``window_s - busy_s`` (program_span
over device_trace).  Near 100: the idle is the host's.  Well under:
the device waits on something no span sees (dispatch-to-start latency,
the copy engine)."""
from benchmark import edge_reduce, span_reduce

NAME = "edge_cover.serve"


def read(ctx):
    records = span_reduce.ring(NAME)
    trace = ctx.get("trace")
    if records is None or trace is None:
        return None
    found = edge_reduce.edges(records)
    idle_s = trace["window_s"] - trace["busy_s"]
    if not found or idle_s <= 0:
        return None
    edge_s = sum(e["s"] for e in found)
    span_reduce.say(
        f"{NAME}: {edge_s:.3f} s in {len(found)} edges over "
        f"{idle_s:.3f} s of idle; launch + wait + fetch cover "
        f"{edge_reduce.leaf_cover(records) or 0.0:.2f}% of their "
        "parents' seconds")
    return 100.0 * edge_s / idle_s
