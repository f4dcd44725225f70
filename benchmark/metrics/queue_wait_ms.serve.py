"""Median time a request waited in the admission queue before a slot
took it: ``queue_wait_ms`` of the ``request`` records the scheduler
wrote while the traced window's profiler session ran (program_span)."""
from benchmark import span_reduce

NAME = "queue_wait_ms.serve"


def read(ctx):
    records = span_reduce.ring(NAME)
    if records is None:
        return None
    waits = [r["queue_wait_ms"] for r in span_reduce.named(records, "request")
             if r.get("queue_wait_ms") is not None]
    span_reduce.say(f"{NAME}: {len(waits)} requests")
    if not waits:
        return None
    return span_reduce.percentile(waits, 50)
