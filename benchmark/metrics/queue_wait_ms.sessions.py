"""Median time a request waited in the admission queue before a slot
took it, in a cell that is judged on ``serve_tok_s``:
``queue_wait_ms`` of the ``request`` records written inside the traced
window (program_span), as ``queue_wait_ms.serve`` takes them.  While a
prompt goes in chunk by chunk no other request is admitted, so this is
what an admission in chunks costs the requests behind it."""
from benchmark import span_reduce

NAME = "queue_wait_ms.sessions"


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
