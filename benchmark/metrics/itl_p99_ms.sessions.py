"""99th percentile of the gap between two successive tokens of one
request, in a cell that is judged on ``serve_tok_s``: all ``gaps_ms``
(differences of ``Request.token_times``) of the ``request`` records
written inside the traced window (program_span), as
``itl_p95_ms.serve`` takes them.

This is what the engine's order of work costs a decoding slot while a
long prompt comes in.  One chunk, then one tick: a gap is a chunk and a
tick long, and the 99th percentile stays near that.  A prompt admitted
whole holds every decoding slot for all its chunks; that is one gap in
some tens, so the 95th percentile does not see it (it FALLS, because
the many gaps of a chunk and a tick are gone) and the 99th does.
"""
from benchmark import span_reduce

NAME = "itl_p99_ms.sessions"


def read(ctx):
    records = span_reduce.ring(NAME)
    if records is None:
        return None
    requests = span_reduce.named(records, "request")
    gaps = [g for r in requests for g in r.get("gaps_ms") or ()]
    span_reduce.say(f"{NAME}: {len(gaps)} gaps of {len(requests)} requests")
    if not gaps:
        return None
    return span_reduce.percentile(gaps, 99)
