"""95th percentile of the gap between two successive tokens of one
request: all ``gaps_ms`` (differences of ``Request.token_times``) of
the ``request`` records written inside the traced window
(program_span).  ``tpot_p95_ms`` averages these over a request."""
from benchmark import span_reduce

NAME = "itl_p95_ms.serve"


def read(ctx):
    records = span_reduce.ring(NAME)
    if records is None:
        return None
    requests = span_reduce.named(records, "request")
    gaps = [g for r in requests for g in r.get("gaps_ms") or ()]
    span_reduce.say(f"{NAME}: {len(gaps)} gaps of {len(requests)} requests")
    if not gaps:
        return None
    return span_reduce.percentile(gaps, 95)
