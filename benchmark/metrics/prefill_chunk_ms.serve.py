"""Mean duration of one chunk of a prompt that is admitted in chunks:
the ``engine.prefill_chunk`` spans whose ``bucket`` is the cell's
largest prefill bucket (a whole chunk: the program and the fetch of its
logits, so the device's time for it), over the spans that closed inside
the traced window (program_span).  The occupied slots' decoding waits
this long between two ticks while a long prompt comes in."""
from benchmark import span_reduce

NAME = "prefill_chunk_ms.serve"


def read(ctx):
    records = span_reduce.ring(NAME)
    if records is None:
        return None
    largest = max(ctx["cell"].traffic["server"]["prefill_buckets"])
    chunks = [r for r in span_reduce.named(records, "engine.prefill_chunk")
              if r.get("bucket") == largest]
    span_reduce.say(f"{NAME}: {len(chunks)} chunks of {largest}")
    if not chunks:
        return None
    return 1e3 * sum(r["dur_s"] for r in chunks) / len(chunks)
