"""Share of the engine thread's time spent admitting: seconds in
``engine.admit`` over seconds in ``engine.admit`` + ``engine.tick`` +
``engine.idle``, all from the spans that closed inside the traced
window (program_span).  Every slot waits while one request is
admitted."""
from benchmark import span_reduce

NAME = "admit_share.serve"


def read(ctx):
    records = span_reduce.ring(NAME)
    if records is None:
        return None
    admit = span_reduce.total_s(records, "engine.admit")
    whole = admit + span_reduce.total_s(records, "engine.tick",
                                        "engine.idle")
    span_reduce.say(
        f"{NAME}: {len(span_reduce.named(records, 'engine.admit'))} "
        f"admissions, {len(span_reduce.named(records, 'engine.tick'))} "
        f"ticks, {whole:.3f} s on the engine thread")
    if not whole:
        return None
    return 100.0 * admit / whole
