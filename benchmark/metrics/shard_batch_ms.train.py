"""Mean host time of ``train.shard_batch``: ``FusedTrainer.step``
placing one host batch on the device, before the step program is
called (program_span)."""
from benchmark import span_reduce

NAME = "shard_batch_ms.train"


def read(ctx):
    records = span_reduce.ring(NAME)
    if records is None:
        return None
    spans = span_reduce.named(records, "train.shard_batch")
    span_reduce.say(f"{NAME}: {len(spans)} steps")
    if not spans:
        return None
    return 1e3 * sum(r["dur_s"] for r in spans) / len(spans)
