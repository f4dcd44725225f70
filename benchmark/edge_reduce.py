"""The edge of a program, read from inside: what the engine thread does
between the device finishing one program and having the next.

Under every program it runs (``engine.step``, ``engine.prefill``,
``engine.prefill_chunk``) the serving scheduler writes three leaf
spans, one open at a time: ``engine.launch`` (the backend's call up to
its return: allocator, argument transfers, the jitted call),
``engine.wait`` (``block_until_ready``: the device finishing) and
``engine.fetch`` (the copy to the host array the sampler reads).  Every
``phase()`` record holds ``start_ns``, its start on the wall clock the
profiler stamps the device's operations with, so gaps between two spans
are differences of one clock.

An **edge** is the host's critical path between two programs: from the
end of an ``engine.wait`` to the end of the next ``engine.launch``,
less any ``engine.idle`` inside it (the engine had nothing to launch).
A launch that no wait precedes -- a block decoder's admission fetches
nothing, so its prefill's launch is followed at once by the step's --
closes no edge: the device is still busy with the program before.

Like ``span_reduce`` this works on plain lists of dicts; the readers
(``benchmark/metrics/edge_ms.serve.py`` and its neighbours) hand it
``span_reduce.ring(...)``.
"""
from benchmark.span_reduce import named
from benchmark.trace_reduce import union_seconds

LEAVES = ("engine.launch", "engine.wait", "engine.fetch")
PROGRAMS = ("engine.step", "engine.prefill", "engine.prefill_chunk")


def interval(record, base_ns=0):
    """``(start, end)`` in seconds since ``base_ns``: from ``start_ns``
    where the record has it, else from the end stamp ``t``."""
    if "start_ns" in record:
        start = (record["start_ns"] - base_ns) * 1e-9
    else:
        start = record["t"] - record["dur_s"] - base_ns * 1e-9
    return start, start + record["dur_s"]


def _covered(lo, hi, intervals):
    return union_seconds([(max(s, lo), min(e, hi)) for s, e in intervals
                          if e > lo and s < hi])


def edges(records):
    """One dict an edge, in order of time: ``after`` and ``before`` (the
    ``program`` of the wait that opens it and of the launch that closes
    it), ``s`` (its seconds, idle taken out) and what of it lies in
    ``fetch``, ``sample`` and ``launch`` spans; ``rest`` is what they
    leave: finishing requests, the queue, the engine's own
    bookkeeping."""
    base = min((r["start_ns"] for r in records if "start_ns" in r),
               default=0)
    at = {name: [interval(r, base) for r in named(records, name)]
          for name in ("engine.idle", "engine.fetch", "engine.sample",
                       "engine.launch")}
    marks = sorted(named(records, "engine.wait", "engine.launch"),
                   key=lambda r: interval(r, base)[1])
    out, wait = [], None
    for r in marks:
        if r["name"] == "engine.wait":
            wait = r
        elif wait is not None:
            lo, hi = interval(wait, base)[1], interval(r, base)[1]
            edge = {"after": wait.get("program"),
                    "before": r.get("program"),
                    "s": (hi - lo) - _covered(lo, hi, at["engine.idle"])}
            for part in ("fetch", "sample", "launch"):
                edge[part] = _covered(lo, hi, at["engine." + part])
            edge["rest"] = edge["s"] - edge["fetch"] - edge["sample"] \
                - edge["launch"]
            out.append(edge)
            wait = None
    return out


def by_program(records, name):
    """``"step 118 x 1.234 ms, prefill 120 x 2.345 ms"``: the spans
    called ``name`` by their ``program``, for a reader's line on
    stderr."""
    groups = {}
    for r in named(records, name):
        groups.setdefault(str(r.get("program")), []).append(r["dur_s"])
    return ", ".join("%s %d x %.3f ms" % (p, len(d), 1e3 * sum(d) / len(d))
                     for p, d in sorted(groups.items()))


def leaf_cover(records):
    """Share (%) of the seconds of the program spans that their leaves
    cover; ``None`` without a program span."""
    programs = named(records, *PROGRAMS)
    whole = sum(r["dur_s"] for r in programs)
    if not whole:
        return None
    sids = {r["sid"] for r in programs}
    inside = sum(r["dur_s"] for r in named(records, *LEAVES)
                 if r.get("parent") in sids)
    return 100.0 * inside / whole


def describe(found):
    """The edges' parts as one line: counts by what stood before and
    after, and the mean of each part an edge."""
    n = len(found)
    kinds = {}
    for e in found:
        key = "%s>%s" % (e["after"], e["before"])
        kinds[key] = kinds.get(key, 0) + 1
    parts = ", ".join("%s %.3f" % (p, 1e3 * sum(e[p] for e in found) / n)
                      for p in ("fetch", "sample", "rest", "launch"))
    return "%d edges (%s); ms an edge: %s" % (
        n, ", ".join("%s %d" % kv for kv in sorted(kinds.items())), parts)
