"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

``jax.profiler.ProfileData`` reads the file with nothing but JAX.  A
device plane (``/device:TPU:<n>``) carries a line of XLA operations;
the host plane carries the benchmark's own ``TraceAnnotation`` spans on
the same clock.  The traced window is the span ``bench_window``.

``reduce`` works on plain lists, so it is tested without a trace file:

- ``busy_s``: union of the device-op intervals inside the window, mean
  over the chips used;
- ``device_ops``: the ten operations with most device time (self time:
  an operation that encloses others on the line is charged only for
  what they leave), instances of one kind and shape added up under a
  short name (``copy bf16[1025,24,16,16,128] x48``);
- ``custom_call_s`` / ``custom_calls``: device time and count of the
  Mosaic kernels (custom calls whose target is ``tpu_custom_call``;
  the compiler's own ``ConcatBitcast`` calls take no time and are not
  kernels);
- ``idle_gaps``: the five longest intervals with nothing on the first
  device, each under the name of the benchmark's host span it falls in.
"""
import functools
import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench_window"
# an event of the ops line is named by its HLO instruction's text
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
_INSTRUCTION = re.compile(
    r"^%(?P<name>[^\s=]+?)(?:\.\d+)? = (?P<shape>\(.*?\)|\S+) "
    r"(?P<op>[a-z][a-z\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


@functools.lru_cache(maxsize=None)
def short_name(event_name):
    """``%copy.177 = bf16[8,4]{1,0:T(8,128)} copy(...)`` -> ``copy
    bf16[8,4]``: the instruction's name without its number when that
    says more than the operation does, and the shape of its (first)
    result without the layout.  Anything else is cut to 80 characters."""
    m = _INSTRUCTION.match(event_name)
    if not m:
        return event_name[:80]
    shape = _LAYOUT.sub("", m["shape"])
    if shape.startswith("("):
        first = shape[1:].split(", ")[0].rstrip(")")
        shape = "(%s, ...)" % first
    what = m["op"] if m["name"] == m["op"] else "%s %s" % (m["name"], m["op"])
    return "%s %s" % (what, shape)


def union_seconds(intervals):
    """Total length covered by ``[(start, end)]`` (any order, overlaps
    allowed)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, start, end):
    """The uncovered stretches of ``[start, end]`` as ``[(s, e)]``."""
    out, at = [], start
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, end)))
        at = max(at, e)
        if at >= end:
            break
    if at < end:
        out.append((at, end))
    return [(s, e) for s, e in out if e > s]


def self_times(events):
    """{name: seconds} where an event enclosing later ones on the same
    line is charged only for the time they leave uncovered."""
    out = {}
    stack = []          # [name, end, start, child_seconds] of open events
    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, start, child = stack.pop()
            out[name] = out.get(name, 0.0) + max(0.0, (end - start) - child)
            if stack:
                stack[-1][3] += end - start
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        stack.append([name, e, s, 0.0])
    close(float("inf"))
    return out


def clip(events, start, end):
    return [(n, max(s, start), min(e, end)) for n, s, e in events
            if e > start and s < end]


def reduce(device_events, host_spans, default_gap_label, top=10,
           longest=5):
    """``device_events``: one list per chip of ``(name, start_s,
    end_s)``; ``host_spans``: ``(name, start_s, end_s)`` of the
    benchmark's annotations, ``bench_window`` among them."""
    window = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not window:
        raise ValueError("the trace holds no bench_window span")
    w0, w1 = window[0]
    busy, ops, count, calls_s, calls = [], {}, {}, 0.0, 0
    for events in device_events:
        events = clip(events, w0, w1)
        busy.append(union_seconds([(s, e) for _n, s, e in events]))
        for name, secs in self_times(events).items():
            short = short_name(name)
            ops[short] = ops.get(short, 0.0) + secs / len(device_events)
        for name, s, e in events:
            short = short_name(name)
            count[short] = count.get(short, 0) + 1
            if MOSAIC_CALL in name:
                calls_s += (e - s) / len(device_events)
                calls += 1
    calls //= max(1, len(device_events))
    spans = [(n, s, e) for n, s, e in host_spans if n != WINDOW_SPAN]
    first = clip(device_events[0], w0, w1) if device_events else []
    idle = sorted(gaps([(s, e) for _n, s, e in first], w0, w1),
                  key=lambda g: g[0] - g[1])[:longest]
    labelled = []
    for s, e in idle:
        best, best_overlap = default_gap_label, 0.0
        for name, hs, he in spans:
            overlap = min(e, he) - max(s, hs)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        labelled.append([best, e - s])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": w1 - w0,
            "busy_s": sum(busy) / len(busy) if busy else 0.0,
            "device_ops": [["%s x%d" % (n, count[n] // len(device_events)),
                            s] for n, s in top_ops],
            "idle_gaps": labelled,
            "custom_call_s": calls_s, "custom_calls": calls}


def idle_percent(trace):
    """Share of the traced window in which no operation ran on the
    device: 1 - union of the device-op intervals over the window."""
    if trace is None or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def load(path, chips, span_names=()):
    """(device_events per chip, host_spans) of one ``.xplane.pb``;
    of the host's events only ``bench_window`` and the driver's own
    ``span_names`` are kept."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    per_chip, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    per_chip[int(m.group(1))] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN or ev.name in span_names:
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    devices = [per_chip[i] for i in sorted(per_chip)][:chips]
    return devices, host


def reduce_dir(trace_dir, chips, default_gap_label, span_names=(),
               keep=None, need_device=True):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    newest = max(paths, key=os.path.getmtime)
    if keep:
        os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
        shutil.copyfile(newest, keep)
    devices, host = load(newest, chips, frozenset(span_names))
    if not devices and need_device:
        raise ValueError("the trace holds no device plane: no operation "
                         "ran on a TPU in the traced window")
    return reduce(devices, host, default_gap_label)
