"""Device time by the program's named scopes.

A TPU trace taken as ``run.py`` takes it (no HLO proto) names each
event of the ``XLA Ops`` line by its HLO instruction's text and carries
neither a name-scope line nor a scope statistic on the operations.  The
scope of an instruction is in the compiled program instead: its
``metadata={op_name="jit(<program>)/layer3/moe.experts/..."}``, which
``jax.named_scope`` wrote.  So the reduction takes two things:

- ``scope_map(hlo_text, scopes, by_name)``: instruction name -> scope,
  from the optimized HLO text of one compiled program.  An instruction
  belongs to the first of ``scopes`` that is a component of its
  ``op_name``; an instruction whose ``op_name`` names none (the TPU
  compiler's own grouped-matmul kernels are called
  ``ragged-dot-none``, with no path) falls to ``by_name``, a list of
  ``(prefix of the instruction's name, scope)``; what is left is
  ``"other"``.
- the trace: each event of ``XLA Ops`` belongs to the program
  (``XLA Modules`` event) that encloses its start, and is looked up in
  that program's map by its instruction name; a name the map does not
  hold counts as ``"unmatched"``, which says how far the maps can be
  trusted.

Time is self time (an enclosing ``while`` is charged only for what its
body's operations leave), clipped to the ``bench_window`` span, of the
first chip.  ``reduce`` works on plain lists, so it is tested without a
trace file.
"""
import bisect
import glob
import os
import re

from benchmark import trace_reduce

_NAMED = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_EVENT = re.compile(r"^%([^\s=]+) = ")
MODULES_LINE = "XLA Modules"


def scope_map(hlo_text, scopes, by_name=()):
    """{instruction name: scope} of one compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _NAMED.match(line)
        if m is None:
            continue
        name = m.group(1)
        scope = None
        op = _OP_NAME.search(line)
        if op is not None:
            parts = op.group(1).split("/")
            scope = next((s for s in scopes if s in parts), None)
        if scope is None:
            scope = next((s for prefix, s in by_name
                          if name.startswith(prefix)), "other")
        out[name] = scope
    return out


def program_of(module_event_name):
    """``jit_decode_step_ling(123)`` -> ``jit_decode_step_ling``."""
    return module_event_name.split("(")[0]


def reduce(op_events, module_events, maps, window):
    """``op_events`` / ``module_events``: ``(name, start_s, end_s)`` of
    one chip's ``XLA Ops`` and ``XLA Modules`` lines; ``maps``: {program
    name: scope map}; ``window``: ``(start_s, end_s)``.  Returns
    ``{program: {scope: seconds}}`` over the programs that have a map;
    events of other programs are left out."""
    w0, w1 = window
    modules = sorted((s, e, program_of(n)) for n, s, e in module_events)
    starts = [m[0] for m in modules]
    per_program = {}
    for name, s, e in op_events:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= modules[i][1]:
            continue
        program = modules[i][2]
        if program not in maps:
            continue
        m = _EVENT.match(name)
        scope = maps[program].get(m.group(1), "unmatched") if m \
            else "unmatched"
        per_program.setdefault(program, []).append((scope, s, e))
    return {program: trace_reduce.self_times(
        trace_reduce.clip(events, w0, w1))
        for program, events in per_program.items()}


def load(path):
    """(op events, module events, window) of the first chip of one
    ``.xplane.pb``; the window is None without a ``bench_window``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, window = {}, None

    def events(line):
        return [(ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events]

    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            if trace_reduce.OPS_LINE in lines and MODULES_LINE in lines:
                chips[int(m.group(1))] = (
                    events(lines[trace_reduce.OPS_LINE]),
                    events(lines[MODULES_LINE]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW_SPAN:
                        window = (ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9)
    if not chips:
        return [], [], window
    ops, modules = chips[min(chips)]
    return ops, modules, window


def reduce_dir(trace_dir, maps):
    """``reduce`` of the newest ``.xplane.pb`` under ``trace_dir``, or
    ``{}`` where there is no trace, no device plane or no window (a
    rehearsal on the CPU)."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return {}
    ops, modules, window = load(max(paths, key=os.path.getmtime))
    if not ops or window is None:
        return {}
    return reduce(ops, modules, maps, window)


def totals(by_program):
    """{scope: seconds} over all programs."""
    out = {}
    for scopes in by_program.values():
        for scope, secs in scopes.items():
            out[scope] = out.get(scope, 0.0) + secs
    return out
