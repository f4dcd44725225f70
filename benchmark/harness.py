"""Finding a cell's pieces by name.

``BENCHMARK.json`` names a cell's configuration and traffic mix and the
metrics it reports; everything else is a file found by that name under
``benchmark/``: ``configs/<config>.json``, ``traffic/<traffic>.json``
(which names its ``driver``), ``limits/<cell>.json``,
``drivers/<driver>.py``, ``families/<family>.py``,
``metrics/<metric>.py``.  Adding a cell, a configuration, a traffic mix,
a driver, a family or a per-layer metric is adding files and entries;
nothing here lists them.
"""
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class BenchmarkError(Exception):
    """The benchmark's files do not fit together."""


class Cell:
    def __init__(self, name, chips, config, traffic, limits, end_to_end,
                 per_layer):
        self.name, self.chips = name, chips
        self.config, self.traffic, self.limits = config, traffic, limits
        self.end_to_end, self.per_layer = end_to_end, per_layer


def _file(base, kind, name, ending):
    if not _NAME.match(name):
        raise BenchmarkError(f"{kind} name {name!r} is not a plain name")
    path = os.path.join(base, kind, name + ending)
    if not os.path.isfile(path):
        raise BenchmarkError(f"no file {path} for {kind} {name!r}")
    return path


def _json(base, kind, name):
    with open(_file(base, kind, name, ".json")) as f:
        return json.load(f)


def _module(base, kind, name):
    path = _file(base, kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, re.sub(r"\W", "_", name)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _overlay(data, rehearse):
    """A data file's ``rehearse`` block holds the tiny sizes of the CPU
    rehearsal; it replaces the top-level keys it names."""
    data = dict(data)
    tiny = data.pop("rehearse", {})
    if rehearse:
        data.update(tiny)
    return data


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell_name, rehearse=False, root=ROOT):
    """The cell ``cell_name`` of ``<root>/BENCHMARK.json`` with its
    files read."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    base = os.path.join(root, "benchmark")
    entry = next((w for w in bench["workloads"] if w["name"] == cell_name),
                 None)
    if entry is None:
        raise BenchmarkError(
            f"no workload {cell_name!r} in BENCHMARK.json (it has "
            f"{[w['name'] for w in bench['workloads']]})")
    conf = next((c for c in bench["configs"] if c["name"] == entry["config"]),
                None)
    if conf is None:
        raise BenchmarkError(f"no configuration {entry['config']!r}")
    with open(os.path.join(root, conf["file"])) as f:
        config = _overlay(json.load(f), rehearse)
    traffic = _overlay(_json(base, "traffic", entry["traffic"]), rehearse)
    limits = _overlay(_json(base, "limits", cell_name), rehearse)
    e2e = [m for m in bench["end_to_end"] if _reports(m, cell_name)]
    per_layer = [m for m in bench["per_layer"] if _reports(m, cell_name)]
    moved = {m["name"] for m in e2e}
    for m in per_layer:
        if m["moves"] not in moved:
            raise BenchmarkError(
                f"{m['name']} moves {m['moves']}, which {cell_name} does "
                "not report")
    return Cell(cell_name, int(entry["chips"]), config, traffic, limits,
                e2e, per_layer)


def load_driver(name, root=ROOT):
    return _module(os.path.join(root, "benchmark"), "drivers", name)


def load_family(name, root=ROOT):
    return _module(os.path.join(root, "benchmark"), "families", name)


def load_metric(name, root=ROOT):
    return _module(os.path.join(root, "benchmark"), "metrics", name)


def peak_of(device_kind, root=ROOT):
    """The chip's published peaks; a device that is not in the table is
    an error, never a default."""
    with open(os.path.join(root, "benchmark", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(it has {sorted(table)})")
    return table[device_kind]
