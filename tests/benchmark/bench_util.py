"""Shared by the benchmark's tests: the repo root on ``sys.path``, one
way to drive ``benchmark/run.py`` in this process, and a throw-away copy
of the benchmark to which a test adds cells."""
import contextlib
import io
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def cells(driver=None):
    """Names of BENCHMARK.json's cells, or of those a driver runs."""
    from benchmark import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    return [c for c in names if driver is None
            or harness.resolve(c).traffic["driver"] == driver]


def run_cell(*argv):
    """(exit code, parsed last stdout line or None, stderr text) of
    ``benchmark/run.py`` called in this process."""
    import jax

    from benchmark import run

    # the run switches JAX's persistent cache on for its process; the
    # tests that share this worker get the settings they had back
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    had = {k: getattr(jax.config, k) for k in keys}
    out, err = io.StringIO(), io.StringIO()
    try:
        with contextlib.redirect_stdout(out), \
                contextlib.redirect_stderr(err):
            code = run.main(list(argv))
    finally:
        for k, v in had.items():
            jax.config.update(k, v)
    lines = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    last = None
    if lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            last = None
    return code, last, err.getvalue()


def copy_of_the_benchmark(tmp_path):
    """``BENCHMARK.json``, ``benchmark/`` and a link to the program in a
    directory of the test's own; returns its path."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "mxnet_tpu"),
               os.path.join(root, "mxnet_tpu"))
    return root


# ResNet-50's files are in benchmark/ but its cell is not in
# BENCHMARK.json (PERF.md, open questions: too little of the chip's
# memory by the driver's reading).  The tests add it the way a later PR
# would: entries, and the cell's limits file.
RESNET_LIMITS = {"loss_gap": 0.001, "grad_norm_gap": 0.02,
                 "change_norm_gap": 0.06}


def add_resnet_cell(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "resnet-50", "reduced": [], "why": "a test",
        "source": "He et al., arXiv:1512.03385, table 1 (50-layer)",
        "file": "benchmark/configs/resnet-50.json"})
    bench["workloads"].append({
        "name": "resnet50_train", "config": "resnet-50",
        "traffic": "imagenet_b256", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "lm_train" in m.get("workloads", ()) \
                and m["name"] != "flash_attn_roofline":
            m["workloads"].append("resnet50_train")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(root, "benchmark", "limits",
                           "resnet50_train.json"), "w") as f:
        json.dump(RESNET_LIMITS, f)
