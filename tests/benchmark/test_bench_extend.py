"""A later PR adds a cell, a configuration, a traffic mix and a
per-layer metric as files and entries of BENCHMARK.json, and edits no
file the benchmark already has: shown on a throw-away copy."""
import hashlib
import json
import os
import subprocess
import sys

from bench_util import add_resnet_cell, copy_of_the_benchmark


def _digests(base):
    out = {}
    for folder, _dirs, files in os.walk(base):
        for name in files:
            path = os.path.join(folder, name)
            if "__pycache__" in path:
                continue
            with open(path, "rb") as f:
                out[os.path.relpath(path, base)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _rehearse(root, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3", "--seconds", "0.5",
         "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def _add_throwaway_cell(root):
    """New files: a configuration (the l8 one at other sizes), a traffic
    mix for the driver that is there, the cell's limits, and a reader of
    a metric nobody had."""
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "cerebras-gpt-1.3b-l8.json")) as f:
        config = json.load(f)
    config.update(config.pop("rehearse"))
    config.update(n_layer=1, n_inner=384, reduced=["n_layer"])
    with open(os.path.join(base, "configs", "throwaway-gpt.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(base, "traffic", "throwaway_b2_t64.json"),
              "w") as f:
        json.dump({"driver": "train", "batch": 2, "seq_len": 64,
                   "host_batch_pool": 3, "trace_seconds": 1}, f)
    with open(os.path.join(base, "limits", "throwaway_cell.json"), "w") as f:
        json.dump({"loss_gap": 0.001, "grad_norm_gap": 0.01,
                   "change_norm_gap": 0.02}, f)
    with open(os.path.join(base, "metrics", "steps.throwaway.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['record']['steps'])\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "throwaway-gpt", "source": "a test", "reduced": ["n_layer"],
        "file": "benchmark/configs/throwaway-gpt.json", "why": "a test"})
    bench["workloads"].append({
        "name": "throwaway_cell", "config": "throwaway-gpt",
        "traffic": "throwaway_b2_t64", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_step_ms":
            m["workloads"].append("throwaway_cell")
    bench["per_layer"].append({
        "name": "steps.throwaway", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "training loop",
        "moves": "train_step_ms", "workloads": ["throwaway_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def test_a_cell_a_configuration_and_a_metric_come_as_files(tmp_path):
    root = copy_of_the_benchmark(tmp_path)
    before = _digests(os.path.join(root, "benchmark"))
    _add_throwaway_cell(root)
    last = _rehearse(root, "throwaway_cell")
    assert last["correct"] is True, last["compared"]
    # the new cell reports the new metric and no metric that lists
    # other cells
    assert set(last["metrics"]) == {"steps.throwaway"}
    assert last["metrics"]["steps.throwaway"]["value"] >= 1
    after = _digests(os.path.join(root, "benchmark"))
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/throwaway-gpt.json", "limits/throwaway_cell.json",
        "metrics/steps.throwaway.py", "traffic/throwaway_b2_t64.json"]


def test_the_resnet50_cell_comes_as_entries_and_a_limits_file(tmp_path):
    """The ResNet-50 family, reference, configuration and traffic stay
    in the benchmark without a cell; the program agrees with the
    reference at the rehearsal's sizes."""
    root = copy_of_the_benchmark(tmp_path)
    before = _digests(os.path.join(root, "benchmark"))
    add_resnet_cell(root)
    last = _rehearse(root, "resnet50_train")
    assert last["correct"] is True, last["compared"]
    assert set(last["metrics"]) == {"host_dispatch_ms.train",
                                    "device_idle.train"}
    after = _digests(os.path.join(root, "benchmark"))
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {"limits/resnet50_train.json"}
