"""``benchmark/run.py`` end to end at the rehearsal sizes: the last
line's keys, what a missing chip does, and -- with the timed path
broken underneath -- that ``correct`` comes out false."""
import json
import os

import numpy as np
import pytest

from bench_util import ROOT, cells, run_cell

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", cells())
def test_rehearsal_ends_in_the_contracts_line(cell):
    code, last, err = run_cell("--workload", cell, "--seed", str(2 ** 31 + 77),
                               "--seconds", "2", "--trace", "0", "--rehearse")
    assert code == 0, err[-2000:]
    assert KEYS <= set(last) and set(last) - KEYS <= {"rehearsal", "compared"}
    assert list(last)[-1] == "compared"
    assert last["correct"] is True, last["compared"]
    assert last["rehearsal"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(last["metrics"]) == want
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for name, row in last["compared"].items():
        assert set(row) == {"value", "limit"}
        assert f"compared {name}:" in err
    assert last["compared"]["programs_lowered_in_window"]["value"] == 0


def test_without_a_chip_there_is_no_result_line():
    code, last, err = run_cell("--workload", cells()[0], "--seed", "1",
                               "--seconds", "1", "--trace", "0")
    assert code == 2 and last is None
    assert "no TPU" in err


def test_without_the_program_there_is_no_result_line(monkeypatch):
    """A directory that holds BENCHMARK.json and the benchmark's own
    files and nothing else: no result, exit code 2."""
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "mxnet_tpu" else real(name, *a))
    code, last, err = run_cell("--workload", cells()[0], "--seed", "1",
                               "--seconds", "1", "--trace", "0", "--rehearse")
    assert code == 2 and last is None
    assert "mxnet_tpu" in err


def test_an_unknown_workload_is_an_error():
    code, last, err = run_cell("--workload", "no_such_cell", "--seed", "1",
                               "--seconds", "1", "--trace", "0", "--rehearse")
    assert code == 2 and last is None and "no_such_cell" in err


def test_an_unknown_device_kind_is_an_error():
    from benchmark import harness

    assert harness.peak_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchmarkError):
        harness.peak_of("cpu")
    with pytest.raises(harness.BenchmarkError):
        harness.peak_of("TPU v5")


# ------------------------------------------- the timed path, broken
@pytest.mark.parametrize("cell", cells("train"))
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        cell, monkeypatch):
    from mxnet_tpu.trainer import FusedTrainer

    real = FusedTrainer.step

    def frozen(self, **batch):
        keep = self.params, self._cparams
        import jax

        saved = jax.tree_util.tree_map(lambda x: x + 0, keep)
        outs = real(self, **batch)
        self.params, self._cparams = saved
        return outs

    monkeypatch.setattr(FusedTrainer, "step", frozen)
    code, last, err = run_cell("--workload", cell, "--seed", "5",
                               "--seconds", "1", "--trace", "0", "--rehearse")
    assert code == 0, err[-2000:]
    assert last["correct"] is False
    row = last["compared"]["change_norm_gap"]
    assert row["value"] > row["limit"]


@pytest.mark.parametrize("cell", cells("train"))
def test_half_of_the_batch_left_out_is_not_correct(cell, monkeypatch):
    from mxnet_tpu.trainer import FusedTrainer

    real = FusedTrainer.step

    def half(self, **batch):
        # the second half of the rows never reaches the step: the first
        # half stands in for it, so the mean is taken over the rest
        cut = {k: np.concatenate([v[:v.shape[0] // 2]] * 2)
               for k, v in batch.items()}
        return real(self, **cut)

    monkeypatch.setattr(FusedTrainer, "step", half)
    code, last, err = run_cell("--workload", cell, "--seed", "6",
                               "--seconds", "1", "--trace", "0", "--rehearse")
    assert code == 0, err[-2000:]
    assert last["correct"] is False
    over = [n for n, r in last["compared"].items() if r["value"] > r["limit"]]
    assert "grad_norm_gap" in over


@pytest.mark.parametrize("cell", cells("serve_closed"))
def test_a_token_altered_where_it_is_produced_is_not_correct(
        cell, monkeypatch):
    from mxnet_tpu.serving.scheduler import SlotScheduler

    real = SlotScheduler._sample
    calls = {"n": 0}

    def altered(req, logits):
        tok = real(req, logits)
        calls["n"] += 1
        if calls["n"] % 2 == 0:
            tok = (tok + 1) % logits.shape[-1]
        return tok

    monkeypatch.setattr(SlotScheduler, "_sample", staticmethod(altered))
    code, last, err = run_cell("--workload", cell, "--seed", "8",
                               "--seconds", "2", "--trace", "0", "--rehearse")
    assert code == 0, err[-2000:]
    assert last["correct"] is False, (calls, err[-1500:])
    row = last["compared"]["served_logit_gap"]
    assert row["value"] > row["limit"]
