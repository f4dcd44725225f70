"""Survival-layer checkpoint tests (ISSUE-11 tentpole).

The acceptance bar: kill-and-resume parity — SIGKILL at an arbitrary
step plus auto-resume must produce params identical to an uninterrupted
run at the same step count — and capture must add zero per-batch host
syncs (the async-stack property PRs 4/5/7/10 carry).
"""
import json
import os
import signal
import subprocess
import sys
import tempfile
import textwrap
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import checkpoint as ckpt  # noqa: E402
from mxnet_tpu import ndarray as nd  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.trainer import FusedTrainer  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _data(n=64, dim=8, seed=0):
    rs = np.random.RandomState(seed)
    return (rs.randn(n, dim).astype(np.float32),
            (rs.rand(n) * 4).astype(np.float32))


def _fixed_params(dim=8):
    rs = np.random.RandomState(3)
    return {
        "fc1_weight": nd.array(rs.randn(16, dim).astype(np.float32) * 0.1),
        "fc1_bias": nd.zeros((16,)),
        "fc2_weight": nd.array(rs.randn(4, 16).astype(np.float32) * 0.1),
        "fc2_bias": nd.zeros((4,)),
    }


def _trainer(optimizer="adam"):
    mx.random.seed(7)
    t = FusedTrainer(_mlp(), optimizer=optimizer,
                     optimizer_params={"lr": 0.05})
    t.init(data=(8, 8), softmax_label=(8,))
    return t


def _steps(t, lo, hi, X, Y):
    for i in range(lo, hi):
        b = slice((i % 8) * 8, (i % 8 + 1) * 8)
        t.step(data=X[b], softmax_label=Y[b])


# ---------------------------------------------------------------------------
# format: manifest, atomicity, corruption fallback, retention
# ---------------------------------------------------------------------------
def test_save_load_roundtrip(tmp_path):
    arrays = {"a/x": np.arange(12, dtype=np.float32).reshape(3, 4),
              "b/y": np.ones((2,), np.int32)}
    w = ckpt.save(str(tmp_path), 5, arrays, meta={"epoch": 1},
                  background=True)
    w.wait()
    assert os.path.basename(w.path) == "ckpt-000000000005"
    loaded, manifest = ckpt.load(w.path)
    assert manifest["meta"]["epoch"] == 1
    assert manifest["step"] == 5
    for k in arrays:
        np.testing.assert_array_equal(arrays[k], loaded[k])
        assert manifest["arrays"][k]["crc32"] >= 0
        assert manifest["arrays"][k]["sharding"]


def test_incomplete_checkpoint_is_invisible(tmp_path):
    """A directory without a manifest (a torn write) is not a
    checkpoint: list/latest skip it entirely."""
    torn = tmp_path / "ckpt-000000000003"
    torn.mkdir()
    (torn / "a00000.npy").write_bytes(b"garbage")
    assert ckpt.list_checkpoints(str(tmp_path)) == []
    assert ckpt.latest(str(tmp_path)) is None


def test_failed_write_publishes_nothing(tmp_path, monkeypatch):
    """An injected writer crash (ckpt_write:err:1) leaves no manifest
    and no temp junk a resume could trip on."""
    monkeypatch.setenv("MXTPU_FAULT_PLAN", "ckpt_write:err:1")
    w = ckpt.save(str(tmp_path), 1, {"x": np.ones(3)}, background=True)
    with pytest.raises(mx.faults.InjectedFault):
        w.wait()
    assert ckpt.list_checkpoints(str(tmp_path)) == []
    monkeypatch.setenv("MXTPU_FAULT_PLAN", "")
    # a later write on the same directory succeeds cleanly
    ckpt.save(str(tmp_path), 2, {"x": np.ones(3)}, background=False)
    assert [s for s, _ in ckpt.list_checkpoints(str(tmp_path))] == [2]


def test_corrupt_checkpoint_falls_back_with_warning(tmp_path, caplog):
    """ISSUE-11 satellite: truncated/bit-flipped newest checkpoint ->
    resume uses the previous complete one (warned), never garbage."""
    ckpt.save(str(tmp_path), 1, {"x": np.full(8, 1.0)}, background=False)
    ckpt.save(str(tmp_path), 2, {"x": np.full(8, 2.0)}, background=False)
    newest = ckpt.list_checkpoints(str(tmp_path))[-1][1]
    manifest = ckpt.validate(newest)
    fname = manifest["arrays"]["x"]["file"]
    with open(os.path.join(newest, fname), "r+b") as f:
        f.seek(-3, os.SEEK_END)
        f.write(b"\xff\xff\xff")  # bit flip -> checksum mismatch
    with pytest.raises(ckpt.CheckpointCorrupt):
        ckpt.validate(newest)
    import logging

    with caplog.at_level(logging.WARNING, "mxnet_tpu.checkpoint"):
        best = ckpt.latest(str(tmp_path))
    assert best is not None and best.endswith("ckpt-000000000001")
    assert any("corrupt" in r.message for r in caplog.records)
    arrays, _ = ckpt.load(best)
    np.testing.assert_array_equal(arrays["x"], np.full(8, 1.0))


def test_truncated_manifest_falls_back(tmp_path):
    ckpt.save(str(tmp_path), 1, {"x": np.zeros(4)}, background=False)
    ckpt.save(str(tmp_path), 2, {"x": np.ones(4)}, background=False)
    newest = ckpt.list_checkpoints(str(tmp_path))[-1][1]
    mpath = os.path.join(newest, ckpt.MANIFEST)
    with open(mpath, "r+b") as f:
        f.truncate(20)
    best = ckpt.latest(str(tmp_path))
    assert best.endswith("ckpt-000000000001")


def test_retention_prunes_oldest(tmp_path):
    for step in range(1, 6):
        ckpt.save(str(tmp_path), step, {"x": np.full(4, step)},
                  keep=2, background=False)
    steps = [s for s, _ in ckpt.list_checkpoints(str(tmp_path))]
    assert steps == [4, 5]


def test_manager_due_and_single_inflight_writer(tmp_path):
    mgr = ckpt.CheckpointManager(str(tmp_path), every=4, keep=2)
    assert not mgr.due(3)
    assert mgr.due(4)
    w = mgr.save(4, {"x": np.zeros(4)})
    assert not mgr.due(4)  # same step never saved twice
    mgr.wait()
    assert w.exc is None


# ---------------------------------------------------------------------------
# FusedTrainer resume
# ---------------------------------------------------------------------------
def test_fused_trainer_kill_resume_step_parity(tmp_path):
    """Train 10 straight vs train 6 + checkpoint + fresh-process-shaped
    restore + 4 more: params must be bit-identical."""
    X, Y = _data()
    t1 = _trainer()
    _steps(t1, 0, 10, X, Y)
    straight = {k: np.asarray(v) for k, v in t1.params.items()}

    t2 = _trainer()
    _steps(t2, 0, 6, X, Y)
    t2.save_state(str(tmp_path), epoch=0, nbatch=5,
                  background=True).wait()

    t3 = _trainer()  # fresh init (different weights until restore)
    meta = t3.restore_state(str(tmp_path))
    assert meta["step"] == 6
    _steps(t3, 6, 10, X, Y)
    for k in straight:
        np.testing.assert_array_equal(
            straight[k], np.asarray(t3.params[k]), err_msg=k)
    # optimizer state resumed too (adam moments), not just weights
    for k, slots in t1.opt_state.items():
        for i, s in enumerate(slots):
            np.testing.assert_array_equal(
                np.asarray(s), np.asarray(t3.opt_state[k][i]),
                err_msg=f"{k}:{i}")


def test_restore_rejects_signature_mismatch(tmp_path):
    t = _trainer()
    t.save_state(str(tmp_path), background=False)
    other = FusedTrainer(
        mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            mx.sym.Variable("data"), num_hidden=4), name="softmax"),
        optimizer="adam")
    other.init(data=(8, 8), softmax_label=(8,))
    with pytest.raises(ckpt.CheckpointError, match="different graph"):
        other.restore_state(str(tmp_path))


def test_fused_trainer_fit_resume_mid_epoch(tmp_path):
    """fit-level resume: interrupt mid-epoch, resume=True replays the
    cursor and lands on the uninterrupted run's exact params."""
    X, Y = _data(n=80)

    def run(interrupt_after=None, resume=None):
        it = mx.io.NDArrayIter(X, Y, batch_size=8, shuffle=False)
        t = _trainer()
        cb = None
        if interrupt_after is not None:
            def cb(param):
                if param.nbatch == interrupt_after:
                    raise KeyboardInterrupt
        mgr = ckpt.CheckpointManager(str(tmp_path), every=3, keep=5)
        try:
            t.fit(it, num_epoch=1, batch_end_callback=cb,
                  checkpoint=mgr, resume=resume)
        except KeyboardInterrupt:
            mgr.wait()
        return t

    straight = run()
    straight_params = {k: np.asarray(v) for k, v in straight.params.items()}
    # fresh dir for the interrupted pair
    import shutil

    shutil.rmtree(tmp_path)
    os.makedirs(tmp_path)
    run(interrupt_after=7)  # dies after batch 7; ckpts at steps 3, 6
    assert ckpt.latest(str(tmp_path)) is not None
    resumed = run(resume=True)
    for k in straight_params:
        np.testing.assert_array_equal(
            straight_params[k], np.asarray(resumed.params[k]), err_msg=k)


def test_preempt_flag_saves_boundary_checkpoint(tmp_path):
    """SIGTERM semantics without the signal: the manager's preempted
    flag makes fit save a checkpoint at the next window boundary and
    raise Preempted naming it."""
    X, Y = _data()
    it = mx.io.NDArrayIter(X, Y, batch_size=8, shuffle=False)
    t = _trainer()
    mgr = ckpt.CheckpointManager(str(tmp_path), every=0, keep=3)

    def cb(param):
        if param.nbatch == 2:
            mgr.preempted = True  # what the SIGTERM handler sets

    with pytest.raises(ckpt.Preempted, match="resume"):
        t.fit(it, num_epoch=1, batch_end_callback=cb, checkpoint=mgr)
    path = ckpt.latest(str(tmp_path))
    assert path is not None
    _, manifest = ckpt.load(path)
    assert manifest["meta"]["nbatch"] == 3  # boundary after the flag


# ---------------------------------------------------------------------------
# Module resume
# ---------------------------------------------------------------------------
def _module_run(tmp_path, X, Y, num_epoch=2, resume=None, every=3,
                interrupt_at=None, optimizer="adam"):
    it = mx.io.NDArrayIter(X, Y, batch_size=8, shuffle=False)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    cb = None
    if interrupt_at is not None:
        def cb(param):
            if (param.epoch, param.nbatch) == interrupt_at:
                raise KeyboardInterrupt
    mgr = ckpt.CheckpointManager(str(tmp_path), every=every, keep=8)
    try:
        mod.fit(it, optimizer=optimizer,
                optimizer_params=(("learning_rate", 0.05),),
                num_epoch=num_epoch, arg_params=_fixed_params(),
                checkpoint=mgr, resume=resume, batch_end_callback=cb)
    except KeyboardInterrupt:
        mgr.wait()
    arg, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in arg.items()}


def test_module_fit_kill_resume_parity(tmp_path):
    """Module path (kvstore fused updates + adam counters): interrupted
    + resumed run must equal the uninterrupted one bit-for-bit."""
    X, Y = _data(n=64)
    straight = _module_run(tmp_path / "a", X, Y)
    _module_run(tmp_path / "b", X, Y, interrupt_at=(1, 2))
    resumed = _module_run(tmp_path / "b", X, Y, resume=True)
    for k in straight:
        np.testing.assert_array_equal(straight[k], resumed[k], err_msg=k)


def test_module_resume_of_finished_run_is_noop(tmp_path):
    X, Y = _data(n=64)
    first = _module_run(tmp_path, X, Y)
    again = _module_run(tmp_path, X, Y, resume=True)
    for k in first:
        np.testing.assert_array_equal(first[k], again[k], err_msg=k)


# ---------------------------------------------------------------------------
# zero-per-batch-sync with checkpointing ARMED (acceptance criterion)
# ---------------------------------------------------------------------------
def test_ckpt_armed_keeps_zero_per_batch_syncs(tmp_path, monkeypatch):
    """MXTPU_CKPT_EVERY armed must not add per-batch host syncs: the
    capture is an async device copy + a writer thread — the loop's
    asnumpy/wait count stays batch-count-independent."""
    from mxnet_tpu import engine

    monkeypatch.setenv("MXTPU_CKPT_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_CKPT_EVERY", "2")
    counts = {"asnumpy": 0, "wait": 0}
    orig_asnumpy = nd.NDArray.asnumpy
    orig_wait = engine.wait_for_var

    def counted_asnumpy(self):
        counts["asnumpy"] += 1
        return orig_asnumpy(self)

    def counted_wait(arr):
        counts["wait"] += 1
        return orig_wait(arr)

    def run(nbatch):
        counts["asnumpy"] = counts["wait"] = 0
        X, Y = _data(n=8 * nbatch)
        it = mx.io.NDArrayIter(X, Y, batch_size=8, shuffle=False)
        mod = mx.mod.Module(_mlp(), context=mx.cpu())
        mod.fit(it, optimizer="sgd",
                optimizer_params=(("learning_rate", 0.1),), num_epoch=1,
                arg_params=_fixed_params())
        return counts["asnumpy"] + counts["wait"]

    monkeypatch.setattr(nd.NDArray, "asnumpy", counted_asnumpy)
    monkeypatch.setattr(engine, "wait_for_var", counted_wait)
    small = run(4)
    large = run(16)
    assert large == small, (small, large)
    # and the checkpoints actually landed
    assert ckpt.list_checkpoints(str(tmp_path))


# ---------------------------------------------------------------------------
# subprocess SIGKILL: the real preemption shape
# ---------------------------------------------------------------------------
_KILL_SCRIPT = textwrap.dedent("""
    import os, sys, json
    import numpy as np
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, {repo!r})
    import mxnet_tpu as mx
    from mxnet_tpu.trainer import FusedTrainer

    mode = sys.argv[1]          # straight | victim | resume
    ckdir = sys.argv[2]
    outfile = sys.argv[3]

    def net():
        d = mx.sym.Variable("data")
        fc1 = mx.sym.FullyConnected(d, num_hidden=16, name="fc1")
        a = mx.sym.Activation(fc1, act_type="relu")
        fc2 = mx.sym.FullyConnected(a, num_hidden=4, name="fc2")
        return mx.sym.SoftmaxOutput(fc2, name="softmax")

    rs = np.random.RandomState(0)
    X = rs.randn(96, 8).astype(np.float32)
    Y = (rs.rand(96) * 4).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=8, shuffle=False)
    mx.random.seed(7)
    t = FusedTrainer(net(), optimizer="adam",
                     optimizer_params={{"lr": 0.05}})
    from mxnet_tpu import checkpoint as ck
    mgr = ck.CheckpointManager(ckdir, every=3, keep=10)

    cb = None
    if mode == "victim":
        def cb(param):
            # tell the parent we are mid-epoch and killable — but only
            # once a COMPLETE checkpoint exists (the background writer
            # races the dispatch loop; a kill before any publish would
            # just test the fresh-start path).  Wait for that publish:
            # on a loaded machine the 24 batches can be over before
            # the writer has renamed its first file, and a victim that
            # never says KILLME exits 0
            if param.nbatch >= 7:
                import time
                deadline = time.monotonic() + 120
                while ck.latest(ckdir) is None \
                        and time.monotonic() < deadline:
                    time.sleep(0.05)
                print("KILLME", flush=True)
                time.sleep(60)   # parent SIGKILLs us here
    t.fit(it, num_epoch=2, checkpoint=mgr,
          resume=(mode == "resume"), batch_end_callback=cb)
    params = {{k: np.asarray(v).tolist() for k, v in t.params.items()}}
    with open(outfile, "w") as f:
        json.dump(params, f)
    print("DONE", flush=True)
""")


def test_subprocess_sigkill_resume_parity(tmp_path):
    """The acceptance test: SIGKILL a training run mid-epoch, rerun
    with resume=True, and land on params identical to an uninterrupted
    run of the same schedule."""
    script = tmp_path / "train.py"
    script.write_text(_KILL_SCRIPT.format(repo=REPO))
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(mode, ckdir, outfile, kill=False):
        proc = subprocess.Popen(
            [sys.executable, str(script), mode, str(ckdir), str(outfile)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        if not kill:
            out, _ = proc.communicate(timeout=300)
            assert proc.returncode == 0, out[-3000:]
            return out
        # wait for the KILLME marker, then SIGKILL — the iterator is
        # mid-epoch, the writer may be mid-write: the atomic-rename
        # format must shrug all of it off
        deadline = time.monotonic() + 300
        for line in proc.stdout:
            if "KILLME" in line:
                break
            if time.monotonic() > deadline:
                proc.kill()
                pytest.fail("victim never reached the kill point")
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=60)
        assert proc.returncode == -signal.SIGKILL
        return None

    straight_out = tmp_path / "straight.json"
    run("straight", tmp_path / "ck_straight", straight_out)
    ckdir = tmp_path / "ck"
    run("victim", ckdir, tmp_path / "unused.json", kill=True)
    assert ckpt.latest(str(ckdir)) is not None, "no checkpoint survived"
    resumed_out = tmp_path / "resumed.json"
    run("resume", ckdir, resumed_out)
    straight = json.loads(straight_out.read_text())
    resumed = json.loads(resumed_out.read_text())
    assert straight.keys() == resumed.keys()
    for k in straight:
        np.testing.assert_array_equal(
            np.asarray(straight[k]), np.asarray(resumed[k]), err_msg=k)


# ---------------------------------------------------------------------------
# resume telemetry
# ---------------------------------------------------------------------------
def test_resume_counts_telemetry(tmp_path):
    import mxnet_tpu.telemetry as tm

    tm.reset()
    tm.enable()
    try:
        t = _trainer()
        X, Y = _data()
        _steps(t, 0, 2, X, Y)
        t.save_state(str(tmp_path), background=False)
        t2 = _trainer()
        t2.restore_state(str(tmp_path))
        fam = {f.name: f for f in tm.get_registry().collect()}
        total = sum(v for _, v in
                    fam["checkpoint_resume_total"].samples())
        assert total >= 1
        assert "checkpoint_write_seconds" in fam
        bytes_total = sum(v for _, v in
                          fam["checkpoint_bytes_total"].samples())
        assert bytes_total > 0
    finally:
        tm.disable()
        tm.reset()
