"""The Olmo-Hybrid cell's own pieces: the configuration against the
published numbers, the parameter and cache arithmetic, the cell and its
traffic, the work functions and the new reader, the cell's rehearsal
with its scopes and counters -- and, with the carried state or the step
size broken underneath, that ``correct`` comes out false.

The rehearsal serves float32 (its configuration's ``rehearse``): at 64
wide a bfloat16 hidden state flips near ties among 256 logits, which
would leave the check no room between the program and a planted
fault."""
import json
import os
import re

import numpy as np
import pytest

from bench_util import ROOT, cells, run_cell

from benchmark import harness, work_olmo_hybrid as work
from benchmark.families import olmo_hybrid as fam

CONFIG = os.path.join(ROOT, "benchmark", "configs", "olmo-hybrid-7b-l8.json")
CELL = "serve_docqa_olmo_hybrid"
DRIVER = "serve_sessions_state"


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


def full(config):
    return {k: v for k, v in config.items() if k != "rehearse"}


# the published config.json's keys, as the source gives them
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


def test_every_published_key_is_kept(config):
    assert config["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    assert config["reduced"] == ["num_layers"]
    assert {k for k, v in PUBLISHED.items() if config.get(k) != v} == set()
    assert config["num_layers"] == 8
    assert "4-stage pipeline" in config["deployment"]
    for key in ("block", "qk_norm", "rotary", "gdn_output",
                "negative_eigenvalues", "decay", "decay_projection",
                "conv_taps", "bias"):
        assert config["assumed"][key]


def test_the_eight_layers_are_two_periods(config):
    s = fam.sizes(full(config))
    assert s["mixers"] == ("gdn", "gdn", "gdn", "attn") * 2
    assert (s["lin_heads"], s["d_k"], s["d_v"], s["conv"]) == (30, 96, 192, 4)
    assert (s["heads"], s["head_dim"], s["neg_eigval"]) == (30, 128, True)
    tiny = dict(config, **config["rehearse"])
    assert fam.sizes(tiny)["mixers"] == ("gdn", "gdn", "gdn", "attn")


def test_parameter_counts(config):
    s = fam.sizes(full(config))
    conv = 11520 * 4
    assert work.gdn_params(s) + conv == 88_750_080
    assert work.gdn_params(s) + conv + work.mlp_params(s) == pytest.approx(
        215.57e6, rel=1e-4)
    assert work.attn_params(s) + work.mlp_params(s) == pytest.approx(
        185.81e6, rel=1e-4)
    specs = fam.param_specs(full(config))
    total = sum(int(np.prod(v["shape"])) for v in specs.values())
    assert total == work.param_count(s) == 2_435_748_072


def test_the_cache_a_slot_and_a_token(config):
    s = fam.sizes(full(config))
    assert 6 * work.state_bytes(s) == 13_685_760
    assert work.kv_bytes_per_token(s) == 30_720
    srv = harness.resolve(CELL).traffic["server"]
    assert srv["num_pages"] == 8 * 17408 // 16
    assert srv["num_pages"] * 16 * 30_720 == pytest.approx(4.28e9, rel=1e-3)


def test_the_cell_and_its_traffic():
    assert cells(DRIVER) == [CELL]
    cell = harness.resolve(CELL)
    t = cell.traffic
    assert t["clients"] == 8 and t["asks"] == 4
    assert t["server"]["num_slots"] == 8 and t["server"]["queue_size"] == 8
    assert t["server"]["kv_block"] == 16 and t["server"]["max_len"] == 17408
    assert t["server"]["prefill_buckets"] == [128, 256, 512, 1024, 2048]
    assert t["doc_len"] == {"median": 8192, "sigma": 0.4, "min": 4096,
                            "max": 16384}
    assert t["question_len"] == {"median": 128, "sigma": 0.6, "min": 32,
                                 "max": 512}
    assert t["output_len"] == {"median": 64, "sigma": 0.6, "min": 16,
                               "max": 192}
    assert (t["check_min_chunked"], t["check_chunks"]) == (2, 3)
    assert cell.chips == 1
    reported = {m["name"] for m in cell.per_layer}
    assert reported == {
        "slot_occupancy.serve", "mfu.serve", "device_idle.serve",
        "engine_host_ms.serve", "prefill_chunk_ms.serve",
        "itl_p99_ms.sessions", "queue_wait_ms.sessions",
        "paged_attn_roofline", "gdn_state_roofline"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s",
                                                    "setup_s"}


def test_every_ask_goes_in_three_to_nine_chunks():
    """The shortest ask is a document of 4096 and a question of 32: three
    chunks of 2048 at the least; the longest, 16384 + 512, nine."""
    t = harness.resolve(CELL).traffic
    sessions = harness.load_driver("serve_sessions")
    docs, questions, _ = sessions.session_pool(t)
    asks = docs[:, None] + questions
    chunks = -(-asks // 2048)
    assert chunks.min() >= 3 and chunks.max() <= 9


def test_no_prompt_token_is_free_and_the_work_of_the_scopes(config):
    s = fam.sizes(full(config))
    d = harness.load_driver(DRIVER).Driver.__new__(
        harness.load_driver(DRIVER).Driver)
    assert d._hist(object()) == 0
    progs = fam.chunks_of(0, 4130, 2048)
    assert progs == [(0, 2048), (2048, 2048), (4096, 34)]
    kw = fam.kernel_work(s, block=16, ticks=100, slot_ticks=800,
                         contexts=[8000, 9000], prompts=progs)
    step, prefill = kw["gdn_state"]
    assert step["bytes"] == pytest.approx(
        6 * (800 * 2 * work.state_bytes(s) + 100 * 2 * work.gdn_params(s)))
    assert prefill["flops"] > 6 * 4130 * 2 * work.gdn_params(s)
    assert kw["paged_attn"]["calls"] == 200
    # a whole prompt's model work does not depend on how it was cut
    whole = work.prefill_flops(s, 4130)
    parts = sum(work.prefill_flops(s, h + t, h) for h, t in progs)
    head = 2 * s["hidden"] * s["vocab_size"]
    assert parts - 2 * head == pytest.approx(whole, rel=1e-9)


def test_the_new_reader_gives_nothing_where_there_is_nothing_to_read():
    """A program without the scopes (the parent of this PR), or a run
    without a peak: no value, no exception."""
    reader = harness.load_metric("gdn_state_roofline")
    peak = harness.peak_of("TPU v5 lite")
    part = [{"flops": 0.0, "bytes": 819e9 * 0.25}]
    for record, p in (({}, peak), ({"kernel_work": {}, "scope_s": {}}, peak),
                      ({"kernel_work": {"gdn_state": part},
                        "scope_s": {"kda": 1.0}}, peak),
                      ({"kernel_work": {"gdn_state": part},
                        "scope_s": {"gdn": 1.0}}, None)):
        assert reader.read({"record": record, "peak": p}) is None
    got = reader.read({"peak": peak, "record": {
        "kernel_work": {"gdn_state": part},
        "scope_s": {"gdn": 0.25, "gdn.state": 0.25}}})
    assert got == pytest.approx(50.0)


# ------------------------------------------------------------- the rehearsal
@pytest.fixture(scope="module")
def traced():
    from mxnet_tpu.telemetry import tracing

    os.environ["MXTPU_SPAN_RING"] = "262144"
    # spans of an earlier traced run in this process would read as this
    # window's
    tracing.clear_spans()
    return run_cell("--workload", CELL, "--seed", "3800000041", "--seconds",
                    "3", "--trace", "1", "--rehearse")


def test_the_rehearsal_runs_and_is_correct(traced):
    code, last, err = traced
    assert code == 0, err[-2000:]
    assert last["correct"] is True and last["failed"] == 0, err[-1500:]
    assert last["compared"]["check_sample_short"]["value"] == 0.0
    # float32 serving: the chunk edges agree with the reference's rows
    assert last["compared"]["chunk_edge_gap"]["value"] < 1e-4
    assert "of them expected from shared pages 0" in err
    # read on the serving engine, the keepers' ticks between the chunks
    m = re.search(r"(\d+) chunk edges read on the engine, (\d+) ticks, "
                  r"(\d+) of (\d+) keepers decoding to the end", err)
    assert m, err[-1500:]
    edges, ticks, decoding, keepers = map(int, m.groups())
    assert edges >= 2 and ticks >= edges and decoding == keepers == 2


def test_the_traced_rehearsal_carries_state_between_chunks(traced):
    """Every ask of 130-270 tokens goes in 3-5 chunks of 64; all but the
    first of each began from a carried state."""
    _, last, err = traced
    line = next(ln for ln in err.splitlines()
                if ln.startswith("counters over the window:"))
    counts = eval(line.split(":", 1)[1])  # noqa: S307 -- our own dict
    assert counts["prefix_tokens_hit"] == 0
    assert counts["prefill_chunks"] >= 3 * counts["admitted"] > 0
    assert counts["state_chunks_carried"] >= 2 * counts["admitted"]
    # the scopes' work takes the window's own prefill programs
    m = re.search(r"prefill programs of the window's spans: (\d+) chunks, "
                  r"0 whole prompts", err)
    assert m and abs(int(m.group(1)) - counts["prefill_chunks"]) <= 2, \
        err[-1500:]
    m = last["metrics"]
    assert m["prefill_chunk_ms.serve"]["value"] > 0
    # no peak in a rehearsal: no share of a roofline or of the peak
    assert "gdn_state_roofline" not in m and "mfu.serve" not in m


@pytest.mark.parametrize("fault", ["state_not_carried",
                                   "conv_tail_not_carried",
                                   "free_rows_stepped",
                                   "no_negative_eigenvalues"])
def test_a_fault_in_the_carried_state_is_not_correct(fault, monkeypatch):
    """The faults the limits are set against on the chip, planted at the
    rehearsal's size: a later chunk's state, or its convolution's tail,
    from zero, the step writing the state row of a slot between two
    chunks, and ``beta`` without its factor 2.  Each fault reads over
    twice a limit (the widest gap and the mean follow which requests the
    seed's sample holds, and a loaded machine answers others)."""
    driver = harness.load_driver(DRIVER)
    obj, attr, planted = driver.faults()[fault]
    monkeypatch.setattr(obj, attr, planted)
    code, last, err = run_cell("--workload", CELL, "--seed", "3800000023",
                               "--seconds", "2", "--trace", "0",
                               "--rehearse")
    assert code == 0, err[-2000:]
    assert last["correct"] is False, err[-1500:]
    rows = [last["compared"][k] for k in ("served_logit_gap",
                                          "served_gap_mean",
                                          "chunk_edge_gap")]
    assert max(r["value"] / r["limit"] for r in rows) > 2, rows
    # the first position of a chunk reads the carried state whole
    edge = last["compared"]["chunk_edge_gap"]
    assert edge["value"] > 10 * edge["limit"]


def test_the_limits_readings_come_from_one_call_a_seed():
    """The program's reading and the float8 control's from one window,
    over the same sample; the control fails a limit the program
    passes."""
    driver = harness.load_driver(DRIVER)
    cell = harness.resolve(CELL, rehearse=True)
    rows = list(driver.calibrate(cell, 3800000029, 2.0, others=False,
                                 rehearse=True))
    assert [r["kind"] for r in rows] == ["program", "control_fp8"]
    program, control = rows
    assert program["failed"] == 0 and program["short"] == 0
    assert program["asks"] == control["asks"]
    over = lambda r: [r[k] > cell.limits[k] for k in (
        "served_logit_gap", "served_gap_mean", "chunk_edge_gap")]
    assert over(program) == [False, False, False]
    assert any(over(control))
    assert len(program["edges_by_request"]) == len(program["asks"])


class _Asked(list):
    def __init__(self, n, ask):
        super().__init__(range(n))
        self.ask = ask


def test_a_sample_without_requests_in_chunks_is_short():
    driver = harness.load_driver(DRIVER)
    d = driver.Driver.__new__(driver.Driver)
    d.seed, d.traffic = 3800000031, harness.resolve(CELL).traffic
    mk = lambda n, ask, i: {"prompt": _Asked(n, ask), "tokens": [1] * 9,
                            "ok": True, "sent": float(i)}
    d._done = [mk(4200, 0, 0), mk(4300, 0, 1), mk(4250, 1, 2),
               mk(4350, 2, 3)]
    assert d._short(d.sample()) == 0
    d._sample = None
    d._done = [mk(3000, 0, 0), mk(4300, 0, 1), mk(1000, 1, 2),
               mk(4350, 2, 3)]
    assert d._short(d.sample()) == 0
    d._sample = None
    d._done = [mk(3000, 0, 0), mk(2000, 0, 1), mk(1000, 1, 2),
               mk(4350, 2, 3)]
    assert d._short(d.sample()) == 1
