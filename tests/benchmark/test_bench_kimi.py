"""The Kimi-K2 cell's own pieces: the configuration against the
catalog's numbers, the session script, the work functions, the cell's
rehearsal with its counters and spans -- and, with the cache path
broken underneath, that ``correct`` comes out false."""
import json
import os

import numpy as np
import pytest

from bench_util import ROOT, cells, run_cell

from benchmark import harness, work_kimi
from benchmark.families import kimi as fam

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "kimi-k2-instruct-ep32-l6.json")
CELL = "serve_docqa_kimi"
# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Kimi-K2-Instruct), every number and flag of it
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "kimi_k2", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 384,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 50000, "routed_scaling_factor": 2.827,
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


def test_every_catalog_key_is_kept_or_listed_as_reduced(config):
    reduced = {"num_layers", "n_routed_experts", "vocab_size"}
    assert set(config["reduced"]) == reduced
    differs = {k for k, v in CATALOG.items() if config.get(k) != v}
    assert differs == reduced - {"num_layers"}
    assert config["n_routed_experts"] == 12 and config["vocab_size"] == 20480
    assert config["num_layers"] == 6 and config["layer_offset"] == 0
    assert config["published"]["n_routed_experts"] == 384
    assert config["published"]["vocab_size"] == 8 * config["vocab_size"]
    assert config["published"]["num_hidden_layers"] == 61
    assert "EP32" in config["deployment"]
    for key in ("deployment", "assumed", "serving", "source", "rehearse"):
        assert config[key]


def test_the_six_layers_are_the_dense_layer_and_five_with_experts(config):
    s = fam.sizes({k: v for k, v in config.items() if k != "rehearse"})
    assert s["mlps"] == ("dense",) + ("moe",) * 5
    assert (s["experts"], s["experts_held"], s["top_k"]) == (384, 12, 8)
    assert (s["n_group"], s["topk_group"]) == (1, 1)


def test_parameter_counts_are_the_issues(config):
    s = fam.sizes(config)
    assert work_kimi.mla_params(s) == pytest.approx(101.1e6, rel=1e-3)
    assert work_kimi.expert_params(s) == pytest.approx(44.04e6, rel=1e-3)
    specs = fam.param_specs(config)
    count = lambda keep: sum(int(np.prod(v["shape"]))
                             for k, v in specs.items() if keep(k))
    assert count(lambda k: True) == pytest.approx(4.173e9, rel=1e-3)
    assert count(lambda k: k.startswith("layer1_")) == pytest.approx(
        676.4e6, rel=1e-3)
    assert count(lambda k: k.startswith("layer0_")) == pytest.approx(
        497.5e6, rel=1e-3)
    assert count(lambda k: not k.startswith("layer")) == pytest.approx(
        293.6e6, rel=1e-3)
    # the latent cache: 576 x 2 B x 6 layers a token
    row = (s["kv_rank"] + s["rope"]) * 2 * s["n_layer"]
    assert row == 6912
    cell = harness.resolve(CELL)
    pages = cell.traffic["server"]["num_pages"]
    assert pages * cell.traffic["server"]["kv_block"] * row \
        == pytest.approx(4.0e9, rel=2e-3)


def test_the_cell_is_the_issues_letter_for_letter():
    assert cells("serve_sessions") == [CELL]
    cell = harness.resolve(CELL)
    t = cell.traffic
    assert cell.chips == 1
    assert t["server"] == {
        "num_slots": 16, "queue_size": 16, "kv_block": 16, "max_len": 17408,
        "prefill_buckets": [128, 256, 512, 1024, 2048], "num_pages": 36160}
    assert (t["clients"], t["asks"], t["sessions_pool"]) == (16, 4, 256)
    assert t["doc_len"] == {"median": 8192, "sigma": 0.4, "min": 4096,
                            "max": 16384}
    assert t["question_len"] == {"median": 128, "sigma": 0.6, "min": 32,
                                 "max": 512}
    assert t["output_len"] == {"median": 64, "sigma": 0.6, "min": 16,
                               "max": 192}
    # the longest request fits the cache window
    assert 16384 + 512 + 192 <= t["server"]["max_len"]
    assert (t["check_requests"], t["check_first_asks"],
            t["check_later_asks"]) == (6, 2, 4)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "slot_occupancy.serve", "mfu.serve", "device_idle.serve",
        "engine_host_ms.serve", "moe_experts_roofline", "mla_attn_roofline",
        "prefix_hit_share.serve", "prefill_chunk_ms.serve",
        "itl_p99_ms.sessions", "queue_wait_ms.sessions"}


# ---------------------------------------------------------------- sessions
def test_a_client_walks_sessions_of_one_document_asked_four_times():
    driver = harness.load_driver("serve_sessions")
    cell = harness.resolve(CELL)
    docs, questions, outputs = driver.session_pool(cell.traffic)
    assert docs.shape == (256,) and questions.shape == outputs.shape \
        == (256, 4)
    assert docs.min() >= 4096 and docs.max() <= 16384
    assert 7000 < np.median(docs) < 9500
    assert questions.min() >= 32 and questions.max() <= 512
    assert outputs.min() >= 16 and outputs.max() <= 192
    # the pool is dealt once into 16 scripts of 16 sessions, every
    # session in one of them, and every round (the r-th sessions of the
    # scripts) holds one document of each sixteenth of the pool by
    # length: at any pace the documents under admission are the pool's
    dealt = driver.scripts(cell.traffic)
    assert dealt.shape == (16, 16)
    assert sorted(dealt.ravel()) == list(range(256))
    rank = np.empty(256, int)
    rank[np.argsort(docs, kind="stable")] = np.arange(256)
    for r in range(16):
        assert sorted(rank[dealt[:, r]] // 16) == list(range(16))
    assert docs[dealt].mean(axis=0) == pytest.approx(docs.mean(), rel=0.03)
    # no script keeps to one size of document
    assert all(len(set(rank[mine] // 16)) > 4 for mine in dealt)
    # a run's seed deals the scripts out to the clients in another
    # order and draws other ids: every seed sends the same lengths, so
    # the seed changes nothing of the work in a window
    deal = lambda seed: [
        [tuple(int(x) for x in (s[0], *s[1], *s[2]))
         for s in driver.SessionScript(cell.traffic, seed, i,
                                       20480)._sessions]
        for i in range(16)]
    a, b = deal(3300000011), deal(3300000013)
    assert all(len(mine) == 16 for mine in a)
    pool = sorted(tuple(int(x) for x in (d, *q, *o))
                  for d, q, o in zip(docs, questions, outputs))
    assert sorted(s for mine in a for s in mine) == pool
    assert a != b and sorted(a) == sorted(b)
    assert sorted(a) == sorted(
        [tuple(int(x) for x in (docs[j], *questions[j], *outputs[j]))
         for j in mine] for mine in dealt)
    ids = lambda seed: driver.SessionScript(
        cell.traffic, seed, 0, 20480).next()[0][:64]
    assert ids(3300000011) != ids(3300000013)
    tiny = harness.resolve(CELL, rehearse=True).traffic
    # every client asks each of its documents ``asks`` times, the first
    # ask first, and nothing holds a request back
    for i in range(3):
        script = driver.SessionScript(tiny, 5, i, 256)
        seen = [script.next() for _ in range(7)]
        assert [p.ask for p, _ in seen] == [0, 1, 2, 0, 1, 2, 0]
        assert [out for _, out in seen[:3]] == [
            int(o) for o in script._sessions[0][2]]
    first, second = seen[0][0], seen[1][0]
    assert first[:first.doc] == second[:second.doc] and first.doc \
        == second.doc
    assert first[first.doc:] != second[second.doc:len(first)]
    assert seen[3][0][:32] != first[:32]          # the next document
    assert max(max(p) for p, _ in seen) < 256


def test_the_generator_is_the_harness_own_but_for_the_script():
    """``serve_closed_family`` is handed the session script under the
    name it asks for, and finds everything else of ``traffic_gen``."""
    from benchmark import traffic_gen

    driver = harness.load_driver("serve_sessions")
    seen = driver._family.traffic_gen
    assert seen.ClientScript is driver.SessionScript
    assert seen.length_pool is traffic_gen.length_pool
    assert seen._lognormal is traffic_gen._lognormal
    with pytest.raises(AttributeError):
        seen.no_such_name
    # another load of the family driver keeps the generator's own script
    assert harness.load_driver("serve_closed_family").traffic_gen \
        is traffic_gen


def test_the_sample_holds_two_first_asks_and_four_later_asks():
    driver = harness.load_driver("serve_sessions")
    cell = harness.resolve(CELL)
    d = driver.Driver(cell, 3300000031)
    rng = np.random.default_rng(1)
    done = []
    for i in range(40):
        p = driver._Prompt([0] * int(rng.integers(4200, 16000)))
        p.ask, p.doc = i % 4, len(p) - 100
        done.append({"prompt": p, "tokens": [1] * 20, "sent": float(i)})
    d.finished = done
    sample = d.sample()
    asks = [p.ask for p, _ in sample]
    assert len(sample) == 6
    assert sum(a == 0 for a in asks) >= 2 and sum(a > 0 for a in asks) >= 4
    longest = max((x for x in done if x["prompt"].ask == 0),
                  key=lambda x: len(x["prompt"]))
    assert sample[0][0] is longest["prompt"]
    again = driver.Driver(cell, 3300000031)
    again.finished = done
    assert [id(p) for p, _ in again.sample()] == [id(p) for p, _ in sample]
    other = driver.Driver(cell, 3300000037)
    other.finished = done
    assert [id(p) for p, _ in other.sample()] != [id(p) for p, _ in sample]


# -------------------------------------------------------------------- work
def test_a_token_from_shared_pages_is_no_work(config):
    s = fam.sizes(config)
    cold = work_kimi.prefill_flops(s, 8192 + 128)
    hit = work_kimi.prefill_flops(s, 8192 + 128, hist=8192)
    assert hit < cold / 20
    # products of 128 tokens, their attention over 8k + 64 on average,
    # the head once
    want = (2 * work_kimi.body_params(s) * 128
            + 2 * 7168 * 20480
            + 6 * 2 * 64 * 320 * 128 * (8192 + 64))
    assert hit == pytest.approx(want, rel=1e-6)
    assert work_kimi.decode_flops(s, 9000) > work_kimi.decode_flops(s, 100)
    # 2.56 GFLOP of products a token through the six layers
    assert 2 * work_kimi.body_params(s) == pytest.approx(2.56e9, rel=0.02)
    assert fam.model_flops(config, [(8320, 3, 8192)]) == pytest.approx(
        hit + work_kimi.decode_flops(s, 8321)
        + work_kimi.decode_flops(s, 8322))


def test_chunks_and_the_work_of_the_scopes(config):
    assert work_kimi.chunks_of(0, 5000, 2048) == [
        (0, 2048), (2048, 2048), (4096, 904)]
    assert work_kimi.chunks_of(8192, 128, 2048) == [(8192, 128)]
    assert work_kimi.chunks_of(0, 2048, 2048) == [(0, 2048)]
    s = fam.sizes(config)
    peak = harness.peak_of("TPU v5 lite")
    programs = work_kimi.chunks_of(0, 8192, 2048) \
        + [(8192, 128)] * 3
    kw = work_kimi.kernel_work(
        s, block=16, ticks=100, slot_ticks=1500, contexts=[9000] * 50,
        prompts=programs, pairs_held=100 * 5 * 4 + 4 * 5 * 512,
        distinct_hits=100 * 5 * 4 + 7 * 5 * 12)
    assert set(kw) == {"moe_experts", "mla_attn"}
    step, prefill = kw["mla_attn"]
    # a tick: the scope's weights once (6 x 202 MB) and 15 slots' live
    # rows (6 x 15 x 9008 x 1152 B): bound by bytes
    assert step["bytes"] / 100 == pytest.approx(
        6 * (101.1e6 * 2 + 15 * 9008 * 1152), rel=2e-3)
    assert step["bytes"] / peak["hbm_bytes_per_s"] \
        > step["flops"] / peak["bf16_flops_per_s"]
    # the prefills: bound by operations, attention over history among them
    assert prefill["flops"] / peak["bf16_flops_per_s"] \
        > prefill["bytes"] / peak["hbm_bytes_per_s"]
    alone = work_kimi.kernel_work(
        s, block=16, ticks=100, slot_ticks=1500, contexts=[9000] * 50,
        prompts=[8192, 128, 128, 128], pairs_held=1, distinct_hits=1)
    assert alone["mla_attn"][1]["flops"] < prefill["flops"]
    # an expert hit is read once: 88 MB
    assert kw["moe_experts"][0]["bytes"] > (100 * 5 * 4) * 88e6


# ------------------------------------------------------------- the rehearsal
@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    os.environ["MXTPU_SPAN_RING"] = "262144"
    return run_cell("--workload", CELL, "--seed", "3300000021", "--seconds",
                    "3", "--trace", "1", "--rehearse")


def test_the_rehearsal_runs_and_is_correct(traced):
    code, last, err = traced
    assert code == 0, err[-2000:]
    assert last["correct"] is True and last["failed"] == 0, err[-1500:]
    for number in ("served_logit_gap", "served_gap_mean"):
        assert last["compared"][number]["value"] \
            <= last["compared"][number]["limit"]
    full = harness.resolve(CELL).limits
    assert (full["served_logit_gap"], full["served_gap_mean"]) == (1.15, 0.03)
    assert "first asks" in err and "later asks" in err


def test_the_traced_rehearsal_reads_the_counters_and_the_chunk_spans(traced):
    _, last, err = traced
    m = last["metrics"]
    # documents of 70-180 tokens behind buckets of 32 and 64: every first
    # ask goes in chunks, every later ask finds its document
    assert 40.0 < m["prefix_hit_share.serve"]["value"] < 90.0
    assert m["prefill_chunk_ms.serve"]["value"] > 0
    assert m["itl_p99_ms.sessions"]["value"] > 0
    assert m["queue_wait_ms.sessions"]["value"] >= 0
    assert "slot_occupancy.serve" in m and "engine_host_ms.serve" in m
    assert "'prefill_chunks':" in err and "'prefix_tokens_hit':" in err


def test_the_new_readers_give_nothing_where_there_is_nothing_to_read():
    """A program without the counters, the span or the ring (the parent
    of the PR that added them): no value, no exception."""
    hit = harness.load_metric("prefix_hit_share.serve")
    cell = harness.resolve(CELL, rehearse=True)
    for record in ({}, {"counters": {}},
                   {"counters": {"expert_distinct_hits": 4}},
                   {"counters": {"prompt_tokens": 0, "prefix_tokens_hit": 0}},
                   {"counters": {"prompt_tokens": 7}}):
        assert hit.read({"record": record}) is None
    assert hit.read({"record": {"counters": {
        "prompt_tokens": 200, "prefix_tokens_hit": 150}}}) == 75.0
    assert hit.read({"record": {"counters": {
        "prompt_tokens": 200, "prefix_tokens_hit": 0}}}) == 0.0
    from mxnet_tpu.telemetry import tracing
    tracing.clear_spans()
    for name in ("prefill_chunk_ms.serve", "itl_p99_ms.sessions",
                 "queue_wait_ms.sessions"):
        assert harness.load_metric(name).read(
            {"record": {}, "cell": cell}) is None


def test_the_gap_readers_take_the_request_records_of_the_window(monkeypatch):
    from benchmark import span_reduce

    gaps = [60.0] * 95 + [310.0] * 3 + [2000.0, 2100.0]
    records = [{"name": "request", "prof": True, "queue_wait_ms": w,
                "gaps_ms": gaps[i::4]} for i, w in enumerate([5.0, 40.0,
                                                              900.0, 12.0])]
    records.append({"name": "request", "prof": True, "queue_wait_ms": None,
                    "gaps_ms": None})
    monkeypatch.setattr(span_reduce, "ring", lambda who: records)
    assert harness.load_metric("itl_p99_ms.sessions").read({}) == 2000.0
    assert harness.load_metric("queue_wait_ms.sessions").read({}) == 12.0
    monkeypatch.setattr(span_reduce, "ring", lambda who: [
        {"name": "engine.tick", "prof": True, "dur_s": 0.06}])
    assert harness.load_metric("itl_p99_ms.sessions").read({}) is None
    assert harness.load_metric("queue_wait_ms.sessions").read({}) is None


@pytest.mark.parametrize("fault", ["history_left_out",
                                   "hist_one_page_short",
                                   "mscale_left_out"])
def test_a_fault_in_the_cache_path_is_not_correct(fault, monkeypatch):
    """The three faults the limit is set against on the chip, planted
    at the rehearsal's size (float32, weights N(0, 0.2) so that a token
    depends on what stands before it): a prefill that leaves the
    history out, a chunk told a history one page short, the YaRN scale
    left out of the scores.  The program reads 0.0 there, each fault
    3.5-5.5 against a limit of 0.004."""
    driver = harness.load_driver("serve_sessions")
    obj, attr, planted = driver.faults()[fault]
    monkeypatch.setattr(obj, attr, planted)
    code, last, err = run_cell("--workload", CELL, "--seed", "3300000023",
                               "--seconds", "2", "--trace", "0",
                               "--rehearse")
    assert code == 0, err[-2000:]
    assert last["correct"] is False, err[-1500:]
    row = last["compared"]["served_logit_gap"]
    assert row["value"] > 100 * row["limit"]
    # the mean is compared too; a fault that moves one request in six
    # need not pass its limit, the widest gap decides there
    assert last["compared"]["served_gap_mean"]["value"] > 0


def test_serving_control_in_float8_fails_the_limit():
    """Requests served greedily by the float32 reference itself read a
    gap of nought; the same tokens judged from the float8 computation's
    first choices lie over the rehearsal's limit."""
    import jax.numpy as jnp

    from benchmark.reference import kimi as ref

    cell = harness.resolve(CELL, rehearse=True)
    c = ref.sizes_of(cell.config)
    params = fam.reference_params(cell.config, 17, round_to=jnp.dtype(
        cell.config["serving"]["weights_dtype"]))
    rng = np.random.default_rng(3)
    requests = []
    for n_prompt in (70, 41, 33, 90):
        toks = rng.integers(0, cell.config["vocab_size"], n_prompt).tolist()
        served = []
        for _ in range(24):
            padded = np.zeros(128, np.int32)
            padded[:len(toks) + len(served)] = toks + served
            lg = ref.logits(params, jnp.asarray(padded), c)
            served.append(int(jnp.argmax(lg[len(toks) + len(served) - 1])))
        requests.append((toks, served))
    out = fam.served(cell.config, 17, requests, compute="fp8", pad_to=64)
    assert out["served"]["gaps"] == [0.0] * 4 and out["served"]["mean"] == 0.0
    control = out["control"]
    assert max(control["gaps"]) > cell.limits["served_logit_gap"], control
    assert control["mean"] > cell.limits["served_gap_mean"], control
    # the float32 pass reads the same with or without the control after it
    assert fam.served(cell.config, 17, requests, pad_to=64) == {
        "served": out["served"]}
    # the mean is over all the served tokens: at most the widest gap,
    # at least the widest gap's share of one token in 96
    assert max(control["gaps"]) / 96 <= control["mean"] <= max(control["gaps"])


def test_the_limits_readings_come_from_one_call_a_seed():
    """``calibrate`` at the rehearsal's size: the program's row and the
    float8 control's from the same sample, each with the widest gap a
    run compares, the requests' gaps and the mean over the tokens."""
    driver = harness.load_driver("serve_sessions")
    cell = harness.resolve(CELL, rehearse=True)
    rows = list(driver.calibrate(cell, 3300000029, 2.0, others=False,
                                 rehearse=True))
    assert [r["kind"] for r in rows] == ["program", "control_fp8"]
    program, control = rows
    assert program["failed"] == 0 and program["requests"] > 3
    assert program["asks"] == control["asks"]
    assert 0 in program["asks"] and max(program["asks"]) > 0
    assert program["served_logit_gap"] <= cell.limits["served_logit_gap"]
    assert control["served_logit_gap"] > cell.limits["served_logit_gap"]
    for r in rows:
        assert r["served_logit_gap"] == pytest.approx(max(r["by_request"]),
                                                      abs=1e-5)
        assert 0 <= r["mean_over_tokens"] <= r["served_logit_gap"]
