"""The SDAR cell's own pieces: the configuration against the catalog's
row, the work functions against hand counts, the cell rehearsed on the
CPU, the planted faults and the float8 control against its limits, and
the three new readers."""
import json
import os

import numpy as np
import pytest

from bench_util import ROOT, cells, run_cell

from benchmark import harness, work_ling, work_sdar
from benchmark.families import sdar as fam

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "sdar-30b-a3b-chat-l7.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "serve_block_sdar"


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


def test_published_widths_are_kept(config):
    want = {"hidden_size": 2048, "num_attention_heads": 32,
            "num_key_value_heads": 4, "head_dim": 128,
            "moe_intermediate_size": 768, "intermediate_size": 6144,
            "num_experts": 128, "num_experts_per_tok": 8,
            "vocab_size": 151936, "rope_theta": 1000000,
            "rms_norm_eps": 1e-06, "norm_topk_prob": True,
            "tie_word_embeddings": False, "num_hidden_layers": 48,
            "num_layers": 7}
    assert {k: config[k] for k in want} == want
    # the depth run stands under a key of its own, as in the Ling file:
    # every published key keeps its published value
    assert config["reduced"] == ["num_layers"]
    assert config["published"]["num_hidden_layers"] == 48
    assert config["generation"] == {
        "block_length": 4, "denoising_steps": 4, "mask_token_id": 151669,
        "remasking": "low_confidence_static"}
    for key in ("deployment", "assumed", "serving", "source"):
        assert config[key]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert config["source"] == row["source_url"]
        assert all(config[k] == v for k, v in row["config"].items())


def test_parameter_counts_are_the_issues(config):
    count = lambda keep: sum(
        int(np.prod(v["shape"])) for k, v in fam.param_specs(config).items()
        if keep(k))
    layer = count(lambda k: k.startswith("layer0_"))
    assert layer == 18874368 + 4352 + 262144 + 603979776       # 623.1 M
    assert count(lambda k: "_experts_" in k) == 7 * 603979776
    ends = count(lambda k: not k.startswith("layer"))
    assert ends == 2 * 151936 * 2048 + 2048                     # 622.3 M
    total = count(lambda k: True)
    assert total == 7 * layer + ends
    assert total == pytest.approx(4.984e9, rel=1e-3)
    assert 2 * total == pytest.approx(9.97e9, rel=1e-3)        # bf16 bytes
    assert 48 * layer + ends == pytest.approx(30.5e9, rel=2e-3)
    # K/V: 2 KB a token and layer; 64 slots of 2432 positions
    s = fam.sizes(config)
    token = 2 * s["kv_heads"] * s["head_dim"] * 2 * s["n_layer"]
    assert token == 14336 and 64 * 2432 * token == pytest.approx(2.23e9,
                                                                 rel=2e-3)
    assert s["vocab_size"] == 151669 and s["vocab_size_full"] == 151936


@pytest.mark.parametrize("steps,masked,want", [
    (4, 4, [1, 1, 1, 1]), (2, 4, [2, 2]), (3, 4, [2, 1, 1]), (1, 4, [4]),
    (4, 1, [1]), (2, 3, [2, 1]), (3, 2, [2])])
def test_the_schedule_of_a_block(steps, masked, want):
    assert work_sdar.unmask_counts(4, steps, masked) == want


def test_work_against_hand_counts(config):
    s = fam.sizes(config)
    assert work_sdar.attn_params(s) == 18874368
    body = 7 * (18874368 + 128 * 2048 + 8 * 3 * 2048 * 768)
    assert work_sdar.body_params(s) == body                    # 398.2 M
    # 9 prompt tokens, 7 asked for, 2 steps: the remainder 1 rides in
    # the first block (3 masked: 2 + 1, then its commit), the second
    # block has 4 masked (2 + 2) and, being the last, no commit
    forwards = work_sdar.request_forwards(s, 9, 7, 2)
    assert forwards == [(12, 3), (12, 1), (12, None), (16, 4), (16, 2)]
    attn = lambda ctx: 4.0 * 7 * 32 * 128 * ctx
    want = 2.0 * body * 8 + attn(1) * 4 * (4 + 8)      # two blocks prefilled
    want += 4 * (5 * 2.0 * body + 3 * attn(12) + 2 * attn(16))
    want += 2.0 * 2048 * 151936 * (3 + 1 + 4 + 2)       # the head, masked
    assert work_sdar.request_flops(s, 9, 7, 2) == pytest.approx(want)
    assert fam.model_flops(config, [(9, 7, 2), (9, 7, 2)]) \
        == pytest.approx(2 * want)
    # a fused commit is the same count: a block's commit is counted
    # whether or not it has a forward of its own
    assert len(work_sdar.request_forwards(s, 8, 8, 4)) == 4 + 1 + 4
    # attn.pages: 5 slot-forwards, contexts 12 x 3 and 16 x 2 = one page
    parts = work_sdar.block_attn_work(s, 16, [(9, 7, 2)], 10)
    per = 10 / 5 * 7
    assert parts[0]["bytes"] == pytest.approx(per * (
        2 * 4 * 128 * 2 * 16 * 5 + 5 * 2 * 4 * 32 * 128 * 2))
    assert parts[0]["flops"] == pytest.approx(
        per * 4.0 * 32 * 128 * 4 * (3 * 12 + 2 * 16))
    assert work_sdar.block_attn_work(s, 16, [], 10) is None
    # moe.experts as work_ling reckons it
    kw = work_sdar.kernel_work(s, block=16, ticks=1, slot_ticks=64,
                               contexts=[], prompts=[], pairs_held=2048,
                               distinct_hits=7 * 128)
    e = 3 * 2048 * 768
    assert work_ling.expert_params(s) == e
    assert kw == {"moe_experts": [{
        "flops": 2.0 * 2048 * e,
        "bytes": 7 * 128 * e * 2 + 2048 * 2 * 2048 * 2}]}
    peak = harness.peak_of("TPU v5 lite")
    # a forward's experts: 8.45 GB of weights, 10.3 ms at 819 GB/s
    assert work_ling.least_seconds(kw["moe_experts"], peak) \
        == pytest.approx(10.3e-3, rel=0.02)


def test_the_cell_names_the_block_driver():
    assert cells("serve_block") == [CELL]
    cell = harness.resolve(CELL)
    t = cell.traffic
    assert cell.chips == 1 and cell.config["family"] == "sdar"
    assert t["clients"] == t["server"]["num_slots"] \
        == t["server"]["queue_size"] == 64
    assert t["server"] == {"num_slots": 64, "queue_size": 64, "kv_block": 16,
                           "max_len": 2432,
                           "prefill_buckets": [256, 512, 1024, 2048]}
    assert t["prompt_len"] == {"median": 512, "sigma": 0.6, "min": 128,
                               "max": 2048}
    assert t["output_len"] == {"median": 128, "sigma": 0.6, "min": 32,
                               "max": 384}
    assert (t["lengths_pool"], t["denoising_steps"], t["check_requests"],
            t["trace_seconds"], t["deadline_ms"]) == (1024, [4, 2], 4, 10,
                                                      60000)
    assert t["first_output_len"] == {"min": 4, "max": 16}
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "block_attn_roofline", "moe_experts_roofline", "mfu.serve",
        "device_idle.serve", "slot_occupancy.serve", "engine_host_ms.serve",
        "tokens_per_forward.serve", "commit_share.serve"}
    assert set(cell.limits) == {"served_logit_gap", "served_order_gap"}
    # half the pool's pairs at 4 steps, half at 2 (a pair drawn twice
    # takes its first place's)
    d = harness.load_driver("serve_block").Driver(cell, 1)
    steps = list(d._steps_of.values())
    assert abs(steps.count(4) - steps.count(2)) <= 8 \
        and 1000 <= len(steps) <= 1024


# ------------------------------------------------ the cell, rehearsed
@pytest.fixture(scope="module")
def rehearsed():
    """The cell's driver through set-up, a short window and its check at
    the rehearsal's sizes: (cell, driver module, checks, sample)."""
    mod = harness.load_driver("serve_block")
    cell = harness.resolve(CELL, rehearse=True)
    d = mod.Driver(cell, 8, rehearse=True)
    d.setup()
    record = d.window(1.5)
    d.free()
    return cell, d, record, d.check(), d.sample()


def test_the_cell_rehearses_correct(rehearsed):
    cell, d, record, checks, sample = rehearsed
    assert record["failed"] == 0 and record["requests"] > 3
    assert record["end_to_end"]["serve_tok_s"] > 0
    assert record["model_flops"] > 0
    assert [name for name, _v, _l in checks] == ["served_logit_gap",
                                                 "served_order_gap"]
    assert all(value <= limit for _n, value, limit in checks), checks
    assert all(len(t) == len(u) for _p, t, u in sample)
    assert {k for _p, _t, u in sample for k in u} <= {0, 1, 2, 3}


@pytest.fixture(scope="module")
def own_trajectories():
    """Three requests generated by the float32 reference itself on the
    served (bfloat16-rounded) weights at the rehearsal's sizes: its own
    gaps are nought, and what a fault or the control opens does not
    hang on which requests a window happened to answer."""
    import jax.numpy as jnp

    from benchmark.reference import sdar as ref

    cell = harness.resolve(CELL, rehearse=True)
    c = ref.sizes_of(cell.config)
    params = fam.reference_params(cell.config, 17, round_to=jnp.bfloat16)
    rng = np.random.default_rng(3)
    requests = []
    for p_len, n_new, steps in [(20, 16, 4), (41, 23, 2), (33, 20, 4)]:
        prompt = rng.integers(0, c.mask_id, p_len).tolist()
        tokens, unmask, _f = ref.generate(params, prompt, n_new, c,
                                          steps=steps)
        requests.append((prompt, tokens, unmask))
    return cell, requests


@pytest.mark.parametrize("kind", (None,) + fam.FAULTS + ("control_fp8",))
def test_a_fault_or_the_float8_control_is_over_a_limit(own_trajectories,
                                                       kind):
    """Program-like readings at this size are <= 0.001 (bfloat16
    against float32), the control's 0.012-0.014, a fault's 0.045 and
    more: the rehearsal's limits of 0.004 stand between."""
    cell, requests = own_trajectories
    kw = {} if kind is None else {"compute": "fp8"} \
        if kind == "control_fp8" else {"fault": kind}
    out = fam.served(cell.config, 17, requests, length=128, **kw)
    over = [max(out["logit_gaps"]) > cell.limits["served_logit_gap"],
            max(out["order_gaps"]) > cell.limits["served_order_gap"]]
    if kind is None:
        assert max(out["logit_gaps"] + out["order_gaps"]) == 0.0
    else:
        assert any(over), out


def test_a_commit_left_out_of_the_program_is_not_correct(monkeypatch):
    """The fault in the program itself: a block's commit forward is
    handed what its last denoising forward was handed, so the K/V kept
    are the ones that forward wrote.  Traced, so that the new readers
    run as well."""
    from mxnet_tpu.serving.paged_kv import PagedSlots

    real = PagedSlots.step
    last = {}

    def step(self, tokens, occupied, commit=None):
        tokens = np.array(tokens)
        handed = tokens.copy()
        for b in np.flatnonzero(commit):
            tokens[b] = last[int(b)]
        for b in np.flatnonzero(occupied & ~commit):
            last[int(b)] = handed[b]
        return real(self, tokens, occupied, commit)

    monkeypatch.setattr(PagedSlots, "step", step)
    monkeypatch.setenv("MXTPU_SPAN_RING", "262144")
    code, line, err = run_cell("--workload", CELL, "--seed", "9",
                               "--seconds", "2", "--trace", "1", "--rehearse")
    assert code == 0, err[-2000:]
    assert line["correct"] is False and line["failed"] == 0, err[-1500:]
    assert any(row["value"] > row["limit"]
               for name, row in line["compared"].items()
               if name.startswith("served_"))
    assert 0.7 < line["metrics"]["tokens_per_forward.serve"]["value"] < 1.5
    assert 15 < line["metrics"]["commit_share.serve"]["value"] < 40


# ------------------------------------------------------- the readers
def test_the_new_readers_on_a_made_up_record():
    peak = harness.peak_of("TPU v5 lite")
    record = {"counters": {"slot_ticks": 1000, "tokens_unmasked": 1040,
                           "commit_forwards": 260},
              # 0.4 GB and 2 GFLOP: bound by bytes, 0.488 ms at 819 GB/s
              "kernel_work": {"block_attn": [{"flops": 2e9, "bytes": 4e8}]},
              "scope_s": {"attn.pages": 1.0e-3}}
    ctx = {"record": record, "peak": peak, "trace": None, "chips": 1}
    read = lambda name, c=ctx: harness.load_metric(name).read(c)
    assert read("tokens_per_forward.serve") == pytest.approx(1.04)
    assert read("commit_share.serve") == pytest.approx(26.0)
    assert read("block_attn_roofline") == pytest.approx(
        100 * 4e8 / peak["hbm_bytes_per_s"] / 1.0e-3)
    # a program without the counters or the scope (the parent commit's):
    # nothing to read, nothing raised
    bare = dict(ctx, record={"counters": {"admitted": 3}, "scope_s": {},
                             "kernel_work": {}})
    for name in ("tokens_per_forward.serve", "commit_share.serve",
                 "block_attn_roofline"):
        assert read(name, bare) is None
        assert read(name, dict(ctx, record={})) is None
    assert read("block_attn_roofline", dict(ctx, peak=None)) is None
