"""The Ling-3.0-flash cell's own pieces: the configuration against the
catalog's numbers, the work functions, and -- with the timed path broken
underneath -- that ``correct`` comes out false."""
import json
import os

import pytest

from bench_util import ROOT, cells, run_cell

from benchmark import harness, work_ling
from benchmark.families import ling as fam

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "ling-3.0-flash-ep4-l7.json")


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


def test_published_widths_are_kept(config):
    want = {"hidden_size": 2560, "num_attention_heads": 32, "head_dim": 128,
            "kv_lora_rank": 512, "qk_rope_head_dim": 64,
            "qk_nope_head_dim": 128, "v_head_dim": 128,
            "moe_intermediate_size": 768, "intermediate_size": 6144,
            "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
            "routed_scaling_factor": 2.5, "num_hidden_layers": 42,
            "layer_group_size": 6, "rope_theta": 6000000,
            "moe_shared_expert_intermediate_size": 768}
    assert {k: config[k] for k in want} == want
    assert sorted(config["reduced"]) == sorted(
        ["num_layers", "first_k_dense_replace", "num_experts", "vocab_size"])
    assert config["published"]["num_experts"] == 512
    assert config["published"]["vocab_size"] == 4 * config["vocab_size"]
    for key in ("deployment", "assumed", "serving", "source"):
        assert config[key]


def test_the_seven_layers_are_one_dense_layer_and_one_period(config):
    s = fam.sizes({k: v for k, v in config.items() if k != "rehearse"})
    assert s["mixers"] == ("kda", "kda", "kda", "kda", "mla", "kda", "kda")
    assert s["mlps"] == ("dense",) + ("moe",) * 6
    assert s["experts"] == 512 and s["experts_held"] == 128


def test_parameter_counts_are_the_issues(config):
    s = fam.sizes(config)
    assert work_ling.kda_params(s) == pytest.approx(52.6e6, rel=2e-3)
    assert work_ling.mla_params(s) == pytest.approx(31.9e6, rel=2e-3)
    assert work_ling.expert_params(s) == pytest.approx(5.898e6, rel=1e-3)
    assert work_ling.active_params(s) == pytest.approx(0.61e9, rel=1e-2)
    specs = fam.param_specs(config)
    total = sum(int(__import__("numpy").prod(v["shape"]))
                for v in specs.values())
    assert 2 * total == pytest.approx(10.34e9, rel=5e-3)    # bf16 bytes
    experts = sum(int(__import__("numpy").prod(v["shape"]))
                  for k, v in specs.items() if "_experts_" in k)
    assert experts == pytest.approx(4.53e9, rel=2e-3)


def test_work_of_the_scopes(config):
    s = fam.sizes(config)
    peak = harness.peak_of("TPU v5 lite")
    kw = work_ling.kernel_work(
        s, block=16, ticks=100, slot_ticks=12800, contexts=[600] * 50,
        prompts=[512] * 200, pairs_held=100 * 6 * 256 + 200 * 6 * 1024,
        distinct_hits=100 * 6 * 111 + 200 * 6 * 128)
    assert set(kw) == {"moe_experts", "kda_state", "mla_attn"}
    # a tick's experts: ~111 of 128 hit in 6 layers, 11.8 MB each
    step_bytes = 6 * 111 * 3 * 2560 * 768 * 2
    assert kw["moe_experts"][0]["bytes"] > 100 * step_bytes
    # state: 6 layers x 128 slots x (read + write) of 2.1 MB, 4.1 ms a tick,
    # and the scope's weights once (0.64 ms)
    state = work_ling.least_seconds(kw["kda_state"][:1], peak) / 100
    assert 4.1e-3 < state < 5.0e-3
    assert all(p["flops"] > 0 and p["bytes"] > 0
               for parts in kw.values() for p in parts)
    # a decode step is bound by bytes, a prefill of 512 by operations
    step, prefill = kw["kda_state"]
    assert step["bytes"] / peak["hbm_bytes_per_s"] \
        > step["flops"] / peak["bf16_flops_per_s"]
    assert prefill["flops"] / peak["bf16_flops_per_s"] \
        > prefill["bytes"] / peak["hbm_bytes_per_s"]


def test_decode_and_prefill_flops(config):
    s = fam.sizes(config)
    assert work_ling.decode_flops(s, 1000) > work_ling.decode_flops(s, 10)
    one = work_ling.decode_flops(s, 512)
    assert one == pytest.approx(2 * 0.61e9, rel=0.05)
    assert fam.model_flops(config, [(512, 64)]) == pytest.approx(
        work_ling.prefill_flops(s, 512)
        + sum(work_ling.decode_flops(s, 512 + j) for j in range(1, 64)))


def test_the_cell_names_the_family_driver():
    assert cells("serve_closed_family") == ["serve_batch_ling"]
    cell = harness.resolve("serve_batch_ling")
    assert cell.traffic["clients"] == cell.traffic["server"]["num_slots"] \
        == cell.traffic["server"]["queue_size"] == 128
    assert cell.traffic["server"]["max_len"] == 2304
    # a closed loop with every slot busy is judged on the tokens it
    # completes; the tails are not reported (PERF.md, PR 27), nor the
    # per-layer metrics that move them
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    named = {m["name"] for m in cell.per_layer}
    assert named == {"moe_experts_roofline", "kda_state_roofline",
                     "mla_attn_roofline", "mfu.serve", "device_idle.serve",
                     "slot_occupancy.serve", "engine_host_ms.serve"}


def test_the_window_opens_with_every_slot_busy():
    """Set-up returns only when the clients' second requests are all
    admitted: the admission of one request a slot, with no tick in
    between, is the loop's start and not part of the window."""
    driver_mod = harness.load_driver("serve_closed_family")
    cell = harness.resolve("serve_batch_ling", rehearse=True)
    d = driver_mod.Driver(cell, 21, rehearse=True)
    d.setup()
    try:
        clients = int(cell.traffic["clients"])
        buckets = len(cell.traffic["server"]["prefill_buckets"])
        assert d.sched.stats["admitted"] >= buckets + 2 * clients
        assert "second requests admitted" in d._setup_note
    finally:
        d.window(0.2)
        d.free()


@pytest.mark.parametrize("cell", cells("serve_closed_family"))
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


@pytest.mark.parametrize("cell", cells("serve_closed_family"))
def test_a_state_carried_over_to_the_next_request_is_not_correct(
        cell, monkeypatch):
    """The fault the per-slot state makes possible: a prefill that starts
    from the slot's old state instead of zero."""
    from mxnet_tpu.serving import paged_kv

    monkeypatch.setattr(paged_kv._PrefillView, "state",
                        lambda self, name: self._state[name][self._slot])
    code, last, err = run_cell("--workload", cell, "--seed", "9",
                               "--seconds", "2", "--trace", "0", "--rehearse")
    assert code == 0, err[-2000:]
    assert last["correct"] is False, err[-1500:]


# ------------------------------------------- the control, and the flips
@pytest.fixture(scope="module")
def greedy_requests():
    """Three requests served greedily by the float32 reference itself
    at the rehearsal's sizes: its own gap is nought."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import ling as ref

    cell = harness.resolve("serve_batch_ling", rehearse=True)
    c = ref.sizes_of(cell.config)
    params = fam.reference_params(cell.config, 17, round_to=jnp.bfloat16)
    rng = np.random.default_rng(3)
    requests = []
    for n_prompt in (20, 41, 33):
        toks = rng.integers(0, cell.config["vocab_size"], n_prompt).tolist()
        served = []
        for _ in range(24):
            padded = np.zeros(128, np.int32)
            padded[:len(toks) + len(served)] = toks + served
            lg = ref.logits(params, jnp.asarray(padded), c)
            served.append(int(jnp.argmax(lg[len(toks) + len(served) - 1])))
        requests.append((toks, served))
    return cell, requests


def test_serving_control_in_float8_fails_the_limit(greedy_requests):
    cell, requests = greedy_requests
    exact, _ = fam.served_gap(cell.config, 17, requests, length=128)
    assert exact == 0.0
    control, detail = fam.served_gap(cell.config, 17, requests,
                                     compute="fp8", length=128)
    assert control > cell.limits["served_logit_gap"], detail
    vocab = cell.config["vocab_size"]
    altered = [(p, t[:5] + [(t[5] + 1) % vocab] + t[6:])
               for p, t in requests]
    worst, _ = fam.served_gap(cell.config, 17, altered, length=128)
    assert worst > cell.limits["served_logit_gap"]


def test_flipped_choices_are_counted_over_the_first_requests(
        greedy_requests):
    """``flips_over`` = 2: the bfloat16-operand pass runs over the first
    two requests alone, and every (real token, MoE layer) pair of those
    two is counted once."""
    cell, requests = greedy_requests
    out = fam.served(cell.config, 17, requests, length=128, flips_over=2)
    assert len(out["gaps"]) == 3 and max(out["gaps"]) == 0.0
    moe_layers = sum(m == "moe" for m in fam.sizes(cell.config)["mlps"])
    assert out["flip_pairs"] == moe_layers * sum(
        len(p) + len(t) for p, t in requests[:2])
    assert 0.0 <= out["flips_held"] <= out["flips"] < 0.5
    assert "flips" not in fam.served(cell.config, 17, requests[:1],
                                     length=128)


def test_two_choices_differ_as_sets_and_by_the_experts_held():
    import numpy as np

    from benchmark.reference import ling as ref

    c = ref.sizes_of(harness.resolve("serve_batch_ling",
                                     rehearse=True).config)
    assert (c.expert_offset, c.experts_held) == (0, 4)
    a = np.array([[1, 9, 5, 7], [0, 1, 2, 3], [8, 9, 10, 11], [4, 5, 6, 7]])
    b = np.array([[9, 1, 7, 5],      # the same set in another order
                  [0, 1, 2, 12],     # held expert 3 left for 12
                  [8, 9, 10, 15],    # 11 -> 15, both held elsewhere
                  [0, 0, 0, 0]])     # beyond the real rows
    assert fam._choices_differ(a, b, 3, c) == (2, 1)
