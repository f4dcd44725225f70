"""The control of "How correct is decided", at a size a test run can
hold: the reference put in the program's place and computed in float8
must come out as not correct, and the float32 reference against itself
must come out exact."""
import pytest

from bench_util import ROOT, add_resnet_cell, cells, copy_of_the_benchmark


@pytest.mark.parametrize("cell_name", cells("train") + ["resnet50_train"])
def test_training_control_in_float8_fails_a_limit(cell_name, tmp_path):
    from benchmark import check_train, harness

    root = ROOT
    if cell_name == "resnet50_train":       # files without a cell, today
        root = copy_of_the_benchmark(tmp_path)
        add_resnet_cell(root)
    cell = harness.resolve(cell_name, rehearse=True, root=root)
    fam = harness.load_family(cell.config["family"])
    recipe = cell.config["training"]
    batches = fam.host_batches(cell.config, cell.traffic, 31, 3)
    args = (fam, cell.config, recipe, 31, batches)
    ref = check_train.reference_readings(*args)
    same, _ = check_train.compare(ref, ref, cell.limits)
    assert all(v == 0.0 for _n, v, _l in same)
    control = check_train.reference_readings(*args, compute="fp8")
    rows, _ = check_train.compare(control, ref, cell.limits)
    assert any(v > limit for _n, v, limit in rows), rows


@pytest.mark.parametrize("cell_name", cells("serve_closed"))
def test_serving_control_in_float8_fails_the_limit(cell_name):
    import numpy as np

    from benchmark import harness

    cell = harness.resolve(cell_name, rehearse=True)
    fam = harness.load_family(cell.config["family"])
    c = fam.sizes(cell.config)
    rng = np.random.default_rng(3)
    import jax
    import jax.numpy as jnp

    from benchmark.reference import gpt2 as ref

    params = fam.reference_params(cell.config, 17, round_to=jnp.bfloat16)
    best = jax.jit(lambda toks, at: jnp.argmax(
        ref.logits(params, toks[None], c["n_head"])[0, at]))
    requests = []
    for n_prompt in (20, 41, 33):
        toks = rng.integers(0, c["vocab_size"], n_prompt).tolist()
        served = []
        for _ in range(40):      # greedy under the float32 reference
            padded = np.zeros(128, np.int32)
            padded[:len(toks) + len(served)] = toks + served
            served.append(int(best(jnp.asarray(padded),
                                   len(toks) + len(served) - 1)))
        requests.append((toks, served))
    exact, _ = fam.served_gap(cell.config, 17, requests, length=128)
    assert exact == 0.0
    control, detail = fam.served_gap(cell.config, 17, requests,
                                     compute="fp8", length=128)
    assert control > cell.limits["served_logit_gap"], detail
    altered = [(p, t[:5] + [(t[5] + 1) % c["vocab_size"]] + t[6:])
               for p, t in requests]
    worst, _ = fam.served_gap(cell.config, 17, altered, length=128)
    assert worst > cell.limits["served_logit_gap"]
