#!/usr/bin/env python
"""chip_smoke.py — quickest proof that training and serving start on the chip.

One process, one chip, the entry points a user calls, at the full width
of models the repo supports (steps and requests are cut, never widths):

- ResNet-50 (1000 classes, 224x224, batch 32, bf16 with f32 masters)
  through ``FusedTrainer.step``, then the same symbol through
  ``mx.mod.Module(..., context=mx.tpu(0)).fit``;
- the compute-bound transformer LM ("lm-560m": L8 H16 D2048 ff8192 T1024
  V32768 B8, bf16, Adam) through ``FusedTrainer.step`` — the flash kernel
  must be in the compiled step, and agree with lax attention (output and
  gradients) on a small input;
- a ``KVDecoder`` at the same width behind ``serving.serve_decoder`` with
  paged KV (16-token pages, 8 slots), answering concurrent
  ``POST /generate`` over HTTP — the Pallas paged kernel must be in the
  step program, and its first-step logits must agree with the gather
  reference.

Every phase asserts that its parameters, optimizer state and KV pool sit
on a ``tpu`` device.  A phase that fails raises, and the run exits
non-zero: nothing here catches an error to carry on.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # only the sharded paths and what
                                     # they are compared with
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny widths on
        # the CPU, Pallas interpreted: finds wrong paths, proves nothing
        # about the chip (add XLA_FLAGS=--xla_force_host_platform_device_
        # count=4 for --chips 4)

One JSON object per phase goes to stdout; the last line is the verdict,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without an accelerator (and without ``--rehearse``) it prints no verdict
and exits 2.  Timings are smoke timings of a handful of steps, not
benchmark results.
"""
import argparse
import gc
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np

SEED = 0

# the repo's own widths: models.get_symbol("resnet-50") as bench.py trains
# it, and models.transformer.MFU_HEADLINE_CONFIG (tools/probe_lm_mfu.py
# "lm-560m") for both the trainer and the decoder
FULL = {
    "resnet": dict(batch=32, image=224, classes=1000, steps=6,
                   fit_batches=3, fit_epochs=2),
    "lm": dict(name="lm-560m", L=8, H=16, D=2048, ff=8192, T=1024, V=32768,
               B=8, steps=4),
    "serve": dict(max_len=1024, block=16, slots=8, max_tokens=8,
                  prompt_lens=(40, 200, 64, 130, 50, 255)),
}
# --rehearse: the same code paths at a size the CPU finishes in a minute
# or two (dh stays 128 so the interpreted paged kernel passes its gate)
TINY = {
    "resnet": dict(batch=4, image=32, classes=10, steps=4,
                   fit_batches=2, fit_epochs=2),
    "lm": dict(name="lm-tiny", L=2, H=4, D=512, ff=1024, T=128, V=512, B=4,
               steps=3),
    "serve": dict(max_len=128, block=16, slots=4, max_tokens=4,
                  prompt_lens=(5, 40, 12, 33)),
}
# first-step logits, Pallas kernel against gather, both bf16: the kernel
# accumulates and normalizes in f32, gather in bf16, and the difference
# rides through every layer's bf16 activations — a few bf16 ulps of the
# largest logit.  A wrong page or mask is O(1), far outside this.
LOGIT_TOL = 0.05
# flash kernels against lax attention on a small bf16 input, output and
# gradients, relative to the largest reference value
FLASH_TOL = 0.05


def emit(obj):
    print(json.dumps(obj), flush=True)


def _memory_stat(dev, key):
    return (dev.memory_stats() or {}).get(key)      # None on the CPU


def _n_files(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _arrays(tree):
    """Every jax array reachable through dicts/sequences/NDArrays."""
    import jax

    out = []

    def walk(x):
        if isinstance(x, jax.Array):
            out.append(x)
        elif hasattr(x, "_read"):
            out.append(x._read())
        elif isinstance(x, dict):
            for v in x.values():
                walk(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)

    walk(tree)
    return out


def assert_placed(what, tree, platform, n_devices=1):
    """Every array of ``tree`` lives on ``platform`` devices only, and the
    tree as a whole touches exactly ``n_devices`` distinct devices."""
    arrs = _arrays(tree)
    if not arrs:
        raise AssertionError(f"{what}: nothing to check placement of")
    seen = set()
    for a in arrs:
        for d in a.devices():
            if d.platform != platform:
                raise AssertionError(
                    f"{what}: array {a.shape} sits on {d} "
                    f"(platform {d.platform!r}, wanted {platform!r})")
            seen.add(d)
    if len(seen) != n_devices:
        raise AssertionError(
            f"{what}: spread over {len(seen)} device(s) "
            f"{sorted(map(str, seen))}, wanted {n_devices}")
    return len(arrs)


def assert_sharded(what, tree, n_slices):
    """Some array of ``tree`` is split — not merely replicated — into
    ``n_slices`` distinct slices held by different devices (read from
    ``addressable_shards``)."""
    best = 0
    for a in _arrays(tree):
        slices = {tuple((i.start, i.stop) for i in s.index)
                  for s in a.addressable_shards}
        best = max(best, len(slices))
    if best != n_slices:
        raise AssertionError(
            f"{what}: no array is split {n_slices} ways (widest: {best})")
    return best


def _ce_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ce(probs, labels):
        p = probs.astype(jnp.float32).reshape(-1, probs.shape[-1])
        idx = labels.astype(jnp.int32).reshape(-1, 1)
        picked = jnp.take_along_axis(p, idx, axis=1)[:, 0]
        return -jnp.mean(jnp.log(jnp.maximum(picked, 1e-30)))

    return ce


def _train_steps(tr, batches, order, ce):
    """AOT-compile the step (timed; kernels and collectives counted in
    its text), then run it over ``order``; every loss is pulled to the
    host, which is the barrier."""
    t0 = time.perf_counter()
    text = tr.lower_step(**batches[order[0]]).compile().as_text()
    compile_s = time.perf_counter() - t0
    losses, walls = [], []
    for i in order:
        t0 = time.perf_counter()
        outs = tr.step(**batches[i])
        losses.append(float(ce(outs[0], batches[i]["softmax_label"])))
        walls.append(time.perf_counter() - t0)
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    out = {"compile_s": round(compile_s, 3),
           "first_step_s": round(walls[0], 3),
           "steady_step_s": round(float(np.median(walls[1:])), 4),
           "losses": [round(x, 4) for x in losses],
           "kernels_in_step": text.count("tpu_custom_call")}
    collectives = {op: text.count(op) for op in
                   ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")}
    if any(collectives.values()):
        out["collectives_in_step"] = {k: v for k, v in collectives.items()
                                      if v}
    return out


def _repeat_order(steps):
    """Two batches alternate; the first and the last step see batch 0, so
    the two losses compared are of the same batch."""
    return [i % 2 for i in range(steps - 1)] + [0]


def _trainer_state(tr):
    return {"params": tr.params, "compute_copy": tr._cparams,
            "aux": tr.aux, "opt_state": tr.opt_state}


# --------------------------------------------------------------- ResNet-50
def _resnet_symbol(cfg):
    from mxnet_tpu import models

    return models.get_symbol(
        "resnet-50", num_classes=cfg["classes"],
        image_shape=(3, cfg["image"], cfg["image"]))


def _image_batches(cfg, batch, n, mesh=None):
    import jax

    rs = np.random.RandomState(SEED)
    out = []
    for _ in range(n):
        data = rs.uniform(
            0, 1, (batch, 3, cfg["image"], cfg["image"])).astype(np.float32)
        label = rs.randint(0, cfg["classes"], batch).astype(np.float32)
        if mesh is None:
            data, label = jax.device_put(data), jax.device_put(label)
        out.append({"data": data, "softmax_label": label})
    return out


def _resnet_trainer(cfg, batch, mesh=None):
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.trainer import FusedTrainer

    np.random.seed(SEED)    # the initializer draws from the global RNG
    mx.random.seed(SEED)
    tr = FusedTrainer(
        _resnet_symbol(cfg), optimizer="sgd",
        optimizer_params={"lr": 0.05, "momentum": 0.9,
                          "rescale_grad": 1.0 / batch},
        dtype=jnp.bfloat16, mesh=mesh)
    tr.init(data=(batch, 3, cfg["image"], cfg["image"]))
    return tr


def phase_resnet_fused(cfg, platform):
    b = cfg["batch"]
    tr = _resnet_trainer(cfg, b)
    out = _train_steps(tr, _image_batches(cfg, b, 2),
                       _repeat_order(cfg["steps"]), _ce_fn())
    if not out["losses"][-1] < out["losses"][0]:
        raise AssertionError(f"loss did not fall: {out['losses']}")
    out["arrays_on_device"] = assert_placed(
        "resnet50 FusedTrainer", _trainer_state(tr), platform)
    out.update(model="resnet-50", batch=b, image=cfg["image"],
               dtype="bfloat16", entry="FusedTrainer.step")
    return out


def _module_arrays(mod):
    ex = mod._exec_group.execs[0]
    state = {"args": ex.arg_dict, "aux": ex.aux_dict, "grads": ex.grad_dict}
    updater = mod._updater or getattr(mod._kvstore, "_updater", None)
    if updater is not None:
        state["opt_state"] = list(updater.states.values())
    return state


def _module_fit(cfg, contexts, batch, n_batches, epochs):
    """``Module.fit`` — the source paper's API — over seeded batches;
    returns (module, mean train cross-entropy per epoch, seconds)."""
    import mxnet_tpu as mx

    np.random.seed(SEED)
    mx.random.seed(SEED)
    rs = np.random.RandomState(SEED)
    n = batch * n_batches
    data = rs.uniform(
        0, 1, (n, 3, cfg["image"], cfg["image"])).astype(np.float32)
    label = rs.randint(0, cfg["classes"], n).astype(np.float32)
    it = mx.io.NDArrayIter(data, label, batch_size=batch)
    mod = mx.mod.Module(_resnet_symbol(cfg), context=contexts)
    ce_by_epoch = {}

    def on_batch(param):
        # the running mean of this epoch so far; the last write stands
        ce_by_epoch[param.epoch] = float(param.eval_metric.get()[1])

    t0 = time.perf_counter()
    mod.fit(it, num_epoch=epochs, eval_metric="ce", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            batch_end_callback=on_batch)
    wall = time.perf_counter() - t0
    ces = [ce_by_epoch[e] for e in sorted(ce_by_epoch)]
    if not all(np.isfinite(ces)):
        raise AssertionError(f"Module.fit: non-finite train ce {ces}")
    return mod, ces, wall


def phase_resnet_module(cfg, platform):
    import mxnet_tpu as mx

    ctx = mx.tpu(0)
    # mx.tpu() folds onto CPU devices where no accelerator is found
    # (context.py, the test strategy); on the chip that must not happen
    if ctx.jax_device.platform != platform:
        raise AssertionError(
            f"mx.tpu(0) resolved to {ctx.jax_device} "
            f"({ctx.jax_device.platform!r}), wanted {platform!r}")
    b = cfg["batch"]
    mod, ces, wall = _module_fit(cfg, ctx, b, cfg["fit_batches"],
                                 cfg["fit_epochs"])
    if not ces[-1] < ces[0]:
        raise AssertionError(f"Module.fit: train ce did not fall: {ces}")
    return {"model": "resnet-50", "batch": b, "image": cfg["image"],
            "entry": "Module.fit", "context": str(ctx),
            "batches": cfg["fit_batches"] * cfg["fit_epochs"],
            "fit_s": round(wall, 3),
            "train_ce_by_epoch": [round(x, 4) for x in ces],
            "arrays_on_device": assert_placed(
                "resnet50 Module", _module_arrays(mod), platform)}


# --------------------------------------------------------------------- LM
def _lm_symbol(cfg):
    from mxnet_tpu import models

    return models.transformer.transformer_lm(
        num_layers=cfg["L"], num_heads=cfg["H"], d_model=cfg["D"],
        d_ff=cfg["ff"], seq_len=cfg["T"], vocab_size=cfg["V"])


def _lm_trainer(cfg, mesh=None, rules=()):
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.trainer import FusedTrainer

    np.random.seed(SEED)
    mx.random.seed(SEED)
    tr = FusedTrainer(_lm_symbol(cfg), optimizer="adam",
                      optimizer_params={"lr": 1e-4}, dtype=jnp.bfloat16,
                      mesh=mesh, sharding_rules=rules)
    tr.init(data=(cfg["B"], cfg["T"]), softmax_label=(cfg["B"], cfg["T"]))
    return tr


def _token_batches(cfg, n, mesh=None):
    import jax

    rs = np.random.RandomState(SEED)
    out = []
    for _ in range(n):
        toks = rs.randint(0, cfg["V"], (cfg["B"], cfg["T"])).astype(np.float32)
        labs = rs.randint(0, cfg["V"], (cfg["B"], cfg["T"])).astype(np.float32)
        if mesh is None:
            toks, labs = jax.device_put(toks), jax.device_put(labs)
        out.append({"data": toks, "softmax_label": labs})
    return out


def _flash_against_lax(interpret):
    """The flash kernels (forward and both backward) against the lax
    attention on a small causal bf16 input: output and q/k/v gradients
    within bf16 rounding of the reference.  Returns the worst relative
    error."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.ring_attention import attention

    rs = np.random.RandomState(SEED)
    shape = (2, 4, 256, 128)
    q, k, v, w = (jnp.asarray(rs.normal(size=shape), jnp.bfloat16)
                  for _ in range(4))

    def run(impl):
        def f(q, k, v):
            o = attention(q, k, v, causal=True, impl=impl)
            return jnp.sum((o * w).astype(jnp.float32)), o

        (_, o), grads = jax.jit(jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return [np.asarray(x, np.float32) for x in (o,) + grads]

    worst = 0.0
    for name, got, ref in zip(
            ("out", "dq", "dk", "dv"),
            run("flash_interpret" if interpret else "flash"), run("lax")):
        err = float(np.abs(got - ref).max()) \
            / max(1.0, float(np.abs(ref).max()))
        if not np.isfinite(got).all() or err > FLASH_TOL:
            raise AssertionError(f"flash {name} vs lax: relative error "
                                 f"{err:.4f} > {FLASH_TOL}")
        worst = max(worst, err)
    return worst


def phase_lm_train(cfg, platform, rehearse):
    tr = _lm_trainer(cfg)
    out = _train_steps(tr, _token_batches(cfg, 2),
                       _repeat_order(cfg["steps"]), _ce_fn())
    # random tokens under Adam at lr 1e-4 with no warm-up: the loss of a
    # repeated batch overshoots within four steps at this width, on the
    # CPU's lax attention exactly as on the chip (PERF.md, PR 21) — so
    # the loss is held to its start, ln(V), not to falling; the kernels'
    # arithmetic is judged against the lax reference below
    if abs(out["losses"][0] - np.log(cfg["V"])) > 0.1:
        raise AssertionError(
            f"first loss {out['losses'][0]} is not ln(V) = "
            f"{np.log(cfg['V']):.4f} of a freshly initialized LM")
    if platform == "tpu" and not out["kernels_in_step"]:
        # attention(impl="auto") took the lax path: the (T, T) scores
        # would hit HBM and the MFU width means nothing
        raise AssertionError("no tpu_custom_call in the compiled LM step: "
                             "the flash kernel is not in it")
    out["flash_vs_lax_rel_err"] = round(_flash_against_lax(rehearse), 5)
    out["flash_tol"] = FLASH_TOL
    out["arrays_on_device"] = assert_placed(
        "lm FusedTrainer", _trainer_state(tr), platform)
    out.update(model=cfg["name"],
               dtype="bfloat16", optimizer="adam", entry="FusedTrainer.step",
               **{k: cfg[k] for k in ("L", "H", "D", "ff", "T", "V", "B")})
    return out


def _lm_params(cfg, max_len):
    """Seeded random weights at the LM's shapes (host f32): norms start
    at identity, biases at zero, the rest N(0, 0.02)."""
    from mxnet_tpu import models

    net = models.transformer.transformer_lm(
        num_layers=cfg["L"], num_heads=cfg["H"], d_model=cfg["D"],
        d_ff=cfg["ff"], seq_len=max_len, vocab_size=cfg["V"])
    shapes, _, _ = net.infer_shape(data=(1, max_len),
                                   softmax_label=(1, max_len))
    rng = np.random.default_rng(SEED)
    params = {}
    for name, shape in zip(net.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("_gamma"):
            params[name] = np.ones(shape, np.float32)
        elif name.endswith(("_beta", "_bias")):
            params[name] = np.zeros(shape, np.float32)
        else:
            params[name] = 0.02 * rng.standard_normal(shape, np.float32)
    return params


# ------------------------------------------------------------------ serve
def _prompts(lens, vocab):
    rs = np.random.RandomState(SEED + 1)
    return [rs.randint(0, vocab, n).tolist() for n in lens]


def _post_generate(port, prompt, max_tokens):
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "temperature": 0, "deadline_ms": 900000}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as resp:
        return json.loads(resp.read())


def _serve_prompts(decoder, prompts, max_tokens, **scheduler_kwargs):
    """A server around ``decoder``, one warm-up request (it compiles),
    then every prompt at once from threads of this process.  Returns
    (scheduler — closed, its backend still holds the programs —,
    answers, warm-up seconds, concurrent seconds)."""
    from mxnet_tpu.serving import serve_decoder

    server, sched = serve_decoder(decoder, port=0, **scheduler_kwargs)
    try:
        port = server.server_address[1]
        t0 = time.perf_counter()
        warm = _post_generate(port, prompts[0], max_tokens)
        warm_s = time.perf_counter() - t0
        answers = [None] * len(prompts)

        def ask(i):
            answers[i] = _post_generate(port, prompts[i], max_tokens)

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall_s = time.perf_counter() - t0
    finally:
        server.shutdown()
        server.server_close()
        sched.close()
    for i, a in enumerate([warm] + answers):
        if a is None or a["outcome"] != "ok" or a["n_tokens"] != max_tokens:
            raise AssertionError(f"request {i - 1}: wanted {max_tokens} "
                                 f"tokens, got {a}")
    return sched, answers, warm_s, wall_s


def _first_step_logits(backend, prompts):
    """Admit every prompt into its own slot, then ONE decode step over
    all slots with the greedy tokens: (step logits, those tokens)."""
    n = len(prompts)
    admit = [np.asarray(backend.admit(s, np.asarray(p, np.int64)),
                        np.float32) for s, p in enumerate(prompts)]
    tokens = np.zeros(backend.num_slots, np.int64)
    occupied = np.zeros(backend.num_slots, bool)
    for s, row in enumerate(admit):
        tokens[s] = int(row.argmax())
        occupied[s] = True
    logits, starved = backend.step(tokens, occupied)
    if starved:
        raise AssertionError(f"slots {starved} starved of KV pages")
    step = np.asarray(logits, np.float32)[:n]
    for s in range(n):
        backend.release(s)
    return step, tokens[:n]


def phase_serve(cfg, lm, platform, rehearse):
    import jax.numpy as jnp

    from mxnet_tpu.models.decode import KVDecoder
    from mxnet_tpu.serving.paged_kv import PagedSlots

    dec = KVDecoder(_lm_params(lm, cfg["max_len"]), num_layers=lm["L"],
                    num_heads=lm["H"], max_len=cfg["max_len"],
                    dtype=jnp.bfloat16)
    prompts = _prompts(cfg["prompt_lens"], lm["V"])
    # default kernel mode on the chip; on the CPU the same kernel runs
    # interpreted, and only because it is asked for here
    kernel = "interpret" if rehearse else None
    sched, answers, warm_s, wall_s = _serve_prompts(
        dec, prompts, cfg["max_tokens"], num_slots=cfg["slots"],
        kv_block=cfg["block"], paged_kernel=kernel)
    backend = sched.backend
    if (backend.schedule or {}).get("impl") != "pallas":
        raise AssertionError(
            f"paged step schedule is {backend.schedule!r}, not the Pallas "
            "kernel")
    t0 = time.perf_counter()
    text = backend.lower_step().compile().as_text()
    step_compile_s = time.perf_counter() - t0
    kernels = text.count("tpu_custom_call")
    if platform == "tpu" and not kernels:
        raise AssertionError("no tpu_custom_call in the paged step program")
    placed = assert_placed("decoder params + KV pool",
                           {"params": dict(dec.p), "pool": backend.pool},
                           platform)
    # the kernel is judged against the reference, not replaced by it:
    # same prompts, same pool layout, kernel="gather"
    step_k, toks = _first_step_logits(backend, prompts)
    ref = PagedSlots(dec, cfg["slots"], block=cfg["block"],
                     prefill_buckets=backend.prefill_buckets, kernel="gather")
    if ref.schedule is not None:
        raise AssertionError("the reference backend is not gather")
    step_g, toks_g = _first_step_logits(ref, prompts)
    if not np.array_equal(toks, toks_g):
        raise AssertionError("prefill (gather in both) disagreed on tokens")
    if not (np.isfinite(step_k).all() and np.isfinite(step_g).all()):
        raise AssertionError("non-finite first-step logits")
    scale = max(1.0, float(np.abs(step_g).max()))
    err = float(np.abs(step_k - step_g).max())
    if err > LOGIT_TOL * scale:
        raise AssertionError(
            f"Pallas paged kernel vs gather: max |dlogit| {err:.4f} > "
            f"{LOGIT_TOL} * {scale:.3f}")
    return {"model": lm["name"],
            "dtype": "bfloat16", "max_len": cfg["max_len"],
            "kv_block": cfg["block"], "slots": cfg["slots"],
            "entry": "serving.serve_decoder + POST /generate",
            "kernel": backend.stats()["kernel"],
            "schedule": backend.schedule,
            "kernels_in_step": kernels,
            "step_recompile_s": round(step_compile_s, 3),
            "requests": len(answers) + 1,
            "tokens_returned": sum(a["n_tokens"] for a in answers)
            + cfg["max_tokens"],
            "warmup_request_s": round(warm_s, 3),
            "concurrent_requests_s": round(wall_s, 3),
            "ttft_ms": [a["ttft_ms"] for a in answers],
            "first_step_max_abs_dlogit": round(err, 5),
            "first_step_logit_scale": round(scale, 4),
            "logit_tol": LOGIT_TOL,
            "first_step_argmax_agree": float(
                (step_k.argmax(-1) == step_g.argmax(-1)).mean()),
            "arrays_on_device": placed}


# -------------------------------------------------------------- four chips
def _mesh(shape, devices):
    from mxnet_tpu.parallel.mesh import create_mesh

    return create_mesh(shape, ("data", "model"), devices=devices)


def _compare_sharded(name, build, batches_for, steps, mesh, platform,
                     expect_split, must_fall=True):
    """The same seeded model and global batch on the mesh's first device
    and on the whole mesh, in this process: first-step losses must
    agree.  ``expect_split``: over how many devices some parameter must
    be split, not merely replicated (0: pure data parallel)."""
    ce, order = _ce_fn(), _repeat_order(steps)
    devices = list(mesh.devices.flat)
    one = _mesh((1, 1), devices[:1])
    tr = build(one)
    r1 = _train_steps(tr, batches_for(one), order, ce)
    assert_placed(f"{name} on one device", _trainer_state(tr), platform, 1)
    del tr
    gc.collect()
    tr = build(mesh)
    rn = _train_steps(tr, batches_for(mesh), order, ce)
    n_arr = assert_placed(f"{name} on the mesh", _trainer_state(tr),
                          platform, len(devices))
    if expect_split:
        assert_sharded(f"{name} parameters", tr.params, expect_split)
    gap = abs(rn["losses"][0] - r1["losses"][0])
    if gap > 2e-2 * max(1.0, abs(r1["losses"][0])):
        raise AssertionError(
            f"{name}: first-step loss {rn['losses'][0]} on the mesh vs "
            f"{r1['losses'][0]} on one device")
    for r in (r1, rn):
        if must_fall and not r["losses"][-1] < r["losses"][0]:
            raise AssertionError(f"{name}: loss did not fall: {r['losses']}")
    return {"mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "one_device": r1, "mesh_run": rn,
            "first_loss_gap": round(gap, 5), "arrays_on_mesh": n_arr}


def phase_dp_resnet(cfg, devices, platform):
    gb = cfg["batch"] * len(devices)

    out = _compare_sharded(
        "resnet50 dp", lambda mesh: _resnet_trainer(cfg, gb, mesh=mesh),
        lambda mesh: _image_batches(cfg, gb, 2, mesh), 3,
        _mesh((len(devices), 1), devices), platform, expect_split=0)
    out.update(model="resnet-50", global_batch=gb,
               entry="FusedTrainer(mesh=data)")
    return out


def phase_dptp_lm(cfg, devices, platform):
    from mxnet_tpu.parallel.mesh import megatron_rules

    tp = len(devices) // 2
    out = _compare_sharded(
        "lm dp x tp",
        lambda mesh: _lm_trainer(cfg, mesh=mesh,
                                 rules=megatron_rules("model")),
        lambda mesh: _token_batches(cfg, 2, mesh), 3,
        _mesh((2, tp), devices), platform, expect_split=tp,
        must_fall=False)    # see phase_lm_train: held to the first step
    if platform == "tpu" and not out["mesh_run"]["kernels_in_step"]:
        raise AssertionError("no flash kernel in the sharded LM step")
    out.update(model=cfg["name"],
               entry="FusedTrainer(mesh=data x model, megatron_rules)")
    return out


def phase_tp_serve(cfg, lm, devices, platform):
    import jax.numpy as jnp

    from mxnet_tpu.models.decode import KVDecoder

    params = _lm_params(lm, cfg["max_len"])
    prompts = _prompts(cfg["prompt_lens"], lm["V"])[:4]
    kw = dict(num_layers=lm["L"], num_heads=lm["H"], max_len=cfg["max_len"],
              dtype=jnp.bfloat16)
    results = {}
    for tag, mesh in (("tp1", None), ("tp%d" % len(devices),
                                      _mesh((1, len(devices)), devices))):
        dec = KVDecoder(params, mesh=mesh, **kw)
        # paged KV does not take a mesh yet: tensor-parallel serving is
        # the contiguous slot cache
        sched, answers, warm_s, wall_s = _serve_prompts(
            dec, prompts, cfg["max_tokens"], num_slots=len(prompts),
            paged=False)
        last = [np.asarray(dec.prefill(np.asarray([p]))[1][0, -1],
                           np.float32) for p in prompts[:2]]
        state = dec.init_state(len(prompts))
        if mesh is None:
            assert_placed("tp1 decoder", {"p": dict(dec.p),
                                          "cache": state[:2]}, platform, 1)
        else:
            assert_placed("tp decoder", {"p": dict(dec.p),
                                         "cache": state[:2]}, platform,
                          len(devices))
            assert_sharded("tp decoder weights", dict(dec.p), len(devices))
            assert_sharded("tp decoder cache", state[:2], len(devices))
        results[tag] = {"tokens": [a["tokens"] for a in answers],
                        "warmup_request_s": round(warm_s, 3),
                        "concurrent_requests_s": round(wall_s, 3),
                        "logits": last}
        del dec, sched, state
        gc.collect()
    a, b = results.values()
    scale = max(1.0, max(float(np.abs(x).max()) for x in a["logits"]))
    err = max(float(np.abs(x - y).max())
              for x, y in zip(a["logits"], b["logits"]))
    if err > LOGIT_TOL * scale:
        raise AssertionError(f"tp logits differ: max |dlogit| {err:.4f} > "
                             f"{LOGIT_TOL} * {scale:.3f}")
    agree = float(np.mean([x == y for ta, tb in zip(a["tokens"], b["tokens"])
                           for x, y in zip(ta, tb)]))
    for r in results.values():
        del r["logits"]
    return {"model": lm["name"],
            "entry": "KVDecoder(mesh) + serve_decoder",
            "prefill_max_abs_dlogit": round(err, 5),
            "logit_scale": round(scale, 4), "logit_tol": LOGIT_TOL,
            "greedy_token_agreement": agree, **results}


def phase_module_multi(cfg, devices, platform):
    import jax

    import mxnet_tpu as mx

    n = len(devices)
    if jax.device_count() < n:
        # mx.tpu(i) wraps modulo the device count (context.py): on fewer
        # chips the four contexts would silently stack on one
        raise AssertionError(f"need {n} devices, have {jax.device_count()}")
    ctxs = [mx.tpu(i) for i in range(n)]
    resolved = [c.jax_device for c in ctxs]
    if len(set(resolved)) != n or any(d.platform != platform
                                      for d in resolved):
        raise AssertionError(f"mx.tpu(0..{n - 1}) resolved to {resolved}")
    gb = cfg["batch"] * n
    mod, ces, wall = _module_fit(cfg, ctxs, gb, 2, 1)
    mesh = mod._exec_group.mesh
    if mesh.size != n:
        raise AssertionError(f"Module's mesh spans {mesh.size} devices")
    return {"model": "resnet-50", "global_batch": gb, "batches": 2,
            "entry": "Module(context=[mx.tpu(i) for i in range(%d)]).fit" % n,
            "fit_s": round(wall, 3), "train_ce": round(ces[-1], 4),
            "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "arrays_on_mesh": assert_placed(
                "Module over four contexts", _module_arrays(mod),
                platform, n)}


# ------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded paths (dp ResNet-50, "
                         "dp x tp LM, tp serving, Module over four "
                         "contexts) and their one-chip comparisons")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on whatever backend JAX has (the "
                         "CPU): a check of the script, not of the chip")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    from mxnet_tpu import _native, compile_cache
    from mxnet_tpu.telemetry import perf

    cache_dir = compile_cache.enable()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (devices: {devices}); "
              "nothing was run.  --rehearse runs the script at tiny "
              "widths on this backend.", file=sys.stderr)
        return 2
    if platform == "tpu" and args.rehearse:
        print("chip_smoke: --rehearse is for a backend without the chip; "
              "run without it here", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX has {len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    peak = perf.peak_flops(kind)
    if peak is None:
        raise SystemExit(f"device kind {kind!r} has no peak in "
                         "telemetry/perf.py:PEAK_TFLOPS")
    cfg = TINY if args.rehearse else FULL
    native = _native.available()
    emit({"phase": "device", "platform": platform, "kind": kind,
          "count": len(devices), "chips_option": args.chips,
          "rehearsal": args.rehearse,
          "peak_tflops": peak / 1e12,
          "peak_gbps": (perf.peak_bytes_per_sec(kind) or 0) / 1e9,
          "compile_cache_dir": cache_dir,
          "compile_cache_entries_at_start": _n_files(cache_dir),
          "native_lib": native,
          "ffi_backend": _native.ffi_backend() if native else "python",
          "jax": jax.__version__})

    if args.chips == 1:
        phases = [
            ("resnet50_fused_trainer",
             lambda: phase_resnet_fused(cfg["resnet"], platform)),
            ("resnet50_module_fit",
             lambda: phase_resnet_module(cfg["resnet"], platform)),
            ("lm_fused_trainer",
             lambda: phase_lm_train(cfg["lm"], platform, args.rehearse)),
            ("serve_paged_http",
             lambda: phase_serve(cfg["serve"], cfg["lm"], platform,
                                 args.rehearse)),
        ]
    else:
        devs = devices[:args.chips]
        phases = [
            ("dp_resnet50",
             lambda: phase_dp_resnet(cfg["resnet"], devs, platform)),
            ("dptp_lm", lambda: phase_dptp_lm(cfg["lm"], devs, platform)),
            ("tp_serve",
             lambda: phase_tp_serve(cfg["serve"], cfg["lm"], devs,
                                    platform)),
            ("module_four_contexts",
             lambda: phase_module_multi(cfg["resnet"], devs, platform)),
        ]
    for name, fn in phases:
        t0 = time.perf_counter()
        out = fn()
        gc.collect()
        line = {"phase": name, "ok": True,
                "wall_s": round(time.perf_counter() - t0, 3)}
        line.update(out)
        line["peak_bytes_in_use"] = _memory_stat(devices[0],
                                                 "peak_bytes_in_use")
        if args.chips > 1:
            line["bytes_in_use_per_device"] = [
                _memory_stat(d, "bytes_in_use")
                for d in devices[:args.chips]]
        emit(line)
    emit({"phase": "total", "wall_s": round(time.perf_counter() - t_start, 3),
          "compile_cache_dir": cache_dir,
          "compile_cache_entries_at_end": _n_files(cache_dir)})
    verdict = {"ok": True, "device": {"platform": platform, "kind": kind,
                                      "count": len(devices)}}
    if args.rehearse:
        verdict["rehearsal"] = True
    emit(verdict)
    return 0


if __name__ == "__main__":
    sys.exit(main())
