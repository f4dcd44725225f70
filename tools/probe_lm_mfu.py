"""Probe: transformer-LM training MFU on the real chip.

ResNet-50-with-BN is HBM-bound on v5e (a raw-JAX probe capped at ~0.175
MFU before PR 1; capture deleted in PR 21, not measured this round), so
the framework's compute-bound headline is the transformer LM: big
matmuls (qkv/proj/ffn/head) dominate and the MXU can actually be fed.  This probe sweeps model/batch configs through the SAME
FusedTrainer + symbol path bench.py uses (no hand-written raw-JAX model)
and reports model-FLOP MFU per config.

FLOP accounting (conservative, causal-halved):
  train FLOPs/token = 6*N_mat + 6*L*T*D
where N_mat counts matmul params only (embedding gathers are free) —
the standard 6N rule with flash attention's causal block skipping
(ops/flash_attention.py) counted at half the full T^2 cost.

Run on the bench chip:  python tools/probe_lm_mfu.py
CPU smoke:  MXTPU_PLATFORM=cpu python tools/probe_lm_mfu.py --smoke
"""
import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

PEAK_BF16 = 197e12  # v5e dense bf16 peak (bench.py table)


def lm_train_flops_per_token(L, D, d_ff, T, V):
    # the one shared accounting rule (models/transformer.py) — bench.py's
    # transformer_lm_mfu extra uses the same function
    from mxnet_tpu.models.transformer import lm_train_flops_per_token as f

    return f(L, D, d_ff, T, V)


def run_config(name, L, H, D, d_ff, T, V, B, iters=12, peak=PEAK_BF16):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import models
    from mxnet_tpu.trainer import FusedTrainer

    lm = models.transformer.transformer_lm(
        num_layers=L, num_heads=H, d_model=D, d_ff=d_ff, seq_len=T,
        vocab_size=V)
    tr = FusedTrainer(lm, optimizer="adam", optimizer_params={"lr": 1e-4},
                      dtype=jnp.bfloat16)
    tr.init(data=(B, T), softmax_label=(B, T))
    rs = np.random.RandomState(0)
    toks = jax.device_put(rs.randint(0, V, (B, T)).astype(np.float32))
    labs = jax.device_put(rs.randint(0, V, (B, T)).astype(np.float32))
    pname = sorted(tr.params)[0]

    def barrier():
        return float(np.asarray(tr.params[pname]).ravel()[0])

    tr.step(data=toks, softmax_label=labs)  # compile
    barrier()
    tr.step(data=toks, softmax_label=labs)  # settle
    barrier()
    tic = time.perf_counter()
    for _ in range(iters):
        tr.step(data=toks, softmax_label=labs)
    barrier()
    dt = time.perf_counter() - tic
    tok_s = B * T * iters / dt
    fpt = lm_train_flops_per_token(L, D, d_ff, T, V)
    mfu = tok_s * fpt / peak
    print(f"{name}: L{L} H{H} D{D} ff{d_ff} T{T} V{V} B{B}  "
          f"{tok_s:9.0f} tok/s  {tok_s * fpt / 1e12:6.1f} TF/s  "
          f"mfu={mfu:.3f}", flush=True)
    return mfu


def run_one_subprocess(name, cfg, iters, timeout=420):
    """One config in its own process: a failed/OOMed config must not
    poison the rest of the sweep (the first on-silicon capture lost 3
    configs to a RESOURCE_EXHAUSTED cascade after one real OOM).  The
    parent never touches JAX, so each child in turn is the one process
    that holds the chip."""
    spec = json.dumps({"name": name, "cfg": cfg, "iters": iters})
    try:
        r = subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--one", spec], capture_output=True,
                           text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"{name}: FAILED timeout", flush=True)
        return 0.0
    for line in r.stdout.splitlines():
        if "mfu=" in line:
            print(line, flush=True)
            return float(line.rsplit("mfu=", 1)[1])
    tail = (r.stdout + r.stderr).strip().splitlines()
    print(f"{name}: FAILED {tail[-1] if tail else 'no output'}", flush=True)
    return 0.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config on cpu (plumbing check only)")
    ap.add_argument("--iters", type=int, default=12)
    ap.add_argument("--one", type=str, default=None,
                    help="(internal) JSON spec: run one config and exit")
    args = ap.parse_args()

    if args.one:
        spec = json.loads(args.one)
        run_config(spec["name"], iters=spec["iters"], **spec["cfg"])
        return

    if os.environ.get("MXTPU_PLATFORM") == "cpu" or args.smoke:
        import jax

        jax.config.update("jax_platforms", "cpu")
        run_config("smoke", L=2, H=4, D=128, d_ff=512, T=128, V=512, B=2,
                   iters=3)
        return

    import jax

    print("devices:", jax.devices(), flush=True)
    from mxnet_tpu.models.transformer import MFU_HEADLINE_CONFIG as HC

    head = dict(L=HC["num_layers"], H=HC["num_heads"], D=HC["d_model"],
                d_ff=HC["d_ff"], T=HC["seq_len"], V=HC["vocab_size"])
    # medium-first: if the big config OOMs or hangs, the smaller numbers
    # are already on stdout
    configs = [
        ("lm-560m-b8",  dict(head, B=8)),   # bench.py's headline config
        ("lm-220m-b8",  dict(L=12, H=16, D=1024, d_ff=4096, T=1024,
                             V=32768, B=8)),
        ("lm-220m-b16", dict(L=12, H=16, D=1024, d_ff=4096, T=1024,
                             V=32768, B=16)),
        ("lm-small-b8", dict(L=4, H=8, D=512, d_ff=2048, T=512,
                             V=8192, B=8)),  # bench.py extras continuity
    ]
    best = (None, 0.0, None)
    for name, cfg in configs:
        mfu = run_one_subprocess(name, cfg, args.iters)
        if mfu > best[1]:
            best = (name, mfu, cfg)
    print(f"best: {best[0]} mfu={best[1]:.3f}", flush=True)


if __name__ == "__main__":
    main()
