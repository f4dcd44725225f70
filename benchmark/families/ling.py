"""Family adapter: the Ling-3.0-flash decoder (``"family": "ling"``),
served as one chip's share of an expert-parallel deployment.

What the serving driver needs to put a configuration of this family
through the program and to hand the same inputs to the plain reference
(``benchmark/reference/ling.py``): the leaves and their shapes, the
weights from the seed (made on the device leaf by leaf: one expert
matrix is 0.5 GB, its float32 draw 1 GB), the decoder, the reference's
gap over served requests computed LAYER BY LAYER (one layer's float32
weights at a time: the whole is 20.7 GB in float32 and does not fit),
and the model work (``benchmark/work_ling.py``).
"""
import functools
import zlib

import numpy as np

from benchmark import weights, work_ling
from benchmark.reference import ling as ref


# the program's side of this family: a tree without it cannot run the cell
PROGRAM_MODULE = "mxnet_tpu.models.ling"


def sizes(config):
    """The reference's ``Sizes`` as a dict, with the vocabulary held
    here and the widths the work functions need."""
    c = ref.sizes_of(config)
    out = {f.name: getattr(c, f.name) for f in ref.dataclasses.fields(c)}
    out.update(vocab_size=int(config["vocab_size"]),
               n_layer=len(c.mixers),
               moe_width=int(config["moe_intermediate_size"]),
               shared_width=int(config["moe_shared_expert_intermediate_size"]),
               dense_width=int(config["intermediate_size"]))
    return out


def param_specs(config):
    """Every leaf the decoder holds, under the program's names.
    Matrices and embeddings N(0, ``initializer_range`` 0.02), norms at
    identity, the expert bias at nought; ``A_log`` = log of U(1, 16) a
    head and ``dt_bias`` the inverse softplus of a step drawn
    log-uniformly from [0.001, 0.1], as the linear-attention family's
    reference code initialises them (``assumed`` in the file)."""
    s = sizes(config)
    D, H, d, V = s["hidden"], s["heads"], s["head_dim"], s["vocab_size"]
    std = float(config.get("initializer_range", 0.02))
    normal = lambda *sh: {"shape": list(sh), "init": "normal", "std": std}
    ones = lambda *sh: {"shape": list(sh), "init": "ones"}
    specs = {"tok_embed_weight": normal(V, D),
             "final_norm_weight": ones(D),
             "lm_head_weight": normal(V, D)}
    for i, (mixer, mlp) in enumerate(zip(s["mixers"], s["mlps"])):
        p = f"layer{i}_"
        specs[p + "norm1_weight"] = ones(D)
        specs[p + "norm2_weight"] = ones(D)
        if mixer == "kda":
            for n in ("q", "k", "v"):
                specs[p + f"kda_{n}_weight"] = normal(H * d, D)
                # a depthwise kernel of 4 taps: N(0, 0.02) would leave
                # q, k, v at 1e-2 of their inputs; taps of order one
                specs[p + f"kda_{n}_conv"] = {
                    "shape": [H * d, s["conv"]], "init": "normal",
                    "std": 0.5}
            specs[p + "kda_a_weight"] = normal(H * d, D)
            specs[p + "kda_A_log"] = {"shape": [H], "init": "log_uniform",
                                      "low": 1.0, "high": 16.0}
            specs[p + "kda_dt_bias"] = {"shape": [H * d], "init": "dt_bias",
                                        "low": 1e-3, "high": 1e-1}
            specs[p + "kda_beta_weight"] = normal(H, D)
            specs[p + "kda_g_weight"] = normal(H, D)
            specs[p + "kda_onorm_weight"] = ones(d)
            specs[p + "kda_o_weight"] = normal(D, H * d)
        else:
            specs[p + "mla_q_weight"] = normal(
                H * (s["nope"] + s["rope"]), D)
            specs[p + "mla_kva_weight"] = normal(
                s["kv_rank"] + s["rope"], D)
            specs[p + "mla_kv_norm_weight"] = ones(s["kv_rank"])
            specs[p + "mla_kvb_weight"] = normal(
                H * (s["nope"] + s["v_dim"]), s["kv_rank"])
            specs[p + "mla_o_weight"] = normal(D, H * s["v_dim"])
        if mlp == "dense":
            F = s["dense_width"]
            specs[p + "mlp_gate_weight"] = normal(F, D)
            specs[p + "mlp_up_weight"] = normal(F, D)
            specs[p + "mlp_down_weight"] = normal(D, F)
        else:
            E, F, Fs = s["experts_held"], s["moe_width"], s["shared_width"]
            specs[p + "router_weight"] = normal(s["experts"], D)
            specs[p + "router_bias"] = {"shape": [s["experts"]],
                                        "init": "zeros"}
            specs[p + "experts_gate_weight"] = normal(E, D, F)
            specs[p + "experts_up_weight"] = normal(E, D, F)
            specs[p + "experts_down_weight"] = normal(E, F, D)
            specs[p + "shared_gate_weight"] = normal(Fs, D)
            specs[p + "shared_up_weight"] = normal(Fs, D)
            specs[p + "shared_down_weight"] = normal(D, Fs)
    return specs


# float32 whatever the serving type: the decay's parameters and the
# expert bias are a few thousand numbers and steer exponentials
KEPT_FLOAT32 = ("kda_A_log", "kda_dt_bias", "router_bias")


@functools.lru_cache(maxsize=None)
def _maker(shape, init, std, low, high, dtype, round_to):
    import jax
    import jax.numpy as jnp

    def make(key):
        if init == "ones":
            x = jnp.ones(shape, jnp.float32)
        elif init == "zeros":
            x = jnp.zeros(shape, jnp.float32)
        elif init == "normal":
            x = std * jax.random.normal(key, shape, jnp.float32)
        elif init == "log_uniform":     # log of U(low, high)
            x = jnp.log(jax.random.uniform(key, shape, jnp.float32, low,
                                           high))
        elif init == "dt_bias":         # softplus^-1 of a log-uniform step
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, np.log(low), np.log(high)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        else:
            raise ValueError(f"unknown init {init!r}")
        if round_to is not None:
            x = x.astype(round_to)
        return x.astype(dtype)

    return jax.jit(make)


def make_leaves(specs, seed, dtype, round_to=None, only=None):
    """The leaves of ``specs`` (those whose name starts with ``only``,
    if given), each from a key folded from the seed and its name, one
    small program a leaf.  ``round_to`` as in ``weights.make``."""
    import jax
    import jax.numpy as jnp

    key = weights.seed_key(seed)
    out = {}
    for name in sorted(specs):
        if only is not None and not name.startswith(only):
            continue
        spec = specs[name]
        keep = name.endswith(KEPT_FLOAT32)
        fn = _maker(tuple(spec["shape"]), spec.get("init", "normal"),
                    float(spec.get("std", 0.02)),
                    float(spec.get("low", 0.0)), float(spec.get("high", 0.0)),
                    jnp.dtype(jnp.float32 if keep else dtype).name,
                    None if keep or round_to is None
                    else jnp.dtype(round_to).name)
        out[name] = fn(jax.random.fold_in(
            key, zlib.crc32(name.encode()) & 0x7FFFFFFF))
    return out


# ---------------------------------------------------------------- serving
def serving_weights(config, seed, dtype):
    return make_leaves(param_specs(config), seed, dtype)


def build_decoder(config, params, max_len, dtype):
    """The program's decoder for ``serving.serve_decoder``."""
    from mxnet_tpu.models.ling import LingDecoder

    return LingDecoder(params, config, max_len=max_len, dtype=dtype)


def reference_params(config, seed, round_to=None, only=None):
    import jax.numpy as jnp

    return make_leaves(param_specs(config), seed, jnp.float32,
                       round_to=round_to, only=only)


def served_gap(config, seed, requests, compute="f32", length=None):
    """(widest gap, per-request gaps) of the served tokens under the
    float32 reference on the served (bfloat16-rounded) weight values,
    teacher-forced.  ``compute="fp8"``: the control, the gap of the
    token the fp8 computation puts first at each served position (the
    float32 pass runs too, to judge those tokens)."""
    gaps = served(config, seed, requests, compute, length)["gaps"]
    return max(gaps), [round(g, 5) for g in gaps]


def served(config, seed, requests, compute="f32", length=None,
           flips_over=0):
    """The reference over ``requests`` ``[(prompt, served tokens)]``,
    each padded to ``length``.  It runs layer by layer over all the
    requests and every computation wanted: one layer's float32 leaves
    are made, used by each and dropped.  Returns ``gaps`` (a request:
    by how much the worst served token -- or, for a ``compute`` other
    than f32, the token that computation puts first -- lies below the
    float32 reference's best logit) and, with ``flips_over`` = n > 0,
    ``flips``: over the first n requests, the share of (real token, MoE
    layer) pairs whose set of chosen experts differs between the
    float32 reference and the reference with bfloat16 operands, and
    ``flips_held``, the share where the difference touches an expert
    held here.  The reference is never handed the program's choices;
    this says how often the stated precision alone flips one."""
    import jax.numpy as jnp

    c = ref.sizes_of(config)
    rounded = jnp.dtype(config["serving"]["weights_dtype"])
    longest = max(len(p) + len(t) for p, t in requests)
    length = length or -(-longest // 128) * 128
    toks = []
    for prompt, tokens in requests:
        t = np.zeros(length, np.int32)
        t[:len(prompt)] = prompt
        t[len(prompt):len(prompt) + len(tokens)] = tokens
        toks.append(jnp.asarray(t))
    # (computation, how many of the requests it runs over)
    passes = {"f32": len(toks)}
    if compute != "f32":
        passes[compute] = len(toks)
    if flips_over:
        passes["bf16"] = max(passes.get("bf16", 0),
                             min(flips_over, len(toks)))
    top = reference_params(config, seed, rounded, only="tok_embed")
    hs = {m: [ref.embed(top, t) for t in toks[:n]]
          for m, n in passes.items()}
    del top
    differ = held = pairs = 0
    for i, (mixer, mlp) in enumerate(zip(c.mixers, c.mlps)):
        w = ref.layer_leaves(
            reference_params(config, seed, rounded, only=f"layer{i}_"), i)
        outs = {m: [ref.layer(h, w, c, mixer, mlp, m) for h in hs[m]]
                for m in passes}
        hs = {m: [o[0] for o in outs[m]] for m in passes}
        if flips_over and mlp == "moe":
            for (prompt, tokens), a, b in zip(
                    requests, outs["f32"], outs["bf16"]):
                d, h = _choices_differ(
                    np.asarray(a[1]), np.asarray(b[1]),
                    len(prompt) + len(tokens), c)
                differ, held, pairs = differ + d, held + h, \
                    pairs + len(prompt) + len(tokens)
        del w, outs
    top = reference_params(config, seed, rounded, only="final_norm")
    top.update(reference_params(config, seed, rounded, only="lm_head"))
    gaps = []
    for j, ((prompt, tokens), t) in enumerate(zip(requests, toks)):
        lg32 = ref.head(hs["f32"][j], top, c, "f32")
        picks = None
        if compute != "f32":
            picks = jnp.argmax(
                ref.head(hs[compute][j], top, c, compute)[:-1], axis=-1)
        g, _ = ref.gaps_from_logits(lg32, t, len(prompt), len(tokens),
                                    picks)
        gaps.append(float(jnp.max(g)))
    out = {"gaps": gaps}
    if flips_over:
        out.update(flips=differ / max(pairs, 1),
                   flips_held=held / max(pairs, 1), flip_pairs=pairs)
    return out


def _choices_differ(a, b, real, c):
    """Of the first ``real`` rows of two (T, top_k) choices: how many
    differ as sets, and in how many the difference holds an expert of
    ``[expert_offset, expert_offset + experts_held)``."""
    a, b = np.sort(a[:real], 1), np.sort(b[:real], 1)
    rows = np.nonzero((a != b).any(1))[0]
    lo, hi = c.expert_offset, c.expert_offset + c.experts_held
    touched = sum(
        any(lo <= e < hi for e in set(a[r]) ^ set(b[r])) for r in rows)
    return len(rows), touched


# ------------------------------------------------------------------ work
def model_flops(config, requests):
    """Model FLOPs of finished requests ``[(prompt_len, n_tokens)]``."""
    s = sizes(config)
    return sum(work_ling.prefill_flops(s, p)
               + sum(work_ling.decode_flops(s, p + j) for j in range(1, n))
               for p, n in requests)


kernel_work = work_ling.kernel_work
