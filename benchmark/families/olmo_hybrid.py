"""Family adapter: the Olmo-Hybrid decoder (``"family": "olmo_hybrid"``),
served as one stage of a pipeline.

What the serving driver needs to put a configuration of this family
through the program and to hand the same inputs to the plain reference
(``benchmark/reference/olmo_hybrid.py``): the leaves and their shapes,
the weights from the seed (leaf by leaf, as ``families/ling.py`` makes
them), the decoder, the reference's gaps over served requests computed
LAYER BY LAYER (one layer's float32 leaves at a time), each request
padded to its own length, the model work
(``benchmark/work_olmo_hybrid.py``), and the named scopes of its mixers
(``drivers/serve_sessions_state.py`` reads them beside the others').
"""
import numpy as np

from benchmark import work_olmo_hybrid
from benchmark.families.ling import make_leaves
from benchmark.reference import olmo_hybrid as ref

# the program's side of this family: a tree without it cannot run the cell
PROGRAM_MODULE = "mxnet_tpu.models.olmo_hybrid"
# rows of hidden state the head runs over for one request's check: the
# served positions (at most 192 a request) and room before them
HEAD_WINDOW = 256
# the named scopes of the two mixers (``gdn.state`` inside ``gdn``),
# ahead of the driver's own
SCOPES = ("gdn.state", "gdn", "attn.pages")
# host counts of ``PagedSlots.stats()`` read over the window
COUNTERS = ("state_chunks_carried",)
# Pallas kernels in the step: the paged attention of each attention
# layer
KERNELS_IN_STEP = 2
# the decay's projection: N(0, 5e-4) keeps ``x W_a`` within about
# +-0.15 of the un-normed residual (OLMo puts no norm before the mixer),
# so the decays spread over the timescales ``dt_bias`` sets (``assumed``)
DECAY_STD = 5e-4


def sizes(config):
    """The reference's ``Sizes`` as a dict, with the vocabulary and the
    widths the work functions need."""
    c = ref.sizes_of(config)
    out = {f.name: getattr(c, f.name) for f in ref.dataclasses.fields(c)}
    out.update(vocab_size=int(config["vocab_size"]),
               n_layer=len(c.mixers),
               mlp_width=int(config["intermediate_size"]))
    return out


def param_specs(config):
    """Every leaf the decoder holds, under the program's names.
    Matrices and embeddings N(0, ``initializer_range``), norms at
    identity, convolution taps N(0, 0.5), the decay's projection N(0,
    ``DECAY_STD``); ``A_log`` = log of U(1, 16) a head and ``dt_bias``
    the inverse softplus of a step drawn log-uniformly from [0.001,
    0.1], as ``families/ling.py`` makes them (``assumed`` in the
    file)."""
    s = sizes(config)
    D, V, F = s["hidden"], s["vocab_size"], s["mlp_width"]
    H, dk, dv = s["lin_heads"], s["d_k"], s["d_v"]
    A = s["heads"] * s["head_dim"]
    std = float(config.get("initializer_range", 0.02))
    normal = lambda *sh: {"shape": list(sh), "init": "normal", "std": std}
    ones = lambda *sh: {"shape": list(sh), "init": "ones"}
    specs = {"tok_embed_weight": normal(V, D),
             "final_norm_weight": ones(D),
             "lm_head_weight": normal(V, D)}
    for i, mixer in enumerate(s["mixers"]):
        p = f"layer{i}_"
        specs.update({p + "mix_norm_weight": ones(D),
                      p + "mlp_norm_weight": ones(D),
                      p + "mlp_gate_weight": normal(F, D),
                      p + "mlp_up_weight": normal(F, D),
                      p + "mlp_down_weight": normal(D, F)})
        if mixer == "gdn":
            p += "gdn_"
            for n, d in (("q", dk), ("k", dk), ("v", dv)):
                specs[p + n + "_weight"] = normal(H * d, D)
                # a depthwise kernel of 4 taps: N(0, 0.02) would leave
                # q, k, v at 1e-2 of their inputs; taps of order one
                specs[p + n + "_conv"] = {"shape": [H * d, s["conv"]],
                                          "init": "normal", "std": 0.5}
            specs.update({
                p + "a_weight": {"shape": [H, D], "init": "normal",
                                 "std": DECAY_STD},
                p + "b_weight": normal(H, D),
                p + "A_log": {"shape": [H], "init": "log_uniform",
                              "low": 1.0, "high": 16.0},
                p + "dt_bias": {"shape": [H], "init": "dt_bias",
                                "low": 1e-3, "high": 1e-1},
                p + "g_weight": normal(H * dv, D),
                p + "onorm_weight": ones(dv),
                p + "o_weight": normal(D, H * dv)})
        else:
            p += "attn_"
            specs.update({p + "q_weight": normal(A, D),
                          p + "k_weight": normal(A, D),
                          p + "v_weight": normal(A, D),
                          p + "o_weight": normal(D, A),
                          p + "q_norm_weight": ones(A),
                          p + "k_norm_weight": ones(A)})
    return specs


# ---------------------------------------------------------------- serving
def serving_weights(config, seed, dtype):
    return make_leaves(param_specs(config), seed, dtype)


def build_decoder(config, params, max_len, dtype):
    """The program's decoder for ``serving.serve_decoder``."""
    from mxnet_tpu.models.olmo_hybrid import OlmoHybridDecoder

    return OlmoHybridDecoder(params, config, max_len=max_len, dtype=dtype)


def reference_params(config, seed, round_to=None, only=None):
    import jax.numpy as jnp

    return make_leaves(param_specs(config), seed, jnp.float32,
                       round_to=round_to, only=only)


def served(config, seed, requests, compute="f32", pad_to=2048, edges=None):
    """The reference over ``requests`` ``[(prompt, served tokens)]``,
    each padded to its own multiple of ``pad_to``, on the
    bfloat16-rounded weights.  A computation at a time (float32; for a
    ``compute`` other than f32 that one after it) it runs layer by layer
    over all the requests: one layer's float32 leaves are made, used by
    each and dropped, and of a request's last hidden states only the
    ``HEAD_WINDOW`` rows that hold its served positions go to the head.
    Returns ``{"served": {"gaps", "mean"}}``: a request, by how much its
    worst served token lies below the float32 reference's best logit,
    and that gap's mean over all the served tokens of all the requests;
    for a ``compute`` other than f32 also ``"control"``, the same of the
    token that computation puts first at each served position.  The
    reference is never handed the program's cache or its state.

    ``edges``: a request, the program's logits at positions of its
    prompt, ``{position: (V,)}`` (``drivers/serve_sessions_state.py:
    Driver.chunk_edges``).  Then ``"edges"`` holds, a request, the
    widest root-mean-square difference over the vocabulary between
    those rows and the float32 reference's logits at the same
    positions (0 for a request without one), and for a ``compute``
    other than f32 ``"control_edges"`` the same of that computation's
    logits."""
    import jax.numpy as jnp

    c = ref.sizes_of(config)
    rounded = jnp.dtype(config["serving"]["weights_dtype"])
    leaves = lambda only: reference_params(config, seed, rounded, only=only)
    toks, windows = [], []
    for prompt, tokens in requests:
        t = np.zeros(-(-(len(prompt) + len(tokens)) // pad_to) * pad_to,
                     np.int32)
        t[:len(prompt)] = prompt
        t[len(prompt):len(prompt) + len(tokens)] = tokens
        toks.append(jnp.asarray(t))
        W = min(HEAD_WINDOW, t.shape[0])
        windows.append((min(len(prompt) - 1, t.shape[0] - W), W))
    logits, at_edges = {}, {}
    where = [sorted(e) for e in edges] if edges is not None else None
    for mode in ("f32",) if compute == "f32" else ("f32", compute):
        top = leaves("tok_embed")
        hs = [ref.embed(top, t) for t in toks]
        for i, mixer in enumerate(c.mixers):
            w = ref.layer_leaves(leaves(f"layer{i}_"), i)
            hs = [ref.layer(h, w, c, mixer, mode) for h in hs]
            del w
        top = dict(leaves("final_norm"), **leaves("lm_head"))
        logits[mode] = [ref.head(h[lo:lo + W], top, c, mode)
                        for h, (lo, W) in zip(hs, windows)]
        if where is not None:
            at_edges[mode] = [ref.head(h[jnp.asarray(pos, jnp.int32)], top,
                                       c, mode) if pos else None
                              for h, pos in zip(hs, where)]
        del hs, top
    out = {}
    n = sum(len(tokens) for _p, tokens in requests)
    for kind, mode in (("served", "f32"), ("control", compute)):
        if kind == "control" and compute == "f32":
            continue
        rows = []
        for j, ((prompt, tokens), t) in enumerate(zip(requests, toks)):
            lo, W = windows[j]
            picks = None if kind == "served" \
                else jnp.argmax(logits[mode][j][:-1], axis=-1)
            g, _ = ref.gaps_from_logits(
                logits["f32"][j], t[lo:lo + W], len(prompt) - lo,
                len(tokens), picks)
            rows.append((float(jnp.max(g)), float(jnp.sum(g))))
        out[kind] = {"gaps": [widest for widest, _ in rows],
                     "mean": sum(total for _, total in rows) / n}
    if where is None:
        return out
    rms = lambda a, b: float(jnp.max(jnp.sqrt(jnp.mean(
        jnp.square(a - b), axis=-1))))
    out["edges"] = [
        rms(jnp.asarray(np.stack([e[p] for p in pos])), want) if pos
        else 0.0 for e, pos, want in zip(edges, where, at_edges["f32"])]
    if compute != "f32":
        out["control_edges"] = [
            rms(got, want) if pos else 0.0 for pos, got, want in
            zip(where, at_edges[compute], at_edges["f32"])]
    return out


# ------------------------------------------------------------------ work
def model_flops(config, requests):
    """Model FLOPs of finished requests ``[(prompt_len, n_tokens,
    hist)]``; ``hist`` is 0 here, no prompt token comes from shared
    pages."""
    s = sizes(config)
    return sum(work_olmo_hybrid.prefill_flops(s, p, hist)
               + sum(work_olmo_hybrid.decode_flops(s, p + j)
                     for j in range(1, n))
               for p, n, hist in requests)


kernel_work = work_olmo_hybrid.kernel_work
chunks_of = work_olmo_hybrid.chunks_of
