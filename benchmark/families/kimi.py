"""Family adapter: the Kimi-K2 decoder (``"family": "kimi"``, the
DeepSeek-V3 block), served as one chip's share of an expert-parallel
deployment.

What the serving driver needs to put a configuration of this family
through the program and to hand the same inputs to the plain reference
(``benchmark/reference/kimi.py``): the leaves and their shapes, the
weights from the seed (leaf by leaf, as ``families/ling.py`` makes them:
one expert stack is 0.35 GB, its float32 draw 0.7 GB), the decoder, the
reference's gap over served requests computed LAYER BY LAYER (one
layer's float32 leaves at a time: 2.7 GB for a layer with experts),
each request padded to its own length, and the model work
(``benchmark/work_kimi.py``).
"""
import numpy as np

from benchmark import work_kimi
from benchmark.families.ling import make_leaves
from benchmark.reference import kimi as ref

# the program's side of this family: a tree without it cannot run the cell
PROGRAM_MODULE = "mxnet_tpu.models.kimi"
# rows of hidden state the head runs over for one request's check: the
# served positions (at most 192 a request) and room before them
HEAD_WINDOW = 256


def sizes(config):
    """The reference's ``Sizes`` as a dict, with the vocabulary held
    here and the widths the work functions need."""
    c = ref.sizes_of(config)
    out = {f.name: getattr(c, f.name) for f in ref.dataclasses.fields(c)}
    out.update(vocab_size=int(config["vocab_size"]),
               n_layer=len(c.mlps),
               moe_width=int(config["moe_intermediate_size"]),
               shared_width=int(config["moe_intermediate_size"])
               * int(config["n_shared_experts"]),
               dense_width=int(config["intermediate_size"]))
    return out


def param_specs(config):
    """Every leaf the decoder holds, under the program's names.
    Matrices and embeddings N(0, ``initializer_range`` 0.02), norms at
    one, the router's correction bias N(0, 0.01) (``assumed`` in the
    file: a trained bias is small and not nought)."""
    s = sizes(config)
    D, H, V = s["hidden"], s["heads"], s["vocab_size"]
    std = float(config.get("initializer_range", 0.02))
    normal = lambda *sh: {"shape": list(sh), "init": "normal", "std": std}
    ones = lambda *sh: {"shape": list(sh), "init": "ones"}
    specs = {"tok_embed_weight": normal(V, D),
             "final_norm_weight": ones(D),
             "lm_head_weight": normal(V, D)}
    for i, mlp in enumerate(s["mlps"]):
        p = f"layer{i}_"
        specs.update({
            p + "norm1_weight": ones(D), p + "norm2_weight": ones(D),
            p + "mla_qa_weight": normal(s["q_rank"], D),
            p + "mla_q_norm_weight": ones(s["q_rank"]),
            p + "mla_qb_weight": normal(H * (s["nope"] + s["rope"]),
                                        s["q_rank"]),
            p + "mla_kva_weight": normal(s["kv_rank"] + s["rope"], D),
            p + "mla_kv_norm_weight": ones(s["kv_rank"]),
            p + "mla_kvb_weight": normal(H * (s["nope"] + s["v_dim"]),
                                         s["kv_rank"]),
            p + "mla_o_weight": normal(D, H * s["v_dim"])})
        if mlp == "dense":
            F = s["dense_width"]
            specs.update({p + "mlp_gate_weight": normal(F, D),
                          p + "mlp_up_weight": normal(F, D),
                          p + "mlp_down_weight": normal(D, F)})
        else:
            E, F, Fs = s["experts_held"], s["moe_width"], s["shared_width"]
            specs.update({
                p + "router_weight": normal(s["experts"], D),
                p + "router_bias": {"shape": [s["experts"]],
                                    "init": "normal", "std": 0.01},
                p + "experts_gate_weight": normal(E, D, F),
                p + "experts_up_weight": normal(E, D, F),
                p + "experts_down_weight": normal(E, F, D),
                p + "shared_gate_weight": normal(Fs, D),
                p + "shared_up_weight": normal(Fs, D),
                p + "shared_down_weight": normal(D, Fs)})
    return specs


# ---------------------------------------------------------------- serving
def serving_weights(config, seed, dtype):
    return make_leaves(param_specs(config), seed, dtype)


def build_decoder(config, params, max_len, dtype):
    """The program's decoder for ``serving.serve_decoder``."""
    from mxnet_tpu.models.kimi import KimiDecoder

    return KimiDecoder(params, config, max_len=max_len, dtype=dtype)


def reference_params(config, seed, round_to=None, only=None):
    import jax.numpy as jnp

    return make_leaves(param_specs(config), seed, jnp.float32,
                       round_to=round_to, only=only)


def served(config, seed, requests, compute="f32", pad_to=1024):
    """The reference over ``requests`` ``[(prompt, served tokens)]``,
    each padded to its own multiple of ``pad_to``.  A computation at a
    time (float32; for a ``compute`` other than f32 that one after it)
    it runs layer by layer over all the requests: one layer's float32
    leaves are made, used by each and dropped, and of a request's last
    hidden states only the ``HEAD_WINDOW`` rows that hold its served
    positions are kept for the head.  Returns ``{"served": {"gaps",
    "mean"}}``: a request, by how much its worst served token lies
    below the float32 reference's best logit, and that gap's mean over
    all the served tokens of all the requests; for a ``compute`` other
    than f32 also ``"control"``, the same of the token that computation
    puts first at each served position.  The reference is never handed
    the program's choices of experts, nor its cache."""
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
    logits = {}
    for mode in ("f32",) if compute == "f32" else ("f32", compute):
        top = leaves("tok_embed")
        hs = [ref.embed(top, t) for t in toks]
        for i, mlp in enumerate(c.mlps):
            w = ref.layer_leaves(leaves(f"layer{i}_"), i)
            hs = [ref.layer(h, w, c, mlp, mode)[0] for h in hs]
            del w
        top = dict(leaves("final_norm"), **leaves("lm_head"))
        logits[mode] = [ref.head(h[lo:lo + W], top, c, mode)
                        for h, (lo, W) in zip(hs, windows)]
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
    return out


# ------------------------------------------------------------------ work
def model_flops(config, requests):
    """Model FLOPs of finished requests ``[(prompt_len, n_tokens,
    hist)]``, ``hist`` of the prompt's tokens having come from shared
    pages."""
    s = sizes(config)
    return sum(work_kimi.prefill_flops(s, p, hist)
               + sum(work_kimi.decode_flops(s, p + j) for j in range(1, n))
               for p, n, hist in requests)


kernel_work = work_kimi.kernel_work
chunks_of = work_kimi.chunks_of
