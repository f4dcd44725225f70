"""Family adapter: GPT-2 style decoders (``"family": "gpt2"``).

What a driver needs to put a configuration of this family through the
program, and to hand the same inputs to the plain reference: the
program's symbol and decoder, the leaves and their shapes, the token
batches, the model work of a step, and the reference's functions.
"""
import numpy as np

from benchmark import weights, work
from benchmark.reference import gpt2 as ref

# token ids are whole numbers: the trainer must not round them to the
# compute dtype on their way to the embedding
WHOLE_NUMBER_INPUTS = ("data",)
LABEL_INPUTS = ("softmax_label",)
SIZES = ("n_layer", "n_head", "n_embd", "n_inner", "vocab_size",
         "n_positions")


def sizes(config):
    return {k: int(config[k]) for k in SIZES}


def param_specs(config, max_len):
    """Every leaf the program's model holds, under the program's names,
    as the benchmark initialises it: matrices and embeddings N(0, 0.02)
    (GPT-2's initializer_range), norms at identity, biases at nought."""
    c = sizes(config)
    D, F, V = c["n_embd"], c["n_inner"], c["vocab_size"]
    std = float(config.get("initializer_range", 0.02))
    normal = lambda *s: {"shape": list(s), "init": "normal", "std": std}
    ones = lambda *s: {"shape": list(s), "init": "ones"}
    zeros = lambda *s: {"shape": list(s), "init": "zeros"}
    specs = {"tok_embed_weight": normal(V, D),
             "pos_embed": normal(1, max_len, D),
             "final_ln_gamma": ones(D), "final_ln_beta": zeros(D),
             "lm_head_weight": normal(V, D), "lm_head_bias": zeros(V)}
    for i in range(c["n_layer"]):
        p = f"layer{i}_"
        for n in ("q", "k", "v", "proj"):
            specs[p + n + "_weight"] = normal(D, D)
            specs[p + n + "_bias"] = zeros(D)
        specs[p + "ffn_in_weight"] = normal(F, D)
        specs[p + "ffn_in_bias"] = zeros(F)
        specs[p + "ffn_out_weight"] = normal(D, F)
        specs[p + "ffn_out_bias"] = zeros(D)
        for n in ("ln1", "ln2"):
            specs[p + n + "_gamma"] = ones(D)
            specs[p + n + "_beta"] = zeros(D)
    return specs


# ---------------------------------------------------------------- program
def build_symbol(config, traffic):
    from mxnet_tpu import models

    c = sizes(config)
    return models.transformer.transformer_lm(
        num_layers=c["n_layer"], num_heads=c["n_head"],
        d_model=c["n_embd"], d_ff=c["n_inner"],
        seq_len=int(traffic["seq_len"]), vocab_size=c["vocab_size"],
        max_len=c["n_positions"])


def input_shapes(config, traffic):
    shape = (int(traffic["batch"]), int(traffic["seq_len"]))
    return {"data": shape, "softmax_label": shape}


def train_specs(config, traffic):
    return param_specs(config, sizes(config)["n_positions"]), {}


def host_batches(config, traffic, seed, n):
    """``n`` host batches of token ids uniform over the vocabulary, ids
    as float32 as the symbol takes them (the vocabulary is below 2**24);
    every row differs."""
    c = sizes(config)
    rng = np.random.default_rng([int(seed), 1])
    shape = (int(traffic["batch"]), int(traffic["seq_len"]))
    return [{"data": rng.integers(0, c["vocab_size"], shape)
             .astype(np.float32),
             "softmax_label": rng.integers(0, c["vocab_size"], shape)
             .astype(np.float32)} for _ in range(n)]


def labels_of(batch):
    return batch["softmax_label"]


def step_flops(config, traffic):
    c = sizes(config)
    tokens = int(traffic["batch"]) * int(traffic["seq_len"])
    return tokens * work.gpt2_train_flops_per_token(
        c["n_layer"], c["n_embd"], c["n_inner"], c["vocab_size"],
        int(traffic["seq_len"]))


def kernel_work(config, traffic):
    """Work of the step's Pallas kernels, by the name of the roofline
    metric that reads it: per step, over all layers."""
    c = sizes(config)
    one = work.flash_attention_train_work(
        int(traffic["batch"]), c["n_head"], int(traffic["seq_len"]),
        c["n_embd"] // c["n_head"])
    return {"flash_attn": {"flops": one["flops"] * c["n_layer"],
                           "bytes": one["bytes"] * c["n_layer"],
                           "calls_per_step": 3 * c["n_layer"]}}


# -------------------------------------------------------------- reference
def reference_params(config, seed, round_to=None, max_len=None):
    c = sizes(config)
    import jax.numpy as jnp

    return weights.make(param_specs(config, max_len or c["n_positions"]),
                        seed, jnp.float32, round_to=round_to,
                        stack_layers=c["n_layer"])


def reference_grads(config, params, aux, batch, rows, compute, keep=None):
    """Gradient of the SUMMED cross-entropy over the batch (what the
    program's SoftmaxOutput hands back, before ``rescale_grad``) and the
    mean cross-entropy, in blocks of ``rows`` rows.  ``keep``: use only
    the first ``keep`` rows and weigh them as the whole batch (the
    planted fault "half of the batch left out, the mean taken over the
    rest")."""
    import jax
    import jax.numpy as jnp

    c = sizes(config)
    tok = jnp.asarray(batch["data"], jnp.int32)
    lab = jnp.asarray(batch["softmax_label"], jnp.int32)
    total = tok.shape[0]
    if keep:
        tok, lab = tok[:keep], lab[:keep]
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    loss = 0.0
    for r in range(0, tok.shape[0], rows):
        acc, part = ref.accumulate_grads(
            params, acc, tok[r:r + rows], lab[r:r + rows],
            n_head=c["n_head"], compute=compute)
        loss += float(part)
    if keep:
        scale = total / tok.shape[0]
        acc = jax.tree_util.tree_map(lambda g: g * scale, acc)
    return loss / tok.size, acc, aux


# ---------------------------------------------------------------- serving
def serving_weights(config, seed, dtype):
    """The program's weights for serving, made on the device in the type
    they are served in."""
    return weights.make(param_specs(config, sizes(config)["n_positions"]),
                        seed, dtype)


def served_gap(config, seed, requests, compute="f32", length=None):
    """(widest gap, per-request gaps) of the served tokens under the
    float32 reference, which computes on the served (bfloat16-rounded)
    weight values.  ``compute="fp8"`` reads the control instead: the gap
    of the token the fp8 computation puts first at each served
    position."""
    import jax.numpy as jnp

    c = sizes(config)
    params = reference_params(
        config, seed, round_to=jnp.dtype(config["serving"]["weights_dtype"]))
    longest = max(len(p) + len(t) for p, t in requests)
    length = length or -(-longest // 128) * 128
    worst, detail = 0.0, []
    for prompt, served in requests:
        toks = np.zeros(length, np.int32)
        toks[:len(prompt)] = prompt
        toks[len(prompt):len(prompt) + len(served)] = served
        toks = jnp.asarray(toks)
        first, count = len(prompt), len(served)
        if compute == "f32":
            gaps, _ = ref.served_gaps(params, toks, first, count,
                                      n_head=c["n_head"], compute="f32")
        else:
            _, picks = ref.served_gaps(params, toks, first, count,
                                       n_head=c["n_head"], compute=compute)
            gaps = ref.gaps_of(params, toks, picks, first, count,
                               n_head=c["n_head"])
        g = float(jnp.max(gaps))
        detail.append(round(g, 5))
        worst = max(worst, g)
    return worst, detail
