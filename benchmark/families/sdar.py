"""Family adapter: the SDAR decoder (``"family": "sdar"``), generation
by diffusion over blocks, served as one pipeline stage.

What the serving driver needs to put a configuration of this family
through the program and to hand the same inputs to the plain reference
(``benchmark/reference/sdar.py``): the leaves and their shapes, the
weights from the seed (leaf by leaf, as ``families/ling.py`` makes
them: one expert matrix is 0.4 GB), the decoder, the reference's gaps
over served trajectories computed LAYER BY LAYER (one layer's float32
leaves at a time: the whole is 20 GB in float32), and the model work
(``benchmark/work_sdar.py``).
"""
import numpy as np

from benchmark import work_sdar
from benchmark.families.ling import make_leaves
from benchmark.reference import sdar as ref

# the program's side of this family: a tree without it cannot run the cell
PROGRAM_MODULE = "mxnet_tpu.models.sdar"
# one paged-attention kernel a layer in the step
KERNELS_IN_STEP = 7
FAULTS = ("token_altered", "unmaskings_swapped", "commit_left_out")


def sizes(config):
    """The reference's ``Sizes`` as a dict and the widths the work
    functions need.  ``vocab_size`` is the TRAFFIC's vocabulary, ``[0,
    mask_token_id)``: no prompt holds the mask id; the model's is
    ``vocab_size_full``."""
    c = ref.sizes_of(config)
    out = {f.name: getattr(c, f.name) for f in ref.dataclasses.fields(c)}
    out.update(vocab_size=c.mask_id,
               vocab_size_full=int(config["vocab_size"]),
               n_layer=c.layers,
               moe_width=int(config["moe_intermediate_size"]))
    return out


def param_specs(config):
    """Every leaf the decoder holds, under the program's names: matrices
    and embeddings N(0, 0.02), norms at identity."""
    s = sizes(config)
    D, H, Hkv, dh = s["hidden"], s["heads"], s["kv_heads"], s["head_dim"]
    E, F, V = s["experts"], s["moe_width"], s["vocab_size_full"]
    std = float(config.get("initializer_range", 0.02))
    normal = lambda *sh: {"shape": list(sh), "init": "normal", "std": std}
    ones = lambda *sh: {"shape": list(sh), "init": "ones"}
    specs = {"tok_embed_weight": normal(V, D),
             "final_norm_weight": ones(D),
             "lm_head_weight": normal(V, D)}
    for i in range(s["n_layer"]):
        p = f"layer{i}_"
        specs.update({
            p + "norm1_weight": ones(D), p + "norm2_weight": ones(D),
            p + "q_weight": normal(H * dh, D),
            p + "k_weight": normal(Hkv * dh, D),
            p + "v_weight": normal(Hkv * dh, D),
            p + "o_weight": normal(D, H * dh),
            p + "q_norm_weight": ones(dh), p + "k_norm_weight": ones(dh),
            p + "router_weight": normal(E, D),
            p + "experts_gate_weight": normal(E, D, F),
            p + "experts_up_weight": normal(E, D, F),
            p + "experts_down_weight": normal(E, F, D)})
    return specs


# ---------------------------------------------------------------- serving
def serving_weights(config, seed, dtype):
    return make_leaves(param_specs(config), seed, dtype)


def build_decoder(config, params, max_len, dtype):
    """The program's decoder for ``serving.serve_decoder``."""
    from mxnet_tpu.models.sdar import SdarDecoder

    return SdarDecoder(params, config, max_len=max_len, dtype=dtype)


def reference_params(config, seed, round_to=None, only=None):
    import jax.numpy as jnp

    return make_leaves(param_specs(config), seed, jnp.float32,
                       round_to=round_to, only=only)


def _up(x, to):
    return -(-x // to) * to


def planted(requests, fault, n, vocab):
    """``requests`` ``[(prompt, tokens, unmask_step)]`` with a fault
    planted in each trajectory: ``token_altered`` -- a token of the
    middle block changed where it was produced; ``unmaskings_swapped``
    -- in every block two positions fixed by different forwards trade
    their forwards.  (``commit_left_out`` is planted in the reference's
    clean stream: ``served(..., fault=...)``.)"""
    out = []
    for prompt, tokens, unmask in requests:
        tokens, unmask = list(tokens), list(unmask)
        if fault == "token_altered":
            j = len(tokens) // 2
            tokens[j] = (tokens[j] + 1) % vocab
        elif fault == "unmaskings_swapped":
            off = -len(prompt) % n      # tokens to the first boundary
            for b in range(off, len(tokens) - n + 1, n):
                lo = min(range(b, b + n), key=lambda j: unmask[j])
                hi = max(range(b, b + n), key=lambda j: unmask[j])
                unmask[lo], unmask[hi] = unmask[hi], unmask[lo]
        out.append((prompt, tokens, unmask))
    return out


def served(config, seed, requests, compute="f32", length=None, fault=None):
    """The reference over served trajectories ``[(prompt, tokens,
    unmask_step)]``: per request ONE forward over its streams
    (``reference.teacher_streams``: the clean sequence and, per ordinal
    of a denoising forward, the generated blocks as they stood before
    it), layer by layer over all the requests and every computation
    wanted, one layer's float32 leaves made, used and dropped.  Returns
    ``logit_gaps`` and ``order_gaps`` a request
    (``reference.trajectory_gaps``) and ``compared``, the positions
    compared.  ``compute`` other than f32: the control -- the tokens
    that computation puts first and the positions it would have fixed,
    judged by the float32 pass.  ``fault``: one of ``FAULTS``, planted
    in the trajectories or, for ``commit_left_out``, in what the
    reference keeps of a block."""
    import jax.numpy as jnp

    c = ref.sizes_of(config)
    if fault in ("token_altered", "unmaskings_swapped"):
        requests = planted(requests, fault, c.block_length, c.mask_id)
    rounded = jnp.dtype(config["serving"]["weights_dtype"])
    n = c.block_length
    steps = max(max(u) for _p, _t, u in requests) + 1
    length = length or _up(max(len(p) + len(t) for p, t, _u in requests), 128)
    noised = _up(max((len(p) % n) + len(t) for p, t, _u in requests), 64)
    streams = [ref.teacher_streams(p, t, u, c, length, noised, steps,
                                   commit=fault != "commit_left_out")
               for p, t, u in requests]
    passes = ["f32"] + ([compute] if compute != "f32" else [])
    top = reference_params(config, seed, rounded, only="tok_embed")
    arrays = [tuple(jnp.asarray(a, jnp.int32) for a in st[:3])
              for st in streams]
    masks = [ref.visible(stream, pos, n) for _ids, pos, stream in arrays]
    hs = {m: [ref.embed(top, ids) for ids, _pos, _st in arrays]
          for m in passes}
    del top
    for i in range(c.layers):
        w = ref.layer_leaves(
            reference_params(config, seed, rounded, only=f"layer{i}_"), i)
        hs = {m: [ref.layer(h, w, c, pos, mask, m)
                  for h, (_ids, pos, _st), mask in zip(hs[m], arrays, masks)]
              for m in passes}
        del w
    top = reference_params(config, seed, rounded, only="final_norm")
    top.update(reference_params(config, seed, rounded, only="lm_head"))
    out = {"logit_gaps": [], "order_gaps": [], "compared": 0}
    for j, ((prompt, tokens, unmask), st) in enumerate(zip(requests,
                                                            streams)):
        fill, whole = st[3], st[4]
        final = np.zeros(steps * noised, np.int64)
        seq = np.array(list(prompt) + list(tokens), np.int64)
        for s in range(steps):
            final[s * noised:s * noised + whole - fill] = seq[fill:whole]
        lg = ref.head(hs["f32"][j][length:], top, c, "f32")
        own_conf = None
        if compute != "f32":
            lg_c = ref.head(hs[compute][j][length:], top, c, compute)
            final = np.asarray(jnp.argmax(lg_c, axis=-1))
            own_conf = ref.reduce_rows(lg_c, final)[1]
            del lg_c
        tops, conf, got = ref.reduce_rows(lg, final)
        del lg
        a, b, k = ref.trajectory_gaps(tops, conf, got, prompt, tokens,
                                      unmask, c, noised, own_conf)
        out["logit_gaps"].append(a)
        out["order_gaps"].append(b)
        out["compared"] += k
    return out


# ------------------------------------------------------------------ work
def model_flops(config, requests):
    """Model FLOPs of finished requests ``[(prompt_len, n_tokens,
    denoising_steps)]``."""
    s = sizes(config)
    return sum(work_sdar.request_flops(s, p, t, k) for p, t, k in requests)


kernel_work = work_sdar.kernel_work
block_attn_work = work_sdar.block_attn_work
