"""Decoder-only Transformer language model (beyond-reference: the
reference's sequence story tops out at bucketed LSTMs, SURVEY.md §5.7).

Built from the same symbol API as every other model-zoo entry, with the
long-context pieces this framework treats as first-class: causal
FlashAttention (Pallas kernel, ops/flash_attention.py) inside the block,
LayerNorm/gelu (ops/nn.py), and — for sequence lengths beyond one chip —
the same attention math is available sharded over a mesh via
parallel/ring_attention.py.

`transformer_lm(...)` returns the training symbol; pair it with
FusedTrainer for the fused train step (examples/transformer-lm/).
"""
from .. import symbol as sym


def _attention_block(h, seq_len, d_model, num_heads, name):
    dh = d_model // num_heads
    ln = sym.LayerNorm(h, name=f"{name}_ln1")
    x2 = sym.Reshape(ln, shape=(-1, d_model))

    # separate q/k/v projections (not one fused 3*d_model FC): under
    # Megatron TP each (d_model, d_model) weight row-shards cleanly on
    # the 'model' axis, whereas a fused qkv shard boundary would cut
    # through the packed q|k|v layout and force GSPMD to re-gather the
    # activation before the head split (parallel/mesh.py megatron_rules)
    def heads(proj_name):
        p = sym.FullyConnected(x2, num_hidden=d_model, name=proj_name)
        p = sym.Reshape(p, shape=(-1, seq_len, num_heads, dh))
        return sym.transpose(p, axes=(0, 2, 1, 3))  # (N, H, T, Dh)

    att = sym.FlashAttention(heads(f"{name}_q"), heads(f"{name}_k"),
                             heads(f"{name}_v"),
                             causal=True, name=f"{name}_attn")
    att = sym.transpose(att, axes=(0, 2, 1, 3))
    att = sym.Reshape(att, shape=(-1, d_model))
    proj = sym.FullyConnected(att, num_hidden=d_model, name=f"{name}_proj")
    proj = sym.Reshape(proj, shape=(-1, seq_len, d_model))
    return h + proj


def _ffn_block(h, seq_len, d_model, d_ff, name, dropout):
    ln = sym.LayerNorm(h, name=f"{name}_ln2")
    x2 = sym.Reshape(ln, shape=(-1, d_model))
    f = sym.FullyConnected(x2, num_hidden=d_ff, name=f"{name}_ffn_in")
    f = sym.Activation(f, act_type="gelu")
    if dropout > 0:
        f = sym.Dropout(f, p=dropout)
    f = sym.FullyConnected(f, num_hidden=d_model, name=f"{name}_ffn_out")
    f = sym.Reshape(f, shape=(-1, seq_len, d_model))
    return h + f


def transformer_lm(num_layers=4, num_heads=4, d_model=128, d_ff=None,
                   seq_len=128, vocab_size=1000, dropout=0.0,
                   ignore_label=None, max_len=None):
    """Next-token LM: data (N, T) token ids, softmax_label (N, T).

    ignore_label masks padding out of the loss/gradient, and max_len
    sizes the positional table independently of this bucket's seq_len —
    together they make the symbol bucketing-ready (BucketingModule
    shares one pos_embed across all sequence-length buckets)."""
    if d_model % num_heads:
        raise ValueError("d_model must divide by num_heads")
    d_ff = d_ff or 4 * d_model
    max_len = max_len or seq_len
    if max_len < seq_len:
        raise ValueError("max_len must be >= seq_len")
    data = sym.Variable("data")
    tok = sym.Embedding(data, input_dim=vocab_size, output_dim=d_model,
                        name="tok_embed")
    pos = sym.Variable("pos_embed", shape=(1, max_len, d_model))
    if max_len != seq_len:
        pos = sym.slice_axis(pos, axis=1, begin=0, end=seq_len)
    h = sym.broadcast_add(tok, pos)
    for i in range(num_layers):
        h = _attention_block(h, seq_len, d_model, num_heads, f"layer{i}")
        h = _ffn_block(h, seq_len, d_model, d_ff, f"layer{i}", dropout)
    h = sym.LayerNorm(h, name="final_ln")
    h = sym.Reshape(h, shape=(-1, d_model))
    logits = sym.FullyConnected(h, num_hidden=vocab_size, name="lm_head")
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    loss_kw = {}
    if ignore_label is not None:
        loss_kw = {"use_ignore": True, "ignore_label": ignore_label,
                   "normalization": "valid"}
    return sym.SoftmaxOutput(logits, label, name="softmax", **loss_kw)


def get_symbol(num_classes=1000, **kwargs):
    kwargs.setdefault("vocab_size", num_classes)
    return transformer_lm(**kwargs)


# ---------------------------------------------------------------------------
# MFU accounting — the ONE definition bench.py and tools/probe_lm_mfu.py
# share, so the bench extra and the probe sweep can never desynchronize.
# ---------------------------------------------------------------------------

# the compute-bound headline config (~540M params with the untied head;
# 613 M at the benchmark's 50257-token vocabulary): big enough matmuls to
# feed the MXU, small enough that Adam state + activations fit one v5e
# chosen by an on-silicon sweep taken before PR 1 (capture deleted in
# PR 21; not measured this round): the d2048 8-layer config more than doubles the d1024 12-layer's MFU
# (0.47-0.53 vs 0.24 at b8 on v5e) — wider matmuls feed the MXU better
# than more layers at the same parameter budget
MFU_HEADLINE_CONFIG = dict(num_layers=8, num_heads=16, d_model=2048,
                           d_ff=8192, seq_len=1024, vocab_size=32768)


def lm_train_flops_per_token(num_layers, d_model, d_ff, seq_len,
                             vocab_size):
    """Model-FLOP cost of ONE training token, conservative accounting:
    6 * matmul-params (qkv/proj, ffn, head; embedding gathers are free)
    plus causal-halved flash attention (6*L*T*D — the Pallas kernel
    skips fully-masked key blocks, ops/flash_attention.py)."""
    n_mat = (num_layers * (4 * d_model * d_model + 2 * d_model * d_ff)
             + d_model * vocab_size)
    return 6 * n_mat + 6 * num_layers * seq_len * d_model
