"""Operations and bytes the algorithms need, from shapes alone.

Every count here is *model* work: what the mathematics of one call
requires, not what a particular program happens to execute (padding,
recomputation and masked-out blocks do not count).  2 FLOPs per
multiply-add everywhere.  The per-layer metrics divide these by measured
time; nothing here measures anything.
"""
import math


# ------------------------------------------------------------ GPT-2 family
def gpt2_matmul_params(n_layer, n_embd, n_inner, vocab_size):
    """Parameters that sit in a matrix multiplication of the forward
    pass: q/k/v/proj (4 D^2) and the two feed-forward matrices per
    layer, and the output head.  Embedding lookups are gathers."""
    return (n_layer * (4 * n_embd * n_embd + 2 * n_embd * n_inner)
            + n_embd * vocab_size)


def gpt2_param_count(n_layer, n_embd, n_inner, vocab_size, n_positions,
                     tied_head=False):
    """All parameters as this repo lays the model out (biases on every
    projection, two LayerNorms a block, a final LayerNorm, learned
    positions, and a head of its own unless ``tied_head``)."""
    per_layer = (4 * (n_embd * n_embd + n_embd)
                 + n_embd * n_inner + n_inner + n_inner * n_embd + n_embd
                 + 4 * n_embd)
    head = 0 if tied_head else n_embd * vocab_size + vocab_size
    return (n_layer * per_layer + vocab_size * n_embd
            + n_positions * n_embd + 2 * n_embd + head)


def gpt2_train_flops_per_token(n_layer, n_embd, n_inner, vocab_size,
                               seq_len):
    """Forward + backward of one training token: 6 x matmul parameters,
    plus causal attention (QK^T and PV are 4 T D a token forward, x3
    for training, halved by the causal mask: 6 L T D)."""
    return (6 * gpt2_matmul_params(n_layer, n_embd, n_inner, vocab_size)
            + 6 * n_layer * seq_len * n_embd)


def gpt2_decode_flops(n_layer, n_embd, n_inner, vocab_size, context):
    """One decoded token whose attention covers ``context`` positions."""
    return (2 * gpt2_matmul_params(n_layer, n_embd, n_inner, vocab_size)
            + 4 * n_layer * context * n_embd)


def gpt2_prefill_flops(n_layer, n_embd, n_inner, vocab_size, prompt):
    """A prompt of ``prompt`` real tokens: every block over every token,
    causal attention, and the head once (only the last position's logits
    are sampled from)."""
    body = gpt2_matmul_params(n_layer, n_embd, n_inner, 0)
    return (2 * body * prompt + 2 * n_embd * vocab_size
            + 2 * n_layer * n_embd * prompt * prompt)


def flash_attention_train_work(batch, heads, seq_len, head_dim,
                               dtype_bytes=2):
    """Causal flash attention of ONE layer, forward and backward.

    Forward: QK^T and PV (4 T^2 dh a head).  Backward, as the algorithm
    needs it with the scores not stored: recompute S, dV, dP, dQ, dK
    (10 T^2 dh).  Halved by the causal mask.  Bytes: forward reads q, k,
    v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv
    (the row statistics are T floats a head and are left out)."""
    t2 = batch * heads * seq_len * seq_len * head_dim
    flops = (4 + 10) * t2 / 2
    tensor = batch * heads * seq_len * head_dim * dtype_bytes
    return {"flops": float(flops), "bytes": float(12 * tensor)}


def paged_attention_step_work(contexts, heads, head_dim, block,
                              dtype_bytes=2):
    """Decode attention of ONE layer for one tick: ``contexts`` holds,
    for each occupied slot, the positions its query attends over
    (cursor + 1).  Bytes are the live K and V pages only, whole pages
    as the kernel has to fetch them, never the pool; q and the output
    are one row a head."""
    flops = 0.0
    nbytes = 0.0
    for ctx in contexts:
        pages = math.ceil(ctx / block)
        nbytes += 2 * pages * block * head_dim * dtype_bytes * heads
        nbytes += 2 * heads * head_dim * dtype_bytes
        flops += 4 * heads * ctx * head_dim
    return {"flops": flops, "bytes": nbytes}


# ------------------------------------------------------------ ResNet family
def resnet_forward_macs(units, filters, image, classes, bottleneck=True):
    """Multiply-adds of the convolutions and the classifier of a
    residual network laid out as He et al. (stem 7x7/2 + 3x3/2 max pool,
    four stages, the first unit of stages 2-4 at stride 2 on its 3x3
    convolution and its projection shortcut), for one ``image`` x
    ``image`` input.  Batch norm, ReLU and pooling are not counted."""
    if not bottleneck:
        raise ValueError("only bottleneck units are counted here")
    hw = (image + 2 * 3 - 7) // 2 + 1           # conv0 7x7 / 2, pad 3
    macs = hw * hw * filters[0] * 3 * 49
    hw = (hw + 2 - 3) // 2 + 1                  # max pool 3x3 / 2, pad 1
    cin = filters[0]
    for stage, n_units in enumerate(units):
        cout = filters[stage + 1]
        mid = cout // 4
        for unit in range(n_units):
            stride = 2 if (unit == 0 and stage > 0) else 1
            out_hw = (hw + 2 - 3) // stride + 1
            macs += hw * hw * cin * mid                  # 1x1
            macs += out_hw * out_hw * mid * mid * 9      # 3x3 (strided)
            macs += out_hw * out_hw * mid * cout         # 1x1
            if unit == 0:
                macs += out_hw * out_hw * cin * cout     # projection
            hw, cin = out_hw, cout
    return macs + cin * classes


def roofline_seconds(work, peak):
    """The least time the chip could take for ``work`` and which of the
    two limits sets it."""
    t_flops = work["flops"] / peak["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    if t_flops >= t_bytes:
        return t_flops, "compute"
    return t_bytes, "memory"
