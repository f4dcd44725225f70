"""Expert parallelism — mixture-of-experts FFN with all_to_all dispatch.

Absent from the reference (SURVEY §2.4 lists expert parallelism as a
gap); on TPU it is a first-class strategy: experts live on an 'expert'
mesh axis, tokens are routed by a learned gate, and two
`jax.lax.all_to_all` collectives carry each token to its expert's device
and back — the standard Switch-Transformer layout over ICI.

Design (top-k routing, dense dispatch; k=1 = Switch, k=2 = the
GShard/Mixtral configuration):
- tokens are sharded over the 'expert' axis ([tokens/world, d_model] per
  device),
- gate logits pick each token's top-k experts; tokens scatter into a
  [n_experts, capacity, d_model] buffer with capacity slots claimed
  choice-major — rank-0 picks never lose a slot to a runner-up — and
  over-capacity choices drop, like Switch,
- all_to_all swaps the expert axis with the device axis so each device
  holds ITS expert's tokens from every peer, runs the expert FFN as one
  batched matmul (MXU-friendly), and the inverse all_to_all + combine
  weights scatter results home.

Everything is differentiable: gates get gradients through the combine
weights, experts through their matmuls.

Two expert layers live here, and they differ in what they may lose:

- :func:`moe_ffn` -- the TRAINING layer above (``examples/`` only):
  softmax gate, a capacity factor, all_to_all over an ``expert`` mesh
  axis.  A choice beyond its expert's capacity is DROPPED.
- :func:`moe_serve` -- the SERVING layer (``models/ling.py``): it is
  told which experts it holds (``expert_offset`` and the leading axis
  of the expert weights), routes over ALL of the router's experts in
  float32 (``route``: :func:`route_group_limited` over sigmoid scores,
  or :func:`route_softmax_topk`), and computes its own experts'
  part of the result by a sort-and-segment grouped matmul
  (``ops/grouped_matmul.py``: a Pallas kernel for bf16 on a TPU,
  ``lax.ragged_dot`` otherwise) sized for the worst case.  It NEVER
  drops a token; what the experts held elsewhere would add is simply
  not in its result.  On one chip it runs without the exchange that
  would sum the shares.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import grouped_matmul as gmm

__all__ = ["init_moe_params", "moe_ffn", "route_group_limited",
           "route_softmax_topk", "moe_serve"]


def init_moe_params(rng, d_model, d_hidden, n_experts, scale=0.02):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "gate_w": jax.random.normal(k1, (d_model, n_experts)) * scale,
        "w_in": jax.random.normal(k2, (n_experts, d_model, d_hidden)) * scale,
        "w_out": jax.random.normal(k3, (n_experts, d_hidden, d_model)) * scale,
    }


def moe_ffn(params, x, mesh: Mesh, axis_name: str = "expert",
            capacity_factor: float = 1.25, activation=jax.nn.relu,
            top_k: int = 1):
    """Apply the expert-parallel FFN.

    x: [tokens, d_model] sharded over `axis_name` on dim 0.
    params: gate_w [d, E]; w_in [E, d, h] / w_out [E, h, d] sharded over
    `axis_name` on dim 0 (one expert slice per device; E == axis size).
    top_k: experts per token — 1 = Switch routing, 2 = the GShard/
    Mixtral configuration (each choice gets its own capacity slot; the
    outputs combine weighted by the renormalized gate probabilities).
    Returns (y [tokens, d_model], aux_loss) — aux_loss is the
    load-balancing loss, to be added to the task loss.
    """
    n_exp = mesh.shape[axis_name]
    if not 1 <= top_k <= n_exp:
        raise ValueError(f"top_k must be in [1, {n_exp}], got {top_k}")

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(None, None), P(axis_name, None, None),
                       P(axis_name, None, None), P(axis_name, None)),
             out_specs=(P(axis_name, None), P()),
             check_vma=False)
    def run(gate_w, w_in, w_out, xs):
        nt = xs.shape[0]  # local tokens
        cap = max(1, int(capacity_factor * top_k * nt / n_exp))
        logits = xs @ gate_w                      # [nt, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, top_k)  # [nt, k]
        if top_k == 1:
            gates = top_p  # Switch: the raw gate prob scales the output
        else:
            # GShard/Mixtral: renormalize over the chosen experts
            gates = top_p / jnp.maximum(
                jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

        # capacity slots are claimed choice-major (all rank-0 choices,
        # then rank-1, ...) so top-1 picks never lose a slot to a
        # runner-up choice; bookkeeping stays integer — in xs.dtype
        # (bf16) a cumsum over >256 same-expert tokens loses exactness
        # and two tokens silently share a slot
        disp = jnp.zeros((nt, n_exp, cap), xs.dtype)
        combine = jnp.zeros((nt, n_exp, cap), xs.dtype)
        counts = jnp.zeros((n_exp,), jnp.int32)
        for j in range(top_k):
            e_j = top_e[:, j]                                # [nt]
            onehot_i = jax.nn.one_hot(e_j, n_exp, dtype=jnp.int32)
            pos = (jnp.take_along_axis(
                jnp.cumsum(onehot_i, axis=0) - onehot_i,
                e_j[:, None], axis=1)[:, 0] + counts[e_j])
            keep = (pos < cap).astype(xs.dtype)
            sel = (jax.nn.one_hot(e_j, n_exp, dtype=xs.dtype)[:, :, None]
                   * jax.nn.one_hot(pos, cap, dtype=xs.dtype)[:, None, :]
                   * keep[:, None, None])
            disp = disp + sel
            combine = combine + sel * gates[:, j][:, None, None]
            counts = counts + jnp.sum(onehot_i, axis=0)
        buf = jnp.einsum("tec,td->ecd", disp, xs)  # [E, cap, d]

        # expert axis <-> device axis: after this, dim 0 indexes the PEER
        # device the tokens came from, and every row belongs to MY expert
        buf = jax.lax.all_to_all(buf, axis_name, 0, 0, tiled=False)
        # buf: [world, cap, d] for my expert
        w1, w2 = w_in[0], w_out[0]
        h = activation(jnp.einsum("wcd,dh->wch", buf, w1))
        y = jnp.einsum("wch,hd->wcd", h, w2)
        y = jax.lax.all_to_all(y, axis_name, 0, 0, tiled=False)  # home again

        # combine: weight by renormalized gate prob, scatter to tokens
        out = jnp.einsum("tec,ecd->td", combine, y)

        # load-balancing loss: E * sum_e f_e * P_e over rank-0 routing
        onehot0 = jax.nn.one_hot(top_e[:, 0], n_exp, dtype=jnp.float32)
        frac = jnp.mean(onehot0, axis=0)          # fraction routed per expert
        prob_mean = jnp.mean(probs.astype(jnp.float32), axis=0)
        aux = n_exp * jnp.sum(frac * prob_mean)
        aux = jax.lax.pmean(aux, axis_name)
        return out, aux.astype(xs.dtype)

    return run(params["gate_w"], params["w_in"], params["w_out"], x)


# ------------------------------------------------------------- serving
def route_group_limited(scores, bias, *, top_k, n_group, topk_group, scale):
    """DeepSeek-V3 style choice (``topk_method`` ``noaux_tc``) over
    ``scores`` (N, E) float32 in [0, 1].  The choice is made on ``scores
    + bias``: the experts lie in ``n_group`` groups, a group scores the
    sum of its two best, the ``topk_group`` best groups stay, the
    ``top_k`` best experts among them are chosen.  Their weights are
    ``scores`` WITHOUT the bias, normalised to sum 1, times ``scale``.
    Returns ``(idx (N, top_k) int32, weights (N, top_k) float32)``."""
    N, E = scores.shape
    sel = scores + bias
    best2 = jnp.sum(jax.lax.top_k(
        sel.reshape(N, n_group, E // n_group), 2)[0], -1)      # (N, G)
    _, kept = jax.lax.top_k(best2, topk_group)
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    masked = jnp.where(jnp.repeat(keep, E // n_group, axis=1), sel,
                       -jnp.inf)
    _, idx = jax.lax.top_k(masked, top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * scale
    return idx.astype(jnp.int32), w


def route_softmax_topk(logits, *, top_k):
    """Qwen3-MoE style choice over router ``logits`` (N, E) float32:
    softmax over ALL experts, the ``top_k`` most probable, their
    probabilities normalised to sum 1 (``norm_topk_prob``).  Returns
    ``(idx (N, top_k) int32, weights (N, top_k) float32)``."""
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return idx.astype(jnp.int32), w / jnp.sum(w, -1, keepdims=True)


def moe_serve(x, router_w, router_b, w_gate, w_up, w_down, *,
              expert_offset, top_k, n_group=None, topk_group=None,
              scale=None, valid=None, route=None):
    """This chip's share of a routed expert layer, dropless.

    ``x`` (N, D) tokens; ``router_w`` (E, D) and ``router_b`` (E,) over
    ALL ``E`` experts; ``w_gate``/``w_up`` (held, D, F) and ``w_down``
    (held, F, D) are experts ``[expert_offset, expert_offset + held)``.
    The router runs in float32 at ``highest`` precision: the 8th and 9th
    expert of a token can lie closer than bfloat16 resolves, and a
    flipped choice moves the result by a whole expert's part.  The
    token-expert pairs are sorted by expert (pairs on absent experts
    last), and a grouped matmul (``ops/grouped_matmul.py``) runs every
    held expert over exactly its rows; the buffers hold all ``N *
    top_k`` pairs, so nothing is ever dropped.  ``route`` maps the
    router's float32 logits (N, E) to ``(idx, weights)`` of ``top_k``
    experts a token (:func:`route_softmax_topk`); without it the choice
    is :func:`route_group_limited` over their sigmoid with
    ``router_b``, ``n_group``, ``topk_group`` and ``scale``.  Named
    scopes ``moe.route`` and ``moe.experts`` carry the two parts in a
    device trace.

    Returns ``(y (N, D), counts (4,) int32)``: ``y`` is the routed part
    of the held experts alone; ``counts`` = pairs that fell on held
    experts, pairs that fell on absent ones, distinct held experts hit
    -- over the rows ``valid`` (N,) marks (all rows when None) -- and 1
    if this call's products took the kernel, 0 for ``lax.ragged_dot``
    (a constant of the traced program)."""
    N, D = x.shape
    held = w_gate.shape[0]
    with jax.named_scope("moe.route"):
        logits = jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32).T,
            precision=jax.lax.Precision.HIGHEST)
        if route is None:
            idx, wts = route_group_limited(
                jax.nn.sigmoid(logits), router_b.astype(jnp.float32),
                top_k=top_k, n_group=n_group, topk_group=topk_group,
                scale=scale)
        else:
            idx, wts = route(logits)
        local = idx - expert_offset
        here = (local >= 0) & (local < held)
        # absent pairs carry the id ``held``: they sort last and belong
        # to no group
        flat = jnp.where(here, local, held).reshape(-1)          # (N k,)
        real = True if valid is None else valid[:, None]
        counted, absent = here & real, ~here & real
        hits = jnp.zeros(held + 1, jnp.int32).at[
            jnp.where(counted, local, held).reshape(-1)].add(1)[:held]
        counts = jnp.stack([jnp.sum(counted), jnp.sum(absent),
                            jnp.sum(hits > 0)]).astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.zeros(held + 1, jnp.int32).at[flat].add(1)[:held]
        xs = jnp.take(x, order // top_k, axis=0)                 # (N k, D)
        # the kernel for bf16 on a TPU, ``lax.ragged_dot`` otherwise:
        # decided here, at trace time, from shape, dtype and platform
        schedule = gmm.default_schedule(
            jax.default_backend(), N * top_k, D, w_gate.shape[2],
            jnp.result_type(xs, w_gate, w_up, w_down), n_rhs=2)
        grouped = partial(gmm.grouped_matmul, group_sizes=sizes,
                          schedule=schedule)
        h = grouped(xs, (w_gate, w_up))        # silu(xs gate) * (xs up)
        ys = grouped(h, w_down)                                  # (N k, D)
        # rows past the last group belong to absent experts: whatever
        # the grouped matmul left there is not part of the result
        w_sorted = jnp.where(here, wts, 0.0).reshape(-1)[order]
        ys = jnp.where((flat[order] < held)[:, None],
                       ys.astype(jnp.float32) * w_sorted[:, None], 0.0)
        back = jnp.argsort(order)
        y = jnp.sum(jnp.take(ys, back, axis=0).reshape(N, top_k, D), 1)
    took = jnp.asarray([schedule["impl"] == "pallas"], jnp.int32)
    return y.astype(x.dtype), jnp.concatenate([counts, took])
