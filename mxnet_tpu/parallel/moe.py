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
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


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
