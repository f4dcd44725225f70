"""The Kimi-K2 decoder family (the DeepSeek-V3 block, ``model_type``
``kimi_k2``) for the serving path.

A fourth decoder family: every layer is multi-head latent attention
(``models/mla.py``) with a LOW-RANK QUERY (``x W_qa`` -> RMSNorm ->
``W_qb``) and YaRN-scaled rotary frequencies whose ``mscale`` squared
rides on the softmax scale; the first ``first_k_dense_replace`` layers
have a dense SwiGLU, the rest the 384-wide sigmoid router with a
correction bias, the top 8 and one shared expert (``models/blocks.py:
moe_block``, ``parallel/moe.py:moe_serve``).  The equations are at the
head of ``benchmark/reference/kimi.py``, the plain float32 reference
the tests and the benchmark compare this file with.

One forward (:meth:`KimiDecoder.forward`) over ``N`` tokens and the
cache view ``serving/paged_kv.py`` hands it:

- the decode step: one token a slot; the view takes a latent row a
  slot and layer and attends over the slot's live pages in the absorbed
  form (``view.attend_pages``: the latent-attention kernel, or its
  gather lowering);
- a prefill: the padded *tail* of ONE sequence behind ``hist`` rows
  that are in the slot's pages already -- a cached prefix, or the
  earlier chunks of a prompt longer than the largest bucket.  The view
  writes the tail's rows a page at a time and hands back this layer's
  rows of the slot with ``hist``; the tail attends over ``[history |
  own rows]`` in the expanded form.

What the decoder declares (:meth:`KimiDecoder.paged_layout`):
``pages``: ``{"latent": (layers, 576)}`` and nothing else -- no per-slot
state, hence ``prefix_reuse`` True: a prefix of the cache can be picked
up at any page boundary.

The share of an expert-parallel deployment is Ling's: ``experts_held``
experts from ``expert_offset`` on live here and ``vocab`` rows of the
embedding and the head; the router keeps its full width.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .blocks import lin as _lin, moe_block, rms_norm, swiglu as _swiglu
from .mla import (mla_absorbed_paged, mla_expanded, rope as _rope,
                  yarn_freq, yarn_mscale)

__all__ = ["KimiConfig", "KimiDecoder"]


@dataclasses.dataclass(frozen=True)
class KimiConfig:
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    rope_theta: float
    yarn: tuple             # (factor, original, beta_fast, beta_slow)
    attn_mscale: float      # m(mscale_all_dim); its square scales scores
    experts: int
    experts_held: int
    expert_offset: int
    top_k: int
    n_group: int
    topk_group: int
    scale: float
    eps: float
    mlps: tuple

    @classmethod
    def from_dict(cls, config):
        """From a ``config.json``-style dict: the published keys, plus
        ``num_layers`` / ``layer_offset`` (layer ``i`` here is published
        layer ``i + layer_offset``, dense where that is below
        ``first_k_dense_replace``), ``n_routed_experts`` = experts held
        here with the router's width under ``published``, and
        ``expert_offset``."""
        n = int(config["num_layers"])
        off = int(config.get("layer_offset", 0))
        dense = int(config["first_k_dense_replace"])
        held = int(config["n_routed_experts"])
        y = config["rope_scaling"]
        # cos and sin would carry m(mscale) / m(mscale_all_dim); the
        # two are published equal, and nothing here multiplies by one
        if float(y["mscale"]) != float(y["mscale_all_dim"]):
            raise ValueError("rope_scaling: mscale != mscale_all_dim is "
                             "not implemented")
        return cls(
            hidden=int(config["hidden_size"]),
            heads=int(config["num_attention_heads"]),
            q_rank=int(config["q_lora_rank"]),
            kv_rank=int(config["kv_lora_rank"]),
            nope=int(config["qk_nope_head_dim"]),
            rope=int(config["qk_rope_head_dim"]),
            v_dim=int(config["v_head_dim"]),
            rope_theta=float(config["rope_theta"]),
            yarn=(float(y["factor"]),
                  int(y["original_max_position_embeddings"]),
                  float(y["beta_fast"]), float(y["beta_slow"])),
            attn_mscale=yarn_mscale(float(y["factor"]),
                                    float(y["mscale_all_dim"])),
            experts=int(config.get("published", {}).get(
                "n_routed_experts", held)),
            experts_held=held,
            expert_offset=int(config.get("expert_offset", 0)),
            top_k=int(config["num_experts_per_tok"]),
            n_group=int(config["n_group"]),
            topk_group=int(config["topk_group"]),
            scale=float(config["routed_scaling_factor"]),
            eps=float(config["rms_norm_eps"]),
            mlps=tuple("dense" if i + off < dense else "moe"
                       for i in range(n)))

    def rope_freq(self):
        factor, original, fast, slow = self.yarn
        return yarn_freq(self.rope_theta, self.rope, factor=factor,
                         original=original, beta_fast=fast, beta_slow=slow)


class KimiDecoder:
    """Bound weights + the family's forward, for ``serve_decoder``.

    ``params``: a flat dict of leaves (``tok_embed_weight``,
    ``layer<i>_<x>``, ``final_norm_weight``, ``lm_head_weight``; shapes in
    ``benchmark/families/kimi.py:param_specs``), served in ``dtype``; the
    router's correction bias stays float32.  There is no contiguous-cache
    form: serve it paged (``kv_block``)."""

    family = "kimi"
    mesh = None

    def __init__(self, params, config, max_len, dtype=jnp.bfloat16):
        self.cfg = config if isinstance(config, KimiConfig) \
            else KimiConfig.from_dict(config)
        self.p = {k: jnp.asarray(v, jnp.float32 if k.endswith("router_bias")
                                 else dtype) for k, v in params.items()}
        self.max_len = int(max_len)
        self.vocab = self.p["lm_head_weight"].shape[0]
        self._cache_dtype = jnp.dtype(dtype)
        held = self.cfg.experts_held
        for i, kind in enumerate(self.cfg.mlps):
            if kind == "moe" and \
                    self.p[f"layer{i}_experts_gate_weight"].shape[0] != held:
                raise ValueError(
                    f"layer {i} holds "
                    f"{self.p[f'layer{i}_experts_gate_weight'].shape[0]} "
                    f"experts, the configuration says {held}")

    def paged_layout(self):
        c = self.cfg
        return {"pages": {"latent": (len(c.mlps), c.kv_rank + c.rope,
                                     self._cache_dtype)},
                "state": {},
                # what moe_block counts (parallel/moe.py:moe_serve)
                "counters": ("expert_assignments_held",
                             "expert_assignments_absent",
                             "expert_distinct_hits",
                             "expert_kernel_calls"),
                "prefix_reuse": True}

    def mla_block(self, p, i, x, view):
        """MLA over ``x`` (N, D): the view takes each token's ``[latent
        | rotary key]`` row; in the step it attends for the absorbed
        form over each slot's pages, in a prefill it hands back this
        slot's history, which the queries attend over beside their own
        rows."""
        c = self.cfg
        N, H = x.shape[0], c.heads
        pre = f"layer{i}_mla_"
        freq = c.rope_freq()
        c_q = rms_norm(_lin(x, p[pre + "qa_weight"]),
                       p[pre + "q_norm_weight"], c.eps)
        q = _lin(c_q, p[pre + "qb_weight"]).reshape(N, H, c.nope + c.rope)
        q_nope = q[..., :c.nope]
        q_rope = _rope(q[..., c.nope:].astype(jnp.float32), view.positions,
                       freq).astype(x.dtype)
        kva = _lin(x, p[pre + "kva_weight"])
        lat = rms_norm(kva[:, :c.kv_rank], p[pre + "kv_norm_weight"], c.eps)
        k_rope = _rope(kva[:, c.kv_rank:].astype(jnp.float32),
                       view.positions, freq).astype(x.dtype)
        rows = jnp.concatenate([lat, k_rope], -1)              # (N, 576)
        if view.step:
            o = mla_absorbed_paged(q_nope, q_rope, rows, view, "latent", i,
                                   p[pre + "kvb_weight"], c)
        else:
            o = mla_expanded(q_nope, q_rope, rows, p[pre + "kvb_weight"], c,
                             history=view.append("latent", i, rows))
        return _lin(o, p[pre + "o_weight"])

    def forward(self, p, tokens, view):
        """``tokens`` (N,) at ``view.positions`` -> logits.  In the step
        (B, V), a row a slot; in a prefill (V,), the row of the last
        real token (``view.length - 1``)."""
        c = self.cfg
        h = jnp.take(p["tok_embed_weight"], tokens.astype(jnp.int32),
                     axis=0)
        for i, mlp in enumerate(c.mlps):
            with jax.named_scope(f"layer{i}"):
                x = rms_norm(h, p[f"layer{i}_norm1_weight"], c.eps)
                with jax.named_scope("mla_attn"):
                    h = h + self.mla_block(p, i, x, view)
                x = rms_norm(h, p[f"layer{i}_norm2_weight"], c.eps)
                if mlp == "dense":
                    with jax.named_scope("mlp.dense"):
                        h = h + _swiglu(
                            x, p[f"layer{i}_mlp_gate_weight"],
                            p[f"layer{i}_mlp_up_weight"],
                            p[f"layer{i}_mlp_down_weight"])
                else:
                    h = h + moe_block(p, f"layer{i}_", x, view, c)
        if not view.step:
            h = jax.lax.dynamic_slice_in_dim(h, view.length - 1, 1)
        with jax.named_scope("head"):
            h = rms_norm(h, p["final_norm_weight"], c.eps)
            logits = _lin(h, p["lm_head_weight"])
        return logits if view.step else logits[0]
