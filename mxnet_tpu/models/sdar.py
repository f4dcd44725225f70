"""The SDAR decoder family for the serving path: generation by
diffusion over blocks.

A third decoder family beside the GPT-2 block (``models/decode.py``) and
Ling (``models/ling.py``).  The block is Qwen3-MoE's: RMSNorm,
bias-free grouped-query attention (32 query heads on 4 K/V heads of
128) with a per-head RMSNorm on ``q`` and ``k`` and rotary positions
over the whole head (half-split form), then a softmax top-8 router over
128 SwiGLU experts (``parallel/moe.py:moe_serve`` with
``route_softmax_topk``), no shared expert, an untied head.  What sets
the family apart is how it generates (SDAR, arXiv:2510.06303; block
diffusion, arXiv:2503.09573): not a token a forward but a BLOCK of
``block_length`` tokens that starts as mask ids and is unmasked over
several forwards, every position of the block attending over the whole
block and everything before it.  The equations are at the head of
``benchmark/reference/sdar.py``, the plain float32 reference the tests
and the benchmark compare this file with.

One forward over a cache view (``serving/paged_kv.py``), two programs:

- the step: ``(B, n)`` tokens, a slot's block at positions ``cursor ..
  cursor + n - 1``.  The view writes the block's K/V rows (provisional
  until the scheduler commits the block) and every query of the block
  attends over ``[0, cursor + n)``; the head reduces ON THE DEVICE to
  the top-1 token and its softmax probability a row, ``(B, n)`` int32
  and float32 -- the full logits (slots x n x 151,936 float32) never
  leave the program.  The token at a position is predicted from the
  hidden state AT that position (no shift);
- an admission's prefill: the prompt's whole blocks of one sequence
  under the block-causal mask.  Nothing is sampled from it; it returns
  the last real position's hidden state (what a pipeline's next stage
  would be handed), which nobody fetches.

Which positions of a block are fixed, in which forward one is unmasked
and when a block is committed is the scheduler's
(``serving/scheduler.py``); the decoder only says ``block_length``,
``mask_id`` and the default ``denoising_steps``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..base import mxu_precision
from ..parallel.moe import moe_serve, route_softmax_topk
from .blocks import lin, rms_norm

__all__ = ["SdarConfig", "SdarDecoder", "rope_half"]


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    experts: int
    top_k: int
    eps: float
    layers: int
    block_length: int
    mask_id: int
    denoising_steps: int

    @classmethod
    def from_dict(cls, config):
        """From a ``config.json``-style dict: the published keys,
        ``num_layers`` (the layers held here of the published
        ``num_hidden_layers``), and under ``generation`` what the family's generation loop needs
        and the row does not give (``block_length``, ``mask_token_id``,
        the default ``denoising_steps``)."""
        gen = config["generation"]
        return cls(
            hidden=int(config["hidden_size"]),
            heads=int(config["num_attention_heads"]),
            kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            rope_theta=float(config["rope_theta"]),
            experts=int(config["num_experts"]),
            top_k=int(config["num_experts_per_tok"]),
            eps=float(config["rms_norm_eps"]),
            layers=int(config["num_layers"]),
            block_length=int(gen["block_length"]),
            mask_id=int(gen["mask_token_id"]),
            denoising_steps=int(gen["denoising_steps"]))


def rope_half(x, pos, theta):
    """Rotary positions over the whole last axis of ``x`` (N, H, d),
    float32, in the half-split (``rotate_half``) form: the pair
    ``(x[j], x[j + d/2])`` is turned by ``pos * theta^(-2j/d)``."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


class SdarDecoder:
    """Bound weights + the family's forward, for ``serve_decoder``.

    ``params``: a flat dict of leaves (``tok_embed_weight``,
    ``layer<i>_<x>``, ``final_norm_weight``, ``lm_head_weight``; shapes
    in ``benchmark/families/sdar.py:param_specs``), served in ``dtype``.
    Served paged only (``kv_block`` a multiple of ``block_length``),
    greedy only: the head hands the scheduler a top-1 token and its
    probability, so a temperature is refused at ``submit``."""

    family = "sdar"
    mesh = None

    def __init__(self, params, config, max_len, dtype=jnp.bfloat16):
        self.cfg = c = config if isinstance(config, SdarConfig) \
            else SdarConfig.from_dict(config)
        self.p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
        self.max_len = int(max_len)
        self.vocab = self.p["lm_head_weight"].shape[0]
        self.block_length, self.mask_id = c.block_length, c.mask_id
        self.denoising_steps = c.denoising_steps
        self._cache_dtype = jnp.dtype(dtype)
        if not 0 <= c.mask_id < self.vocab:
            raise ValueError(f"mask id {c.mask_id} outside the vocabulary "
                             f"of {self.vocab}")
        if c.heads % c.kv_heads:
            raise ValueError(f"{c.heads} query heads on {c.kv_heads} K/V "
                             "heads")

    def paged_layout(self):
        c = self.cfg
        return {"kv_pages": (c.layers, c.kv_heads, c.head_dim,
                             self._cache_dtype),
                "pages": {}, "state": {},
                # what the expert layers count (parallel/moe.py:moe_serve)
                "counters": ("expert_assignments_held",
                             "expert_assignments_absent",
                             "expert_distinct_hits",
                             "expert_kernel_calls"),
                # a page is whole blocks, and a block's K/V depend on
                # nothing behind it
                "prefix_reuse": True}

    def attn_block(self, p, i, x, view):
        """Grouped-query attention over ``x`` (N, D): ``S`` sequences of
        ``n`` positions (the slots' blocks, or one prompt).  A K/V
        head's ``G`` query heads go to the view side by side, ``(S,
        Hkv, G n, dh)``: they share its keys."""
        c = self.cfg
        N, H, Hkv, dh = x.shape[0], c.heads, c.kv_heads, c.head_dim
        pre = f"layer{i}_"
        pos = view.positions
        S = N // self.block_length if view.step else 1
        n, G = N // S, H // Hkv

        def heads(name, count):
            a = rms_norm(lin(x, p[pre + name + "_weight"])
                         .reshape(N, count, dh),
                         p[pre + name + "_norm_weight"], c.eps)
            return rope_half(a.astype(jnp.float32), pos,
                             c.rope_theta).astype(x.dtype)

        q = heads("q", H).reshape(S, n, Hkv, G, dh).transpose(
            0, 2, 3, 1, 4).reshape(S, Hkv, G * n, dh)
        k = heads("k", Hkv).reshape(S, n, Hkv, dh).transpose(0, 2, 1, 3)
        v = lin(x, p[pre + "v_weight"]).reshape(S, n, Hkv, dh).transpose(
            0, 2, 1, 3)
        o = view.attend(i, q, k, v)                    # (S, Hkv, G n, dh)
        o = o.reshape(S, Hkv, G, n, dh).transpose(0, 3, 1, 2, 4)
        return lin(o.reshape(N, H * dh), p[pre + "o_weight"])

    def forward(self, p, tokens, view):
        """``tokens`` at ``view.positions``: ``(B, n)`` in the step ->
        ``(token, prob)``, each ``(B, n)``: per row the most probable
        token and its softmax probability, float32; ``(T,)`` in a
        prefill -> the hidden state of the last real token, ``(D,)``."""
        c = self.cfg
        shape = tokens.shape
        h = jnp.take(p["tok_embed_weight"],
                     tokens.reshape(-1).astype(jnp.int32), axis=0)
        route = functools.partial(route_softmax_topk, top_k=c.top_k)
        for i in range(c.layers):
            pre = f"layer{i}_"
            with jax.named_scope(f"layer{i}"):
                x = rms_norm(h, p[pre + "norm1_weight"], c.eps)
                with jax.named_scope("attn"):
                    h = h + self.attn_block(p, i, x, view)
                x = rms_norm(h, p[pre + "norm2_weight"], c.eps)
                y, counts = moe_serve(
                    x, p[pre + "router_weight"], None,
                    p[pre + "experts_gate_weight"],
                    p[pre + "experts_up_weight"],
                    p[pre + "experts_down_weight"], expert_offset=0,
                    top_k=c.top_k, valid=view.valid, route=route)
                view.count(counts)
                h = h + y
        if not view.step:
            return jax.lax.dynamic_index_in_dim(h, view.length - 1,
                                                keepdims=False)
        with jax.named_scope("head"):
            return self.head(p, h, shape)

    def head(self, p, h, shape):
        """The step's head over ``h`` (N, D), reduced on the device:
        float32 logits, per row the top-1 token and its softmax
        probability, each reshaped to ``shape``."""
        x = rms_norm(h, p["final_norm_weight"], self.cfg.eps)
        w = p["lm_head_weight"]
        logits = jnp.dot(x, w.T, precision=mxu_precision(x, w),
                         preferred_element_type=jnp.float32)
        top = jnp.max(logits, axis=-1)
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        prob = 1.0 / jnp.sum(jnp.exp(logits - top[:, None]), axis=-1)
        return token.reshape(shape), prob.reshape(shape)
