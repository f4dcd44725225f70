"""Incremental (KV-cache) decoding for the transformer LM
(beyond-reference: the reference has no autoregressive serving story —
its RNN demos re-run full windows per token.  This is the standard
O(T) decode: prefill once, then one position per step against cached
K/V, everything jitted with static shapes).

Works straight off a `models.transformer.transformer_lm` checkpoint:
the decoder reads the SAME arg_params the training symbol binds
(tok_embed/pos_embed/layer{i}_*/final_ln/lm_head), re-expressing the
forward functionally so each step is one XLA program with
`lax.dynamic_update_slice` into a (L, B, H, max_len, dh) cache.
`tests/test_decode.py` pins step-by-step equivalence against the
symbol graph's full forward.

Beyond the shared-position API (`prefill`/`step`, every row at the same
``pos``), the decoder also exposes a **slot-pool API** for the serving
subsystem (`mxnet_tpu/serving/`): each batch row is an independent
*slot* with its own host-tracked ``(start, cursor)`` cache window, so
requests of different prompt lengths decode in ONE jitted step and
finished rows can be replaced mid-flight without touching the others —
see :meth:`KVDecoder.prefill_padded`, :meth:`KVDecoder.step_slots`, and
:meth:`KVDecoder.adopt_row`.  ``quantize="int8"`` stores the weights as
int8 + per-channel scales and dequantizes inside the compiled programs
(`serving/quantize.py`).
"""
import numpy as np

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _snap(x, dtype=np.int32):
    """Device copy of host-side slot state (tokens/start/cursor/block
    tables) that can never alias the caller's buffer.  The CPU PJRT
    backend zero-copy-aliases suitably aligned numpy arrays on
    ``jnp.asarray``, so the steady-state idiom of mutating the host
    array in place right after an async dispatch (``cursor[b] += 1``,
    ``bt[b, idx] = page``) races with the still-executing program and
    flips its inputs mid-flight — the source of the long-standing
    serving bitwise-parity flake."""
    return jnp.asarray(np.array(x, dtype, copy=True))


def _count_compiles(fn, kind):
    """Wrap a to-be-jitted callable so each trace (= each XLA compile)
    lands in ``executor_compile_total{kind=decode_*}`` — the serving
    tests assert this stays flat after warmup (zero per-tick recompiles).
    """
    import functools

    from .. import telemetry as _tm

    ctr = _tm.counter(
        "executor_compile_total",
        "graph traces handed to XLA: one per jit cache miss, including "
        "per-shape recompiles", labels=("kind",))

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        ctr.inc(kind=kind)
        return fn(*args, **kwargs)

    return wrapper


class _WeightProgram:
    """``jax.jit`` of one decoder program with the weights as its first
    ARGUMENT.  A closed-over array is baked into the program as a
    constant, which at lm-560m width is 1.1 GB in every program —
    minutes of compile each (measured against the v5e compiler) and a
    private copy of the weights in every executable.  ``fn(p, *args)``
    reads the weights from ``p``; every call hands the decoder's in.
    The jitted function carries ``name``, so the XLA module
    (``jit_<name>``) and its operations in a device trace say which
    program they belong to.  ``donate`` names the positions in ``args``
    (the weights are never among them) whose buffers the program takes
    over: the caller's arrays are deleted by the call and the outputs
    that alias them are written in place.  Callable and
    ``.lower()``-able like the jit it wraps; lowering consumes nothing."""

    def __init__(self, decoder, fn, name, donate=()):
        self._dec = decoder
        view = type(decoder.p)      # dict, or the dequantize-on-read view

        def program(weights, *args):
            return fn(view(weights), *args)

        program.__name__ = program.__qualname__ = name
        self._jit = jax.jit(
            program, donate_argnums=tuple(1 + i for i in donate))

    def __call__(self, *args):
        return self._jit(dict(self._dec.p), *args)

    def lower(self, *args):
        return self._jit.lower(dict(self._dec.p), *args)


class _DequantView(dict):
    """Param dict whose int8 entries dequantize on read.  Inside a jit
    trace the int8 array is the program's argument and the
    ``astype * scale`` fuses into the consumer (matmul/gather), so the
    device holds int8 storage while compute runs in the compute dtype."""

    def __getitem__(self, key):
        v = dict.__getitem__(self, key)
        deq = getattr(v, "dequantize", None)
        return deq() if deq is not None else v


def _logsumexp(x):
    m = x.max(-1, keepdims=True)
    return m + np.log(np.exp(x - m).sum(-1, keepdims=True))


def _ln(x, g, b, eps=1e-5):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * g + b


def _fc(x, w, b=None):
    y = x @ w.T
    return y if b is None else y + b


# ------------------------------------------------------- cache views
# What `KVDecoder.forward` sees of the contiguous (L, B, H, max_len, dh)
# cache.  A view says where its tokens stand (``embed``: the hidden
# state the blocks start from, token rows plus the positional table's at
# the view's positions, and the mask of what each query may see) and
# takes a layer's new K/V rows in before attending over the cache
# (``attend``).  serving/paged_kv.py holds the two views of the page
# pool, under the same two names.

class _PositionsView:
    """``n`` new positions from ``pos`` on, every row at the same ones
    (``prefill``, ``step``, the generate loops).  ``pos`` rides as a
    traced scalar; the HOST tracks the counter so no step ever fetches
    device state (a per-step sync would serialize the host behind every
    decode step)."""
    step, length = False, None

    def __init__(self, kc, vc, pos):
        self.kc, self.vc, self._pos = kc, vc, pos

    def embed(self, tok, table):
        _, n, D = tok.shape
        h = tok + jax.lax.dynamic_slice(table, (0, self._pos, 0), (1, n, D))
        # positions 0..max_len-1 valid iff <= pos + the query's offset
        span = self._pos + jnp.arange(n)                     # (n,)
        self._mask = jnp.arange(self.kc.shape[3])[None, :] <= span[:, None]
        return h

    def attend(self, i, qh, kh, vh):
        self.kc = jax.lax.dynamic_update_slice(
            self.kc, kh[None], (i, 0, 0, self._pos, 0))
        self.vc = jax.lax.dynamic_update_slice(
            self.vc, vh[None], (i, 0, 0, self._pos, 0))
        scores = jnp.einsum("bhnd,bhsd->bhns", qh, self.kc[i]) \
            / jnp.sqrt(jnp.asarray(qh.shape[-1], qh.dtype))
        scores = jnp.where(self._mask[None, None], scores, NEG_INF)
        att = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhns,bhsd->bhnd", att, self.vc[i])


class _SlotsView:
    """One decode position for EVERY slot at once, each row at its own
    cache position: row ``b`` writes its new K/V at ``cursor[b]`` and
    attends over ``[start[b], cursor[b]]`` with position embedding
    ``cursor[b] - start[b]``.  Rows whose slot is free still ride along
    (fixed batch keeps this ONE compiled program); their outputs are
    garbage the caller ignores and their writes land at position
    ``cursor[b]`` of a row :meth:`KVDecoder.adopt_row` fully overwrites
    on the next admission."""
    step, length = True, None

    def __init__(self, kc, vc, start, cursor):
        self.kc, self.vc, self._start, self._cursor = kc, vc, start, cursor

    def embed(self, tok, table):
        S = self.kc.shape[3]
        pos_ids = jnp.clip(self._cursor - self._start, 0, S - 1)
        h = (tok + jnp.take(table[0], pos_ids, axis=0))[:, None]
        s_idx = jnp.arange(S)
        self._valid = (s_idx[None, :] >= self._start[:, None]) & \
            (s_idx[None, :] <= self._cursor[:, None])        # (B, S)
        self._rows = jnp.arange(tok.shape[0])
        return h

    def attend(self, i, qh, kh, vh):
        at = (i, self._rows, slice(None), self._cursor)
        self.kc = self.kc.at[at].set(kh[:, :, 0])
        self.vc = self.vc.at[at].set(vh[:, :, 0])
        scores = jnp.einsum("bhnd,bhsd->bhns", qh, self.kc[i]) \
            / jnp.sqrt(jnp.asarray(qh.shape[-1], qh.dtype))
        scores = jnp.where(self._valid[:, None, None, :], scores, NEG_INF)
        att = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhns,bhsd->bhnd", att, self.vc[i])


class _PaddedView:
    """Left-padded prefill: row ``b``'s real prompt right-aligned in the
    last ``T - start[b]`` positions.  Real tokens write K/V at their
    padded index and attend over ``[start[b], n]``; pad queries
    (n < start) attend to themselves only -- finite garbage that every
    real query's window excludes.  Left-padding makes ``logits[:, -1]``
    the next-token logits of EVERY row regardless of its prompt
    length."""
    step, length = False, None

    def __init__(self, kc, vc, start):
        self.kc, self.vc, self._start = kc, vc, start

    def embed(self, tok, table):
        T, S, start = tok.shape[1], self.kc.shape[3], self._start
        pos_ids = jnp.clip(jnp.arange(T)[None, :] - start[:, None],
                           0, S - 1)                         # (B, T)
        h = tok + jnp.take(table[0], pos_ids, axis=0)
        n_idx = jnp.arange(T)
        s_idx = jnp.arange(S)
        lo = jnp.minimum(start[:, None], n_idx[None, :])     # (B, T)
        self._valid = (s_idx[None, None, :] <= n_idx[None, :, None]) & \
            (s_idx[None, None, :] >= lo[:, :, None])         # (B, T, S)
        return h

    def attend(self, i, qh, kh, vh):
        self.kc = jax.lax.dynamic_update_slice(
            self.kc, kh[None], (i, 0, 0, 0, 0))
        self.vc = jax.lax.dynamic_update_slice(
            self.vc, vh[None], (i, 0, 0, 0, 0))
        scores = jnp.einsum("bhnd,bhsd->bhns", qh, self.kc[i]) \
            / jnp.sqrt(jnp.asarray(qh.shape[-1], qh.dtype))
        scores = jnp.where(self._valid[:, None], scores, NEG_INF)
        att = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhns,bhsd->bhnd", att, self.vc[i])


class KVDecoder:
    """One instance per (checkpoint, batch, max_len) combination.

    state = (k_cache, v_cache, pos):
      k/v_cache (L, B, H, max_len, dh); pos int32 — tokens filled so far.
    """

    # the paged programs' names: jit_decode_step_paged,
    # jit_prefill_paged_b<bucket> (serving/paged_kv.py)
    family = "paged"

    def __init__(self, arg_params, num_layers, num_heads, max_len,
                 dtype=jnp.float32, mesh=None, model_axis="model",
                 quantize=None):
        """``mesh``: shard serving over devices, Megatron-style — q/k/v
        and ffn_in weights column-parallel, proj and ffn_out
        row-parallel, the K/V cache split on its HEAD axis — so each
        device holds 1/tp of the weights and cache and GSPMD inserts
        the one all-reduce per block the row-parallel products need
        (the serving mirror of parallel/mesh.megatron_rules)."""
        to = lambda a: jnp.asarray(
            a.asnumpy() if hasattr(a, "asnumpy") else a, dtype)
        p = {k: to(v) for k, v in arg_params.items()}
        self.mesh = mesh
        self.model_axis = model_axis
        if mesh is not None:
            from ..parallel.mesh import megatron_rules, shard_params

            tp = mesh.shape[model_axis]
            if num_heads % tp:
                raise ValueError(
                    f"num_heads {num_heads} must divide by the "
                    f"{model_axis!r} mesh axis ({tp})")
            for k, v in p.items():
                if k.endswith("_ffn_in_weight") and v.shape[0] % tp:
                    raise ValueError(
                        f"{k}: d_ff {v.shape[0]} must divide by the "
                        f"{model_axis!r} mesh axis ({tp})")
            # the training layout, minus the vocab-sharded head/embed
            # (decode keeps logits replicated — the sampler reads them
            # on the host every step)
            rules = tuple(r for r in megatron_rules(model_axis)
                          if "lm_head" not in r.pattern
                          and "tok_embed" not in r.pattern)
            p = shard_params(mesh, p, rules)
        self.L, self.H = num_layers, num_heads
        self.max_len = max_len
        self.d_model = p["tok_embed_weight"].shape[1]
        self.dh = self.d_model // num_heads
        self.vocab = p["lm_head_weight"].shape[0]
        self._cache_dtype = p["tok_embed_weight"].dtype
        if p["pos_embed"].shape[1] < max_len:
            raise ValueError(
                f"checkpoint pos table {p['pos_embed'].shape[1]} < "
                f"max_len {max_len}")
        if quantize not in (None, "int8"):
            raise ValueError(f"unknown quantize mode {quantize!r} "
                             "(supported: 'int8')")
        if quantize == "int8":
            if mesh is not None:
                raise ValueError(
                    "quantize='int8' is not supported together with a "
                    "tensor-parallel mesh (shard the fp weights instead)")
            from ..serving.quantize import quantize_params

            p = _DequantView(quantize_params(p, dtype=dtype))
        self.quantize = quantize
        self.p = p
        self._step_jit = _WeightProgram(
            self, self._positions, "decode_step")
        self._reorder_jit = jax.jit(
            lambda kc, vc, idx: (kc[:, idx], vc[:, idx]))
        self._prefill_cache = {}
        self._scan_cache = {}
        self._padded_prefill_cache = {}
        self._slot_step_jit = _WeightProgram(
            self, _count_compiles(
                lambda p, kc, vc, tokens, start, cursor: self._over(
                    _SlotsView(kc, vc, start, cursor), p, tokens),
                "decode_step"),
            "decode_step_slots")
        # perf plane (telemetry/perf.py): one analytical cost row per
        # compiled decode program, captured at first dispatch
        self._cost_step_done = False
        self._cost_prefill_done = set()
        self._adopt_jit = jax.jit(_count_compiles(
            lambda kc, vc, kr, vr, slot: (
                jax.lax.dynamic_update_slice(kc, kr, (0, slot, 0, 0, 0)),
                jax.lax.dynamic_update_slice(vc, vr, (0, slot, 0, 0, 0))),
            "decode_adopt"))

    def _cache_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        # (L, B, H, max_len, dh): split the head axis
        return NamedSharding(self.mesh, P(None, None, self.model_axis))

    def paged_layout(self):
        """What ``serving/paged_kv.py`` keeps for this decoder: K/V
        pages, which a cached prefix can share."""
        return {"kv_pages": (self.L, self.H, self.dh, self._cache_dtype),
                "pages": {}, "state": {}, "prefix_reuse": True}

    # ---------------------------------------------------------------- core
    def _block_qkv(self, p, i, h2):
        name = f"layer{i}"
        q = _fc(h2, p[f"{name}_q_weight"], p[f"{name}_q_bias"])
        k = _fc(h2, p[f"{name}_k_weight"], p[f"{name}_k_bias"])
        v = _fc(h2, p[f"{name}_v_weight"], p[f"{name}_v_bias"])
        return q, k, v

    def forward(self, p, tokens, view):
        """The GPT-2 forward, the only one served: ``tokens`` embedded at
        the view's positions, the blocks, the head.  What differs
        between the programs that call it -- where a token stands, where
        its K/V rows are written, what its query attends over -- is the
        cache view's (``embed``, ``attend``: the views below for the
        contiguous cache, ``serving/paged_kv.py``'s for the page pool).
        Logits ``(B, n, V)``; ``(B, V)`` from a view of one position a
        row (``view.step``), ``(V,)``, the last real token's row, from
        a paged prefill (``view.length`` real tokens of one row)."""
        H, dh = self.H, self.dh
        if view.length is not None:     # one sequence, handed over flat
            tokens = tokens[None]
        tok = jnp.take(p["tok_embed_weight"], tokens.astype(jnp.int32),
                       axis=0)
        h = view.embed(tok, p["pos_embed"])                  # (B, n, D)
        B, n, D = h.shape
        for i in range(self.L):
            name = f"layer{i}"
            with jax.named_scope(name):
                h2 = _ln(h, p[f"{name}_ln1_gamma"], p[f"{name}_ln1_beta"])
                q, k, v = self._block_qkv(p, i, h2)
                sh = lambda a: a.reshape(B, n, H, dh).transpose(0, 2, 1, 3)
                ctx = view.attend(i, sh(q), sh(k), sh(v))    # (B, H, n, dh)
                ctx = ctx.transpose(0, 2, 1, 3).reshape(B, n, D)
                proj = _fc(ctx, p[f"{name}_proj_weight"],
                           p[f"{name}_proj_bias"])
                h = h + proj
                h2 = _ln(h, p[f"{name}_ln2_gamma"], p[f"{name}_ln2_beta"])
                f = _fc(h2, p[f"{name}_ffn_in_weight"],
                        p[f"{name}_ffn_in_bias"])
                f = jax.nn.gelu(f)
                f = _fc(f, p[f"{name}_ffn_out_weight"],
                        p[f"{name}_ffn_out_bias"])
                h = h + f
        h = _ln(h, p["final_ln_gamma"], p["final_ln_beta"])
        logits = _fc(h, p["lm_head_weight"], p["lm_head_bias"])
        if view.step:
            return logits[:, 0]
        return logits if view.length is None else logits[0, view.length - 1]

    def _over(self, view, p, tokens):
        """:meth:`forward` over a view of the contiguous cache ->
        ``((kc, vc), logits)``: the body of every program below."""
        logits = self.forward(p, tokens, view)
        return (view.kc, view.vc), logits

    def _positions(self, p, kc, vc, pos, tokens):
        return self._over(_PositionsView(kc, vc, pos), p, tokens)

    # ----------------------------------------------------------------- API
    def init_state(self, batch):
        """state = (k_cache, v_cache, pos) — pos is a HOST int."""
        shape = (self.L, batch, self.H, self.max_len, self.dh)
        dtype = self._cache_dtype
        if self.mesh is not None:
            # allocate SHARDED: each device holds 1/tp of the cache from
            # the start (a dense zeros + reshard would transiently put
            # the whole cache on one device)
            sh = self._cache_sharding()
            kc = jnp.zeros(shape, dtype, device=sh)
            vc = jnp.zeros(shape, dtype, device=sh)
        else:
            kc = jnp.zeros(shape, dtype)
            vc = jnp.zeros(shape, dtype)
        return (kc, vc, 0)

    def prefill(self, tokens):
        """tokens (B, T) -> (state, logits (B, T, V)); one compile per
        distinct prompt length."""
        tokens = jnp.asarray(tokens)
        B, T = tokens.shape
        if T > self.max_len:
            raise ValueError(f"prompt {T} > max_len {self.max_len}")
        if T not in self._prefill_cache:
            self._prefill_cache[T] = _WeightProgram(
                self, self._positions, f"decode_prefill_t{T}")
        kc, vc, pos = self.init_state(B)
        (kc, vc), logits = self._prefill_cache[T](kc, vc, pos, tokens)
        return (kc, vc, pos + T), logits

    def step(self, state, token):
        """token (B,) -> (state, logits (B, V)) — ONE fused XLA program
        per call, O(max_len) attention, no host-device sync."""
        kc, vc, pos = state
        if pos >= self.max_len:
            raise ValueError(
                f"cache full: {self.max_len} positions decoded (the "
                "checkpoint's positional table ends there)")
        (kc, vc), logits = self._step_jit(
            kc, vc, pos, jnp.asarray(token).reshape(-1, 1))
        return (kc, vc, pos + 1), logits[:, 0]

    # ------------------------------------------------- slot-pool API
    # (continuous batching, mxnet_tpu/serving/): each batch row is an
    # independent request slot whose cache window [start, cursor] the
    # CALLER tracks as host int arrays — no step reads device state, so
    # the scheduler's bookkeeping costs zero syncs, exactly like the
    # shared-pos API's host counter.  serving/paged_kv.py runs the same
    # forward over its views of a shared page pool — equal to this path
    # to the last bits of a sum taken in another order, test-pinned.

    def init_slot_state(self, num_slots):
        """Empty slot-pool cache ``(k_cache, v_cache)`` for ``num_slots``
        slots; the per-slot ``start``/``cursor`` windows live with the
        caller (host int arrays)."""
        kc, vc, _ = self.init_state(num_slots)
        return kc, vc

    def prefill_padded(self, tokens, lengths):
        """Variable-length co-batched prefill.  ``tokens`` (B, T)
        LEFT-padded, ``lengths`` (B,) real prompt lengths (0 < len <= T).
        Returns ``((kc, vc), logits)`` with logits (B, T, V);
        ``logits[:, -1]`` is every row's next-token distribution.  The
        caller's slot windows are ``start = T - lengths``, ``cursor = T``.
        One compile per distinct padded length T (bucket prompt lengths
        to bound the program count)."""
        tokens = jnp.asarray(tokens)
        B, T = tokens.shape
        lengths = np.asarray(lengths, np.int64)
        if T > self.max_len:
            raise ValueError(f"padded prompt {T} > max_len {self.max_len}")
        if lengths.shape != (B,) or (lengths <= 0).any() \
                or (lengths > T).any():
            raise ValueError(
                f"lengths must be (B,) in [1, {T}], got {lengths!r}")
        if T not in self._padded_prefill_cache:
            self._padded_prefill_cache[T] = _WeightProgram(
                self,
                _count_compiles(
                    lambda p, kc, vc, tokens, start: self._over(
                        _PaddedView(kc, vc, start), p, tokens),
                    "decode_prefill"),
                f"decode_prefill_padded_t{T}")
        kc, vc, _ = self.init_state(B)
        start = (T - lengths).astype(np.int32)
        (kc, vc), logits = self._padded_prefill_cache[T](
            kc, vc, tokens, jnp.asarray(start))
        if T not in self._cost_prefill_done:
            from .. import telemetry as _tm

            if _tm.perf.enabled():
                self._cost_prefill_done.add(T)
                _tm.perf.attach_cost_analysis(
                    f"decode_prefill[b{T}]",
                    self._padded_prefill_cache[T],
                    kc, vc, tokens, jnp.asarray(start))
        return (kc, vc), logits

    def step_slots(self, cache, tokens, start, cursor):
        """One decode tick over the whole slot pool: (B,) next tokens in,
        ``((kc, vc), logits (B, V))`` out.  ``start``/``cursor`` are the
        host-tracked per-slot cache windows; the caller advances
        ``cursor[b] += 1`` for every row it actually consumed and MUST
        keep ``cursor < max_len`` (finish the request when its window is
        full).  ONE fused XLA program regardless of which slots are
        live."""
        kc, vc = cache
        cursor = np.asarray(cursor)
        if (cursor >= self.max_len).any():
            raise ValueError(
                f"slot cursor at max_len {self.max_len}: finish or evict "
                "the request before ticking it")
        (kc, vc), logits = self._slot_step_jit(
            kc, vc, _snap(tokens), _snap(start), _snap(cursor))
        if not self._cost_step_done:
            from .. import telemetry as _tm

            if _tm.perf.enabled():
                self._cost_step_done = True
                _tm.perf.attach_cost_analysis(
                    "decode_step_slots", self._slot_step_jit,
                    kc, vc, _snap(tokens), _snap(start), _snap(cursor))
        return (kc, vc), logits

    def adopt_row(self, cache, row_cache, slot):
        """Copy a freshly prefilled batch-1 cache (from
        :meth:`prefill_padded` at B=1) into slot ``slot`` of the pool —
        the admission write of the continuous-batching scheduler.  The
        slot index rides as a traced scalar, so every admission reuses
        ONE compiled program."""
        kc, vc = cache
        kr, vr = row_cache
        if kr.shape[1] != 1:
            raise ValueError(f"row cache must be batch-1, got {kr.shape}")
        kc, vc = self._adopt_jit(kc, vc, kr, vr, jnp.int32(slot))
        return kc, vc

    def _check_generation_budget(self, prompt, n_tokens):
        """Shared generate()/generate_scan() prologue: normalized prompt
        plus the empty-result short-circuit (None when real work remains)."""
        prompt = np.asarray(prompt)
        total = prompt.shape[1] + n_tokens
        if total > self.max_len:
            raise ValueError(
                f"prompt+n_tokens = {total} exceeds max_len "
                f"{self.max_len} (the checkpoint's positional table)")
        empty = (np.zeros((prompt.shape[0], 0), np.int64)
                 if n_tokens <= 0 else None)
        return prompt, empty

    def generate(self, prompt, n_tokens, temperature=1.0, top_k=None,
                 rng=None):
        """Greedy/temperature sampling loop; returns (B, n_tokens)."""
        rng = rng or np.random.RandomState(0)
        prompt, empty = self._check_generation_budget(prompt, n_tokens)
        if empty is not None:
            return empty
        state, logits = self.prefill(prompt)
        last = logits[:, -1]
        out = []
        for i in range(n_tokens):
            lg = np.asarray(last, np.float32)
            if temperature <= 0:
                nxt = lg.argmax(-1)
            else:
                lg = lg / temperature
                if top_k:
                    kth = np.partition(lg, -top_k, axis=-1)[:, -top_k, None]
                    lg = np.where(lg < kth, -np.inf, lg)
                z = lg - lg.max(-1, keepdims=True)
                prob = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
                nxt = np.array([rng.choice(lg.shape[-1], p=p_)
                                for p_ in prob])
            out.append(nxt)
            if i + 1 < n_tokens:  # the last sampled token needs no step
                state, last = self.step(state, nxt)
        return np.stack(out, axis=1)

    def generate_scan(self, prompt, n_tokens, temperature=0.0,
                      top_k=None, seed=0, eos_id=None):
        """generate(), but the WHOLE autoregressive loop is one compiled
        lax.scan — one dispatch for n_tokens steps instead of one per
        token: it removes n-1 dispatches (how much of a decode step
        that is on a local chip is ROADMAP S2's to measure).  Greedy
        when temperature<=0,
        otherwise categorical sampling (jax.random, seeded) with
        optional static top_k.  Token-for-token equal to generate() in
        greedy mode (pinned by tests/test_decode.py).

        With ``eos_id``, rows that emit it are eos-padded from then on
        (beam_search's convention) and the loop becomes a
        lax.while_loop that EXITS as soon as every row has finished —
        early stopping happens on device, still within the single
        dispatch."""
        prompt, empty = self._check_generation_budget(prompt, n_tokens)
        if empty is not None:
            return empty
        state, logits = self.prefill(prompt)
        kc, vc, pos = state
        key = (prompt.shape[0], n_tokens, float(temperature),
               top_k or 0, eos_id if eos_id is not None else -1)
        fn = self._scan_cache.get(key)
        if fn is None:
            greedy = temperature <= 0

            def pick(lg, k_):
                if top_k:
                    kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
                    lg = jnp.where(lg < kth, NEG_INF, lg)
                if greedy:
                    return jnp.argmax(lg, axis=-1)
                return jax.random.categorical(k_, lg / temperature)

            def step_once(p, kc, vc, pos, tok, k_):
                """ONE decode position + next-token pick — shared by the
                scan and while_loop bodies so they cannot diverge."""
                (kc, vc), lg = self._positions(
                    p, kc, vc, pos, tok[:, None])
                k_, sub = jax.random.split(k_)
                return kc, vc, pick(lg[:, 0], sub), k_

            def loop(p, kc, vc, pos0, last_logits, rng_key):
                k0, krest = jax.random.split(rng_key)
                first = pick(last_logits, k0)

                def body(carry, i):
                    kc, vc, tok, k_ = carry
                    kc, vc, nxt, k_ = step_once(p, kc, vc, pos0 + i, tok, k_)
                    return (kc, vc, nxt, k_), nxt

                (kc, vc, _, _), rest = jax.lax.scan(
                    body, (kc, vc, first, krest),
                    jnp.arange(n_tokens - 1, dtype=jnp.int32))
                toks = jnp.concatenate(
                    [first[:, None], rest.transpose(1, 0)], axis=1)
                return kc, vc, toks

            def loop_eos(p, kc, vc, pos0, last_logits, rng_key):
                B = last_logits.shape[0]
                k0, krest = jax.random.split(rng_key)
                first = pick(last_logits, k0)
                done0 = first == eos_id
                buf = jnp.full((n_tokens, B), eos_id, jnp.int32)
                buf = buf.at[0].set(first.astype(jnp.int32))

                def cond(carry):
                    i, kc, vc, tok, k_, done, buf = carry
                    return jnp.logical_and(i < n_tokens - 1,
                                           jnp.logical_not(done.all()))

                def body(carry):
                    i, kc, vc, tok, k_, done, buf = carry
                    kc, vc, nxt, k_ = step_once(p, kc, vc, pos0 + i, tok, k_)
                    nxt = jnp.where(done, eos_id, nxt)  # freeze finished
                    done = jnp.logical_or(done, nxt == eos_id)
                    buf = buf.at[i + 1].set(nxt.astype(jnp.int32))
                    return (i + 1, kc, vc, nxt, k_, done, buf)

                (_, kc, vc, _, _, _, buf) = jax.lax.while_loop(
                    cond, body,
                    (jnp.int32(0), kc, vc, first, krest, done0, buf))
                return kc, vc, buf.transpose(1, 0)

            fn = _WeightProgram(
                self, loop if eos_id is None else loop_eos,
                "decode_generate_loop")
            self._scan_cache[key] = fn
        kc, vc, toks = fn(kc, vc, jnp.int32(pos),
                          logits[:, -1].astype(jnp.float32),
                          jax.random.PRNGKey(seed))
        return np.asarray(toks, np.int64)

    def beam_search(self, prompt, n_tokens, beam_size=4,
                    length_penalty=0.0, eos_id=None):
        """Beam decode: returns (tokens (B, beam, n_tokens),
        scores (B, beam)) sorted best-first per batch row.

        With ``eos_id`` set, beams that emit it stop accumulating score
        (further positions are eos-padded) and ``length_penalty``
        normalizes each beam's score by its OWN length^penalty — the
        standard way longer unfinished beams compete with short
        finished ones.  Without an eos, every beam has equal length and
        the penalty only rescales scores.

        The cache runs at batch B*beam from the start (prompt rows
        replicated); beam reordering is a jitted row-gather on the
        device cache, the bookkeeping (log-probs, back-pointers) stays
        host-side like the sampling loop."""
        prompt = np.asarray(prompt)
        B, T = prompt.shape
        if T + n_tokens > self.max_len:
            raise ValueError(
                f"prompt+n_tokens = {T + n_tokens} exceeds max_len "
                f"{self.max_len}")
        if beam_size > self.vocab:
            raise ValueError(
                f"beam_size {beam_size} > vocab {self.vocab}")
        if n_tokens <= 0:
            return (np.zeros((B, beam_size, 0), np.int64),
                    np.zeros((B, beam_size), np.float32))
        K = beam_size

        def topk(mat, k):
            part = np.argpartition(-mat, k - 1, axis=-1)[:, :k]
            vals = np.take_along_axis(mat, part, axis=-1)
            order = np.argsort(-vals, axis=-1)
            return np.take_along_axis(part, order, axis=-1)

        state, logits = self.prefill(np.repeat(prompt, K, axis=0))
        last = np.asarray(logits[:, -1], np.float32)     # (B*K, V)
        V = last.shape[-1]
        logp = last - _logsumexp(last)
        # first expansion: distinct top-K continuations per batch row
        first = logp.reshape(B, K, V)[:, 0]              # replicas identical
        top = topk(first, K)                             # (B, K)
        scores = np.take_along_axis(first, top, axis=-1)  # (B, K)
        seqs = top[:, :, None]                           # (B, K, 1)
        finished = (top == eos_id) if eos_id is not None \
            else np.zeros((B, K), bool)
        lengths = np.ones((B, K), np.int64)
        nxt = top.reshape(-1)
        for i in range(1, n_tokens):
            if finished.all():
                pad = np.full((B, K, n_tokens - i), eos_id, np.int64)
                seqs = np.concatenate([seqs, pad], axis=2)
                break
            state, lg = self.step(state, nxt)
            logp = np.asarray(lg, np.float32)
            logp = (logp - _logsumexp(logp)).reshape(B, K, V)
            cand = scores[:, :, None] + logp             # (B, K, V)
            if eos_id is not None:
                # a finished beam contributes exactly one candidate:
                # itself, eos-padded, score frozen
                cand[finished] = NEG_INF
                cand[finished, eos_id] = scores[finished]
            flat = cand.reshape(B, K * V)
            top = topk(flat, K)                          # (B, K)
            beam_idx, tok = top // V, top % V
            scores = np.take_along_axis(flat, top, axis=-1)
            seqs = np.concatenate(
                [np.take_along_axis(seqs, beam_idx[:, :, None], axis=1),
                 tok[:, :, None]], axis=2)
            parent_fin = np.take_along_axis(finished, beam_idx, axis=-1)
            lengths = np.take_along_axis(lengths, beam_idx, axis=-1) \
                + (~parent_fin)
            if eos_id is not None:
                finished = parent_fin | (tok == eos_id)
            nxt = tok.reshape(-1)
            if i + 1 < n_tokens and not finished.all():
                # follow the survivors on the device cache (skipped on
                # the last step — nothing consumes it)
                rows = (np.arange(B)[:, None] * K + beam_idx).reshape(-1)
                kc, vc, pos = state
                kc, vc = self._reorder_jit(kc, vc, jnp.asarray(rows))
                state = (kc, vc, pos)
        if length_penalty:
            scores = scores / (lengths.astype(np.float32)
                               ** length_penalty)
        order = np.argsort(-scores, axis=-1)
        return (np.take_along_axis(seqs, order[:, :, None], axis=1),
                np.take_along_axis(scores, order, axis=-1))
