"""Driver ``serve_sessions_state``: ``serve_sessions_scoped`` for a
decoder with a recurrent state a slot, whose long prompts go in chunks
with the state carried from one to the next and which reuses no other
request's prefix (``families/olmo_hybrid.py``).

What differs from ``serve_sessions_scoped``:

- no prompt token is expected from shared pages (``_hist`` is 0): every
  ask prefills its whole document again, in chunks of the largest
  bucket;
- the scopes' work (``family.kernel_work``) needs no expert counter:
  after a traced window it is computed from the scheduler's ticks, the
  finished requests' positions and the prefill programs the window ran,
  each ``(hist, tokens)`` of an ``engine.prefill_chunk`` span of the
  program's span ring (every prompt of this traffic is longer than the
  largest bucket, so each of its programs is a chunk);
- the check's sample must also hold ``check_min_chunked`` requests of
  at least ``check_chunks`` chunks, else ``check_sample_short`` counts
  the shortfall and ``correct`` fails;
- the check reads one more number, ``chunk_edge_gap``: after the
  window, on the serving engine itself (its pool, programs and
  scheduler), each sampled prompt cut after each of its chunk edges is
  sent as a request of one token while the other slots decode, so that
  the engine ticks between its chunks as it does between a served
  prompt's (:meth:`Driver.chunk_edges`); the program's logits at the
  edge -- the first position of a chunk, which reads most of the
  carried state and convolution tail, where the served tokens sit up to
  2,048 positions behind the last edge -- are compared with the float32
  reference's there (``family.served(edges=...)``);
- the planted faults of the limits' readings (:func:`faults`) are in
  the state carried between chunks, in the step's state rows and in the
  delta rule's step size.
"""
import sys
import time

import numpy as np

from benchmark import harness, span_reduce

_scoped = harness.load_driver("serve_sessions_scoped")

# the prompt of each request that keeps a slot decoding while the chunk
# edges are read
KEEPER_PROMPT = 16


class Driver(_scoped.Driver):

    def free(self):
        """:meth:`chunk_edges` over the sample on the engine as it
        served the window, then the base class's."""
        for t in self._threads:         # the clients' last requests
            t.join(timeout=300)
        try:
            self.edges = self.chunk_edges(self.sample())
        finally:
            super().free()

    def chunk_edges(self, sample):
        """The program's logits at each chunk edge of each prompt of
        ``sample``: a request, ``{position: logits (V,)}`` for every
        multiple of the chunk inside the prompt, each the logits of a
        one-token request of the prompt up to and with that position.
        ``num_slots - 1`` requests of ``KEEPER_PROMPT`` tokens decode
        meanwhile, so each of those requests comes in as the served
        prompts do: one chunk, then a tick of the other slots, its slot
        a free row of the step until its last chunk.  They are queued
        as far as the queue takes them, so that the keepers' ticks
        between them are few.  Not timed: after the window."""
        sched = self.sched
        srv = self.traffic["server"]
        block = int(srv["kv_block"])
        chunk = max(srv["prefill_buckets"]) // block * block
        cuts = [(i, at) for i, (prompt, _t) in enumerate(sample)
                for at in range(chunk, len(prompt), chunk)]
        rows = {}
        real = sched._sample

        def sampled(req, logits):
            # the clients ask 16 tokens and more, the keepers more still
            if req.max_new_tokens == 1:
                rows[req.id] = np.array(logits, np.float32)
            return real(req, logits)

        vocab = self.family.sizes(self.config)["vocab_size"]
        rng = np.random.default_rng([self.seed, 17])
        ticks = sched.stats["ticks"]
        sched._sample = sampled
        try:
            keepers = [sched.submit(rng.integers(0, vocab, KEEPER_PROMPT),
                                    max_new_tokens=self.max_len
                                    - KEEPER_PROMPT, deadline_ms=None)
                       for _ in range(self.slots - 1)]
            t_end = time.perf_counter() + 300
            while sched.occupied < len(keepers) \
                    and time.perf_counter() < t_end:
                time.sleep(0.005)
            pending, live, got = list(cuts), [], {}
            while pending or live:
                while pending and len(live) < sched.queue_size:
                    i, at = pending.pop(0)
                    req = sched.submit(
                        np.asarray(sample[i][0][:at + 1], np.int64),
                        max_new_tokens=1, deadline_ms=None)
                    live.append((i, at, req))
                i, at, req = live.pop(0)
                req.wait(600)
                if req.outcome != "ok" or req.id not in rows:
                    raise RuntimeError(
                        "a chunk edge's request ended %s: %s"
                        % (req.outcome, req.error))
                got[i, at] = rows.pop(req.id)
            decoding = sum(not k.done for k in keepers)
        finally:
            sched._sample = real
        print("check: %d chunk edges read on the engine, %d ticks, %d of "
              "%d keepers decoding to the end"
              % (len(cuts), sched.stats["ticks"] - ticks, decoding,
                 self.slots - 1), file=sys.stderr, flush=True)
        return [{at: got[i, at] for j, at in cuts if j == i}
                for i in range(len(sample))]

    def check(self):
        """``serve_sessions_scoped``'s three numbers and the chunk edges':
        the widest root-mean-square difference, over the vocabulary,
        between the program's logits and the float32 reference's at one
        chunk edge of the sample."""
        sample = self.sample()
        if not sample:
            raise RuntimeError("no request was answered")
        t0 = time.perf_counter()
        out = self.family.served(self.config, self.seed, sample,
                                 pad_to=int(self.traffic["check_pad_to"]),
                                 edges=self.edges)
        served = out["served"]
        print("check: served-token gaps by request %s, mean over the "
              "tokens %.5f, chunk-edge logit differences by request %s "
              "(ask, prompt, tokens: %s) in %.1f s"
              % ([round(g, 5) for g in served["gaps"]], served["mean"],
                 [round(g, 5) for g in out["edges"]],
                 [(p.ask, len(p), len(t)) for p, t in sample],
                 time.perf_counter() - t0), flush=True)
        limits = self.cell.limits
        return [("served_logit_gap", max(served["gaps"]),
                 limits["served_logit_gap"]),
                ("served_gap_mean", served["mean"],
                 limits["served_gap_mean"]),
                ("chunk_edge_gap", max(out["edges"], default=0.0),
                 limits["chunk_edge_gap"]),
                ("check_sample_short", float(self._short(sample)), 0.0)]

    def _hist(self, prompt):
        """Nothing of a prompt comes from shared pages: the decoder
        reuses no other request's prefix."""
        return 0

    def _chunks(self, prompt):
        chunk = max(self.traffic["server"]["prefill_buckets"])
        return -(-len(prompt) // chunk)

    def window(self, seconds, tracer=None):
        record = super().window(seconds, tracer)
        moved = record.get("counters") or {}
        ok = self.finished
        if tracer is None or not ok or not record["slot_ticks"] \
                or not moved.get("prefill_chunks"):
            return record
        spans = span_reduce.ring("serve_sessions_state")
        if spans is None:
            return record
        programs = [(r["hist"], r["tokens"]) for r in
                    span_reduce.named(spans, "engine.prefill_chunk")]
        record["notes"].append(
            "prefill programs of the window's spans: %d chunks, %d whole "
            "prompts (left out of the scopes' work); the counter says %d"
            % (len(programs), len(span_reduce.named(spans, "engine.prefill")),
               moved["prefill_chunks"]))
        record["kernel_work"] = self.family.kernel_work(
            self.family.sizes(self.config),
            block=int(self.traffic["server"]["kv_block"]),
            ticks=record["ticks"], slot_ticks=record["slot_ticks"],
            contexts=[len(d["prompt"]) + j for d in ok
                      for j in range(1, len(d["tokens"]))],
            prompts=programs)
        return record

    def _short(self, sample):
        """The base class's shortfall of kinds, and how many of the
        ``check_min_chunked`` requests of at least ``check_chunks``
        chunks the sample lacks."""
        chunked = sum(self._chunks(p) >= int(self.traffic["check_chunks"])
                      for p, _t in sample)
        return super()._short(sample) + max(
            0, int(self.traffic["check_min_chunked"]) - chunked)


def faults():
    """``{name: (object, attribute, replacement)}`` of the planted
    faults, each in the state a chunk takes over or in the step size:
    ``state_not_carried``: a chunk after the first starts its
    gated-DeltaNet state from zero; ``conv_tail_not_carried``: its
    convolution starts from zeros in place of the last inputs of the
    chunk before; ``free_rows_stepped``: the step writes the state row
    of every slot, those between two chunks of a prompt too;
    ``no_negative_eigenvalues``: ``beta = sigmoid(.)``, without the
    factor 2."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import olmo_hybrid
    from mxnet_tpu.serving.paged_kv import _PrefillView, _StepView

    state = _PrefillView.state

    def zero(suffix):
        def planted(self, name):
            s = state(self, name)
            return jnp.zeros_like(s) if name.endswith(suffix) else s
        return planted

    def every_row(self, name, value):
        self._state[name] = value.astype(self._state[name].dtype)

    return {"state_not_carried": (_PrefillView, "state", zero("gdn_S")),
            "conv_tail_not_carried": (_PrefillView, "state",
                                      zero("gdn_conv")),
            "free_rows_stepped": (_StepView, "set_state", every_row),
            "no_negative_eigenvalues": (
                olmo_hybrid, "gdn_beta",
                lambda b, neg_eigval: jax.nn.sigmoid(b))}


def calibrate(cell, seed, seconds, others, rehearse=False):
    """Readings of one seed, each what a run compares, over the sample a
    run draws: the program's (a short window at the cell's own load
    judged by the float32 reference) and, from the same call, the
    control's (the token float8 puts first at each served position); on
    ``others`` seeds also the faults', each in a run of its own with the
    fault planted."""
    def run(kind):
        d = Driver(cell, seed, rehearse=rehearse)
        d.setup()
        record = d.window(seconds)
        d.free()
        sample = d.sample()
        t0 = time.perf_counter()
        out = d.family.served(
            d.config, d.seed, sample,
            compute="f32" if kind.startswith("fault") else "fp8",
            pad_to=int(d.traffic["check_pad_to"]), edges=d.edges)
        for which, edges in (("served", "edges"),
                             ("control", "control_edges")):
            if which not in out:
                continue
            row = out[which]
            yield {"kind": kind if which == "served" else "control_fp8",
                   "served_logit_gap": max(row["gaps"]),
                   "served_gap_mean": row["mean"],
                   "chunk_edge_gap": max(out[edges], default=0.0),
                   "by_request": [round(g, 5) for g in row["gaps"]],
                   "edges_by_request": [round(g, 5) for g in out[edges]],
                   "asks": [p.ask for p, _t in sample],
                   "lengths": [(len(p), len(t)) for p, t in sample],
                   "short": d._short(sample),
                   "requests": record["requests"],
                   "failed": record["failed"],
                   "check_s": round(time.perf_counter() - t0, 1)}

    yield from run("program")
    if not others:
        return
    for name, (obj, attr, planted) in faults().items():
        real = getattr(obj, attr)
        setattr(obj, attr, planted)
        try:
            yield from run("fault_" + name)
        finally:
            setattr(obj, attr, real)
