"""Driver ``serve_closed_family``: the closed loop of ``serve_closed``
for a decoder that its family builds (``family.build_decoder``).

Everything about clients, the window, the sample and the check is
``serve_closed.Driver``'s.  Restated here is what is GPT-2's there:

- ``setup``: the decoder comes from the family, token ids from the
  family's vocabulary (a slice, where the configuration is a share), no
  Pallas kernel is insisted on, and it returns only when the clients'
  second requests are all admitted (``_fill_slots``): the window opens
  with every slot busy, not on the admission of one request a slot;
- the model work: ``family.model_flops`` and, after the window, the
  work of the traced scopes (``family.kernel_work``), which needs the
  program's own counters (``PagedSlots.stats()``: token-expert
  assignments on held experts, distinct held experts hit) read before
  and after the window;
- ``kernels_expected``: the family's own count of Pallas kernels in the
  step (``family.KERNELS_IN_STEP``, 0 where absent) -- the base class
  expects one a layer;
- a traced run: device time by named scope (``scope_s``, from
  ``benchmark/scope_reduce.py``), reduced from the trace file before
  ``run.py`` reads and removes it, against the scope maps of the
  compiled step and prefill programs (built in ``count_kernels``, which
  only a traced run calls, inside set-up).

The program's span ring (``MXTPU_SPAN_RING``, read once when
``mxnet_tpu`` is first imported) is given room for a traced window of
128 slots before that import: with the default of 2048 records a 10 s
window of short ticks overflows, and the span readers then give no
value.
"""
import os

os.environ.setdefault("MXTPU_SPAN_RING", "65536")

import importlib  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

from benchmark import harness, scope_reduce, traffic_gen  # noqa: E402

_base = harness.load_driver("serve_closed")

# the named scopes the program's layers carry (docs/tracing.md), most
# specific first, and the compiler's own kernels by instruction name
SCOPES = ("moe.experts", "moe.route", "moe.shared", "mla_attn", "kda",
          "mlp.dense", "head")
BY_NAME = (("ragged-dot", "moe.experts"),)
# requests (the longest first) over which the check counts flipped
# expert choices: a second pass of the reference, so not all eight
FLIPS_OVER = 2


class _Hooked:
    """The harness's tracer with a call after its start and one before
    its stop."""

    def __init__(self, tracer, after_start, before_stop):
        self._tracer = tracer
        self._after_start, self._before_stop = after_start, before_stop

    def start(self):
        self._tracer.start()
        self._after_start()

    def stop(self):
        self._before_stop()
        self._tracer.stop()


class Driver(_base.Driver):

    def __init__(self, cell, seed, rehearse=False):
        super().__init__(cell, seed, rehearse=rehearse)
        self._scope_maps = {}

    # ------------------------------------------------------------- set-up
    def setup(self):
        import jax
        import jax.numpy as jnp

        # a program without this family fails here, at once, before any
        # weight is made
        importlib.import_module(self.family.PROGRAM_MODULE)
        from mxnet_tpu.serving import serve_decoder

        clock = [time.perf_counter()]
        c = self.family.sizes(self.config)
        srv = dict(self.traffic["server"])
        dtype = jnp.dtype(self.config["serving"]["weights_dtype"])
        params = self.family.serving_weights(self.config, self.seed, dtype)
        jax.block_until_ready(params)
        clock.append(time.perf_counter())
        self.decoder = self.family.build_decoder(
            self.config, params, int(srv.pop("max_len")), dtype)
        del params
        srv["prefill_buckets"] = tuple(srv["prefill_buckets"])
        self.slots = int(srv["num_slots"])
        self.max_len = self.decoder.max_len
        self.server, self.sched = serve_decoder(self.decoder, port=0, **srv)
        self.port = self.server.server_address[1]
        # one request a prefill bucket, two tokens each: compiles every
        # prefill program and the step
        rng = np.random.default_rng([self.seed, 3])
        low = 1
        for bucket in srv["prefill_buckets"]:
            n = (low + bucket) // 2 + 1
            self._post(rng.integers(0, c["vocab_size"], n).tolist(), 2)
            low = bucket
        clock.append(time.perf_counter())
        clients = int(self.traffic["clients"])
        self._first = threading.Barrier(clients + 1)
        for i in range(clients):
            script = traffic_gen.ClientScript(self.traffic, self.seed, i,
                                              c["vocab_size"])
            t = threading.Thread(target=self._client, args=(script,),
                                 name=f"bench-client-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        try:
            self._first.wait(timeout=900)
        except threading.BrokenBarrierError:
            raise RuntimeError("a client's first request failed: %s"
                               % self._errors[:3]) from None
        clock.append(time.perf_counter())
        self._fill_slots()
        clock.append(time.perf_counter())
        self._setup_note = (
            "set-up of the driver: weights %.1f s, decoder, server and one "
            "request a prefill bucket %.1f s, the clients' first requests "
            "%.1f s, their second requests admitted %.1f s"
            % (clock[1] - clock[0], clock[2] - clock[1],
               clock[3] - clock[2], clock[4] - clock[3]))

    def _fill_slots(self, timeout=300.0):
        """Wait until the clients' second requests are all admitted.
        The clients leave the barrier together, so one request a slot
        arrives at once, and the scheduler admits them one after
        another with no tick in between: seconds without a decoded
        token that no later moment of a closed loop repeats.  That is
        the loop's start, so it counts as set-up, and the window opens
        on what the cell is for: every slot busy."""
        # admitted so far: one request a bucket, one a client
        want = len(self.sched.prefill_buckets) + 2 * len(self._threads)
        t_end = time.perf_counter() + timeout
        while self.sched.stats["admitted"] < want:
            if self._errors:
                raise RuntimeError("a client failed while the slots "
                                   "filled: %s" % self._errors[:3])
            if time.perf_counter() > t_end:
                raise RuntimeError(
                    "the clients' second requests were not admitted in "
                    "%.0f s: %d admissions of %d"
                    % (timeout, self.sched.stats["admitted"], want))
            time.sleep(0.01)

    def count_kernels(self):
        """Pallas kernels of the step program, and on the way the scope
        map of every program the window will run (a traced run only)."""
        backend = self.sched.backend
        text = backend.lower_step().compile().as_text()
        self.kernels_in_step = text.count("tpu_custom_call")
        self._scope_maps = {
            "jit_decode_step_" + self.decoder.family:
                scope_reduce.scope_map(text, SCOPES, BY_NAME)}
        if hasattr(backend, "lower_prefill"):
            for bucket in self.sched.prefill_buckets:
                self._scope_maps[
                    "jit_prefill_%s_b%d" % (self.decoder.family, bucket)] = \
                    scope_reduce.scope_map(
                        backend.lower_prefill(bucket).compile().as_text(),
                        SCOPES, BY_NAME)
        return self.kernels_in_step

    # -------------------------------------------------------------- window
    def _counts(self):
        """The program's counters now (one device fetch, which waits for
        the program in flight) and the scheduler's admissions."""
        stats = self.sched.backend.stats()
        return dict({k: v for k, v in stats.items()
                     if k.startswith("expert_")},
                    admitted=self.sched.stats["admitted"])

    def window(self, seconds, tracer=None):
        if tracer is None:
            return super().window(seconds)
        # the counters are read as the profiler has started and before it
        # stops, next to the base class's own reading of the scheduler's:
        # starting a trace takes seconds, and the clients never pause
        marks = {}
        record = super().window(seconds, _Hooked(
            tracer, lambda: marks.update(before=self._counts()),
            lambda: marks.update(after=self._counts())))
        moved = {k: marks["after"][k] - marks["before"][k]
                 for k in marks["after"]}
        record["counters"] = moved
        record["kernels_expected"] = \
            None if record["kernels_expected"] is None \
            else int(getattr(self.family, "KERNELS_IN_STEP", 0))
        ok = self.finished
        if ok and record["slot_ticks"] and "expert_distinct_hits" in moved:
            # the prompts of the requests answered in the window stand
            # for those admitted in it
            prompts = [len(d["prompt"]) for d in ok]
            prompts = prompts * (moved["admitted"] // len(ok)) \
                + prompts[:moved["admitted"] % len(ok)]
            record["kernel_work"] = self.family.kernel_work(
                self.family.sizes(self.config),
                block=int(self.traffic["server"]["kv_block"]),
                ticks=record["ticks"], slot_ticks=record["slot_ticks"],
                contexts=[len(d["prompt"]) + j for d in ok
                          for j in range(1, len(d["tokens"]))],
                prompts=prompts,
                pairs_held=moved["expert_assignments_held"],
                distinct_hits=moved["expert_distinct_hits"])
        by_program = scope_reduce.reduce_dir(tracer.dir, self._scope_maps)
        record["scope_s"] = scope_reduce.totals(by_program)
        record["notes"] += [
            "counters over the window: %s" % moved,
            "device seconds by program and scope: %s" % {
                p: {k: round(v, 4) for k, v in sorted(s.items())}
                for p, s in sorted(by_program.items())}]
        return record

    # --------------------------------------------------------------- after
    def check(self):
        """The base class's comparison, and beside it how often the
        stated precision alone flips an expert choice over the first
        ``FLIPS_OVER`` checked requests (the reference in float32
        against the reference with bfloat16 operands: a flipped choice
        moves the hidden state by a whole expert's part, which is what
        the widest gaps are made of)."""
        if not self.finished:
            raise RuntimeError("no request finished inside the window")
        if not hasattr(self.family, "served"):
            return super().check()
        out = self.family.served(
            self.config, self.seed, self.sample(), length=self.max_len,
            flips_over=FLIPS_OVER)
        print("check: served-token gaps by request %s; expert choices "
              "that bfloat16 operands flip in the reference: %.4f of %d "
              "(token, layer) pairs, %.4f touching an expert held here"
              % ([round(g, 5) for g in out["gaps"]], out["flips"],
                 out["flip_pairs"], out["flips_held"]), flush=True)
        return [("served_logit_gap", max(out["gaps"]),
                 self.cell.limits["served_logit_gap"])]

    def _model_flops(self, ok, c):
        return self.family.model_flops(
            self.config, [(len(d["prompt"]), len(d["tokens"])) for d in ok])

    def _kernel_work(self, ok, c, slot_ticks, ticks):
        return {}       # needs the counters: filled in by window()


def calibrate(cell, seed, seconds, others, rehearse=False):
    """``serve_closed.calibrate`` with this module's driver."""
    real = _base.Driver
    _base.Driver = Driver
    try:
        yield from _base.calibrate(cell, seed, seconds, others,
                                   rehearse=rehearse)
    finally:
        _base.Driver = real
