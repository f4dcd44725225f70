"""Driver ``serve_closed``: a closed loop of clients against
``serving.serve_decoder`` over loopback HTTP.

Set-up makes the weights on the device from the seed, builds the
decoder and the server, warms each prefill bucket and the step with one
request each, starts the clients (threads of this process; each sends
its next request when the reply arrives) and returns when every
client's first, short request is answered.  The window then measures
the requests answered inside it, whenever they began; those in flight
when it closes are dropped with the server and count in nothing.
"""
import gc
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from benchmark import harness, traffic_gen, work


def _percentile(values, q):
    """The ``q``-th percentile as the smallest value with at least that
    share of the sample at or below it (no interpolation)."""
    s = sorted(values)
    k = max(0, int(np.ceil(q / 100.0 * len(s))) - 1)
    return s[k]


class Driver:
    default_gap_label = "engine_host"

    def __init__(self, cell, seed, rehearse=False):
        self.cell, self.seed, self.rehearse = cell, int(seed), rehearse
        self.config, self.traffic = cell.config, cell.traffic
        self.family = harness.load_family(cell.config["family"])
        self.kernels_in_step = None
        self._lock = threading.Lock()
        self._done = []          # finished requests, by any client
        self._stop = threading.Event()
        self._threads = []
        self._errors = []

    # ------------------------------------------------------------- set-up
    def setup(self):
        import jax
        import jax.numpy as jnp

        from mxnet_tpu.models.decode import KVDecoder
        from mxnet_tpu.serving import serve_decoder

        clock = [time.perf_counter()]
        c = self.family.sizes(self.config)
        srv = dict(self.traffic["server"])
        dtype = jnp.dtype(self.config["serving"]["weights_dtype"])
        params = self.family.serving_weights(self.config, self.seed, dtype)
        jax.block_until_ready(params)
        clock.append(time.perf_counter())
        self.decoder = KVDecoder(params, c["n_layer"], c["n_head"],
                                 max_len=int(srv.pop("max_len")),
                                 dtype=dtype)
        del params
        srv["prefill_buckets"] = tuple(srv["prefill_buckets"])
        self.slots = int(srv["num_slots"])
        self.max_len = self.decoder.max_len
        self.server, self.sched = serve_decoder(self.decoder, port=0, **srv)
        self.port = self.server.server_address[1]
        kernel = self.sched.backend.stats()["kernel"]
        if not self.rehearse and kernel != "pallas":
            raise RuntimeError(f"the paged step runs {kernel!r}, not the "
                               "Pallas kernel")
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
        self._setup_note = (
            "set-up of the driver: weights %.1f s, decoder, server and one "
            "request a prefill bucket %.1f s, the clients' first requests "
            "%.1f s" % (clock[1] - clock[0], clock[2] - clock[1],
                        clock[3] - clock[2]))

    def count_kernels(self):
        text = self.sched.backend.lower_step().compile().as_text()
        self.kernels_in_step = text.count("tpu_custom_call")
        return self.kernels_in_step

    # ------------------------------------------------------------ clients
    def _post(self, prompt, max_tokens):
        body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                           "temperature": 0,
                           "deadline_ms": int(self.traffic["deadline_ms"])
                           }).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        limit = self.traffic["deadline_ms"] / 1e3 + 30
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=limit) as resp:
                reply = json.loads(resp.read())
        except urllib.error.HTTPError as e:
            reply = {"outcome": f"http_{e.code}", "tokens": [],
                     "n_tokens": 0, "ttft_ms": None}
        t1 = time.perf_counter()
        ok = (reply.get("outcome") == "ok"
              and reply.get("n_tokens") == max_tokens)
        return {"prompt": prompt, "max_tokens": max_tokens, "sent": t0,
                "answered": t1, "ok": ok, "outcome": reply.get("outcome"),
                "tokens": reply.get("tokens", []),
                "ttft_ms": reply.get("ttft_ms")}

    def _client(self, script):
        first = True
        while not self._stop.is_set():
            prompt, out = script.next()
            try:
                done = self._post(prompt, out)
            except (OSError, ValueError) as e:
                # the server going away under a request in flight is how
                # the window's end looks from here; anything earlier is a
                # failed request
                if not self._stop.is_set():
                    with self._lock:
                        self._errors.append(repr(e))
                    if first:
                        self._first.abort()
                return
            if self._stop.is_set():
                return
            with self._lock:
                self._done.append(done)
            if first:
                first = False
                try:
                    self._first.wait(timeout=900)
                except threading.BrokenBarrierError:
                    return

    # -------------------------------------------------------------- window
    def window(self, seconds, tracer=None):
        if tracer is not None:
            tracer.start()
        before = dict(self.sched.stats)
        t_open = time.perf_counter()
        time.sleep(seconds)
        t_close = time.perf_counter()
        after = dict(self.sched.stats)
        if tracer is not None:
            tracer.stop()
        self._stop.set()
        with self._lock:
            inside = [d for d in self._done
                      if t_open <= d["answered"] <= t_close]
            errors = list(self._errors)
        wall = t_close - t_open
        ok = [d for d in inside if d["ok"]]
        failed = len(inside) - len(ok) + len(errors)
        self.finished = ok
        c = self.family.sizes(self.config)
        ticks = after["ticks"] - before["ticks"]
        slot_ticks = after["slot_ticks"] - before["slot_ticks"]
        deadline = float(self.traffic["deadline_ms"])
        ttft = [d["ttft_ms"] for d in ok] + [deadline] * failed
        rtt = [1e3 * (d["answered"] - d["sent"]) for d in ok]
        tpot = [(r - d["ttft_ms"]) / (d["max_tokens"] - 1)
                for r, d in zip(rtt, ok)] + [deadline] * failed
        tokens = sum(len(d["tokens"]) for d in ok)
        notes = [self._setup_note,
                 "requests answered in the window %d (failed %d), tokens %d;"
                 " ticks %d, slot-ticks %d; client round trip ms p50 %.1f "
                 "max %.1f; errors %s"
                 % (len(inside), failed, tokens, ticks, slot_ticks,
                    _percentile(rtt, 50) if rtt else -1,
                    max(rtt) if rtt else -1, errors[:3])]
        e2e = {}
        if ok:
            e2e = {"serve_tok_s": tokens / wall,
                   "ttft_p95_ms": _percentile(ttft, 95),
                   "tpot_p95_ms": _percentile(tpot, 95)}
        return {"window_s": wall, "attempted": len(inside) + len(errors),
                "failed": failed, "end_to_end": e2e, "notes": notes,
                "ticks": ticks, "slot_ticks": slot_ticks,
                "slots": self.slots, "requests": len(ok),
                "model_flops": self._model_flops(ok, c),
                "kernel_work": self._kernel_work(ok, c, slot_ticks, ticks),
                "kernels_in_step": self.kernels_in_step,
                # one paged-attention kernel a layer, where counted
                "kernels_expected": None if self.kernels_in_step is None
                or self.rehearse else c["n_layer"]}

    def _model_flops(self, ok, c):
        total = 0.0
        for d in ok:
            p, n = len(d["prompt"]), len(d["tokens"])
            total += work.gpt2_prefill_flops(
                c["n_layer"], c["n_embd"], c["n_inner"], c["vocab_size"], p)
            total += sum(work.gpt2_decode_flops(
                c["n_layer"], c["n_embd"], c["n_inner"], c["vocab_size"],
                p + j) for j in range(1, n))
        return total

    def _kernel_work(self, ok, c, slot_ticks, ticks):
        """The paged kernel's model work over the window: what one
        slot-tick needs on average over the finished requests' decode
        positions (position ``p + j`` attends over ``p + j`` positions,
        in whole pages), times the window's slot-ticks, all layers."""
        block = int(self.traffic["server"]["kv_block"])
        dh = c["n_embd"] // c["n_head"]
        contexts = [len(d["prompt"]) + j for d in ok
                    for j in range(1, len(d["tokens"]))]
        if not contexts or not slot_ticks:
            return {}
        all_ = work.paged_attention_step_work(contexts, c["n_head"], dh,
                                              block)
        scale = slot_ticks / len(contexts) * c["n_layer"]
        return {"paged_attn": {"flops": all_["flops"] * scale,
                               "bytes": all_["bytes"] * scale,
                               "calls": ticks * c["n_layer"]}}

    # --------------------------------------------------------------- after
    def free(self):
        self._stop.set()
        self.server.shutdown()
        self.server.server_close()
        self.sched.close()
        for t in self._threads:
            t.join(timeout=60)
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"clients still running: {alive}")
        self.sched.backend.pool = None
        self.sched.backend = None
        self.decoder.p = None
        self.sched = self.server = self.decoder = None
        gc.collect()

    def check(self):
        """The widest gap by which a served token's logit lies below the
        float32 reference's best, teacher-forced over a sample of the
        window's finished requests (the longest among them)."""
        if not self.finished:
            raise RuntimeError("no request finished inside the window")
        gap, detail = self.family.served_gap(
            self.config, self.seed, self.sample(), compute="f32",
            length=self.max_len)
        print("check: served-token gaps by request %s" % detail, flush=True)
        return [("served_logit_gap", gap,
                 self.cell.limits["served_logit_gap"])]

    def sample(self):
        """``check_requests`` of the finished requests, drawn from the
        seed, the longest (prompt + served tokens) always among them."""
        done = sorted(self.finished, key=lambda d: d["sent"])
        n = min(int(self.traffic["check_requests"]), len(done))
        longest = max(range(len(done)), key=lambda i: (
            len(done[i]["prompt"]) + len(done[i]["tokens"])))
        rest = [i for i in range(len(done)) if i != longest]
        rng = np.random.default_rng([self.seed, 13])
        picked = [longest] + list(rng.permutation(rest)[:n - 1])
        return [(done[i]["prompt"], done[i]["tokens"]) for i in picked]


def calibrate(cell, seed, seconds, others, rehearse=False):
    """Readings of one seed: a short window at the cell's own load, the
    served tokens' widest gap, and on ``others`` seeds the control's
    (the token float8 puts first) and an altered token's."""
    d = Driver(cell, seed, rehearse=rehearse)
    d.setup()
    record = d.window(seconds)
    d.free()
    sample = d.sample()
    gap, detail = d.family.served_gap(d.config, d.seed, sample,
                                      length=d.max_len)
    yield {"kind": "program", "served_logit_gap": gap, "by_request": detail,
           "requests": record["requests"],
           "tokens_compared": sum(len(t) for _p, t in sample)}
    if not others:
        return
    gap, detail = d.family.served_gap(d.config, d.seed, sample,
                                      compute="fp8", length=d.max_len)
    yield {"kind": "control_fp8", "served_logit_gap": gap,
           "by_request": detail}
    vocab = d.family.sizes(d.config)["vocab_size"]
    altered = [(p, t[:len(t) // 2] + [(t[len(t) // 2] + 1) % vocab]
                + t[len(t) // 2 + 1:]) for p, t in sample]
    gap, detail = d.family.served_gap(d.config, d.seed, altered,
                                      length=d.max_len)
    yield {"kind": "fault_token_altered", "served_logit_gap": gap,
           "by_request": detail}
