"""Driver ``serve_block``: the closed loop of ``serve_closed_family``
for a decoder that decodes a block a slot (``decoder.block_length`` >
1: generation by diffusion over blocks).

Everything about clients, the window, the slots filled before it opens,
the scope maps and the experts' counters is ``serve_closed_family``'s.
Restated here is what assumes one token a slot a tick there:

- a request names its ``denoising_steps`` (from the traffic file's list,
  by the parity of its length pair's place in the pool; a pair drawn
  twice takes its first place's), and the reply's ``unmask_step`` is
  kept: the check needs the trajectory, not the tokens alone;
- a tick is a forward and a slot-tick a slot-forward: the model work
  counts a request's forwards (``family.model_flops`` over ``(prompt,
  tokens, steps)``), the attention scope's work the window's
  slot-forwards over their contexts (``family.block_attn_work``), and
  the scheduler's block counters (``commit_forwards``,
  ``tokens_unmasked``) are read beside the experts';
- ``check``: the served trajectories, teacher-forced through the float32
  reference (``family.served``), give ``served_logit_gap`` and
  ``served_order_gap``.
"""
import json
import time
import urllib.error
import urllib.request

from benchmark import harness, traffic_gen

_base = harness.load_driver("serve_closed_family")
# this module's private copy of the base driver reads its scopes from
# its own global: the step's paged-attention call first, inside ``attn``
_base.SCOPES = ("attn.pages", "attn", "moe.experts", "moe.route", "head")


class Driver(_base.Driver):

    def __init__(self, cell, seed, rehearse=False):
        super().__init__(cell, seed, rehearse=rehearse)
        pool = traffic_gen.length_pool(self.traffic)
        choice = [int(k) for k in self.traffic["denoising_steps"]]
        self._steps_of = {}
        for i, (p_len, out) in enumerate(pool):
            self._steps_of.setdefault((int(p_len), int(out)),
                                      choice[i % len(choice)])

    # ------------------------------------------------------------ clients
    def _post(self, prompt, max_tokens):
        """``serve_closed``'s, with the request's ``denoising_steps``
        (the decoder's default for a request outside the pool: a warm-up,
        a client's short first one) and the reply's ``unmask_step``."""
        body = {"prompt": prompt, "max_tokens": max_tokens,
                "temperature": 0,
                "deadline_ms": int(self.traffic["deadline_ms"])}
        steps = self._steps_of.get((len(prompt), max_tokens))
        if steps is not None:
            body["denoising_steps"] = steps
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/generate",
            data=json.dumps(body).encode(),
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
              and reply.get("n_tokens") == max_tokens
              and len(reply.get("unmask_step", ())) == max_tokens)
        return {"prompt": prompt, "max_tokens": max_tokens, "sent": t0,
                "answered": t1, "ok": ok, "outcome": reply.get("outcome"),
                "tokens": reply.get("tokens", []),
                "unmask_step": reply.get("unmask_step", []),
                "steps": steps or self.decoder.denoising_steps,
                "ttft_ms": reply.get("ttft_ms")}

    # -------------------------------------------------------------- window
    def _counts(self):
        stats = self.sched.stats
        return dict(super()._counts(), **{
            k: stats[k] for k in ("slot_ticks", "commit_forwards",
                                  "tokens_unmasked", "blocks_committed")})

    def _requests(self, ok):
        return [(len(d["prompt"]), len(d["tokens"]), d["steps"])
                for d in ok]

    def window(self, seconds, tracer=None):
        record = super().window(seconds, tracer)
        if tracer is not None:
            work = self.family.block_attn_work(
                self.family.sizes(self.config),
                int(self.traffic["server"]["kv_block"]),
                self._requests(self.finished), record["slot_ticks"])
            if work:
                record["kernel_work"] = dict(record.get("kernel_work") or {},
                                             block_attn=work)
        return record

    def _model_flops(self, ok, c):
        return self.family.model_flops(self.config, self._requests(ok))

    # --------------------------------------------------------------- after
    def sample(self):
        """The base class's sample as ``(prompt, tokens, unmask_step)``."""
        by_prompt = {id(d["prompt"]): d for d in self.finished}
        return [(p, t, by_prompt[id(p)]["unmask_step"])
                for p, t in super().sample()]

    def check(self):
        if not self.finished:
            raise RuntimeError("no request finished inside the window")
        out = self.family.served(self.config, self.seed, self.sample(),
                                 length=self.max_len)
        print("check: over %d positions, by request: logit gaps %s, order "
              "gaps %s" % (out["compared"],
                           [round(g, 5) for g in out["logit_gaps"]],
                           [round(g, 5) for g in out["order_gaps"]]),
              flush=True)
        return [("served_logit_gap", max(out["logit_gaps"]),
                 self.cell.limits["served_logit_gap"]),
                ("served_order_gap", max(out["order_gaps"]),
                 self.cell.limits["served_order_gap"])]


def calibrate(cell, seed, seconds, others, rehearse=False):
    """Readings of one seed: a short window at the cell's own load and
    the served trajectories' two gaps; on ``others`` seeds also the
    control's (the reference in float8) and each planted fault's."""
    d = Driver(cell, seed, rehearse=rehearse)
    d.setup()
    record = d.window(seconds)
    d.free()
    sample = d.sample()

    def row(kind, **kw):
        out = d.family.served(d.config, d.seed, sample, length=d.max_len,
                              **kw)
        return {"kind": kind, "served_logit_gap": max(out["logit_gaps"]),
                "served_order_gap": max(out["order_gaps"]),
                "compared": out["compared"]}

    yield dict(row("program"), requests=record["requests"])
    if not others:
        return
    yield row("control_fp8", compute="fp8")
    for fault in d.family.FAULTS:
        yield row("fault_" + fault, fault=fault)
