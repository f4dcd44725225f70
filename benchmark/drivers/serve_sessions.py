"""Driver ``serve_sessions``: the closed loop of ``serve_closed_family``
over *sessions* -- a client puts several questions to one long document
and waits for each reply before the next.

Everything about the server, the window, the counters and the traced
scopes is ``serve_closed_family.Driver``'s.  What is this driver's:

- the clients' script (:class:`SessionScript`): a session is one
  document asked ``asks`` times, request ``k`` = document + question
  ``k``, sent whole each time.  The first ask of a document is a cold
  admission (in chunks, where the document is longer than the largest
  prefill bucket); the later asks find the document's full pages in
  the prefix index and prefill the question alone;
- the model work: a prompt token that came from shared pages is no
  work, so a request counts with its expected history (the document's
  whole pages, for every ask after the first), and the scopes' work
  (``family.kernel_work``) is handed the window's prefill PROGRAMS,
  each ``(hist, tokens)``;
- the counters ``prefill_chunks``, ``prompt_tokens`` and
  ``prefix_tokens_hit`` of ``PagedSlots.stats()``, read beside the
  expert counters (``record["counters"]``; a program without them
  leaves them out, and their readers then give nothing);
- the sample of the check: at least ``check_first_asks`` first asks and
  ``check_later_asks`` later asks among ``check_requests``, each padded
  to its own length for the reference, and the two numbers compared:
  the widest gap of one served token and the gap's mean over them all.
"""
import time

import numpy as np

from benchmark import harness, traffic_gen

_family = harness.load_driver("serve_closed_family")

HOST_COUNTERS = ("prefill_chunks", "prompt_tokens", "prefix_tokens_hit")


class _Prompt(list):
    """Token ids that know their place: ``ask`` (0 for the first of a
    document) and ``doc``, the document's length."""
    ask = doc = 0


def session_pool(traffic):
    """``sessions_pool`` sessions from the traffic file's own seed: a
    document length and, an ask, a question length and an output
    length; ``(docs (n,), questions (n, asks), outputs (n, asks))``."""
    rng = np.random.default_rng(int(traffic["lengths_seed"]))
    n, asks = int(traffic["sessions_pool"]), int(traffic["asks"])
    draw = lambda spec, k: traffic_gen._lognormal(rng, traffic[spec], k)
    return (draw("doc_len", n),
            draw("question_len", n * asks).reshape(n, asks),
            draw("output_len", n * asks).reshape(n, asks))


def scripts(traffic):
    """The pool dealt once, from the traffic file's own seed, into one
    script a client: ``(clients, rounds)`` session indices.  Round ``r``
    (the ``r``-th session of every script) holds one session of each of
    ``clients`` strata of the pool by document length, so that at any
    pace the documents under admission are the pool's mix, and which
    script gets which of them is drawn too."""
    docs = session_pool(traffic)[0]
    clients = int(traffic["clients"])
    rng = np.random.default_rng([int(traffic["lengths_seed"]), 5])
    strata = np.argsort(docs, kind="stable").reshape(clients, -1)
    rounds = np.stack([rng.permutation(s) for s in strata], axis=1)
    return np.stack([rng.permutation(r) for r in rounds], axis=1)


class SessionScript:
    """The requests one client sends, in order, for as long as asked:
    ``next()`` gives (prompt token ids, max_tokens).  The run's seed
    deals the pool's scripts (:func:`scripts`) out to the clients in
    another order and draws the ids; every document is asked ``asks``
    times, and a client that has walked its sessions starts them again
    with fresh ids.

    Every seed so sends the same lengths, on other clients and with
    other ids.  It has to: a client gets through about one session a
    window, so when the seed chose WHICH sessions met in the window
    (any shuffle of the pool, even one that kept every round's
    documents and output tokens alike) ``serve_tok_s`` followed the
    choice by 5-8% from seed to seed, and the driver's check refused
    the cell (PERF.md, section 2)."""

    def __init__(self, traffic, seed, client, vocab):
        docs, questions, outputs = session_pool(traffic)
        clients = int(traffic["clients"])
        order = np.random.default_rng([int(seed), 7]).permutation(clients)
        mine = scripts(traffic)[order[client]]
        self._sessions = list(zip(docs[mine], questions[mine],
                                  outputs[mine]))
        self._rng = np.random.default_rng([int(seed), 11, client])
        self._vocab = int(vocab)
        self._i = -1
        self._doc = None

    def next(self):
        self._i += 1
        asks = len(self._sessions[0][1])
        doc_len, questions, outputs = self._sessions[
            self._i // asks % len(self._sessions)]
        k = self._i % asks
        if k == 0:
            self._doc = self._rng.integers(0, self._vocab,
                                           int(doc_len)).tolist()
        prompt = _Prompt(self._doc + self._rng.integers(
            0, self._vocab, int(questions[k])).tolist())
        prompt.ask, prompt.doc = k, int(doc_len)
        return prompt, int(outputs[k])


class _SessionTraffic:
    """``traffic_gen`` as ``serve_closed_family`` sees it from here: its
    ``setup`` asks the generator for a ``ClientScript`` a client, and
    this driver's copy of that module (``load_driver`` makes one a
    call) is handed :class:`SessionScript` under that name, everything
    else being the generator's own.  A ``Driver._script(i)`` for a
    subclass to override would need an edit to
    ``drivers/serve_closed_family.py``, which is not this PR's to
    make."""
    ClientScript = SessionScript

    def __getattr__(self, name):
        return getattr(traffic_gen, name)


_family.traffic_gen = _SessionTraffic()


class Driver(_family.Driver):

    # -------------------------------------------------------------- window
    def _counts(self):
        stats = self.sched.backend.stats()
        return dict(super()._counts(), **{
            k: stats[k] for k in HOST_COUNTERS if k in stats})

    def _hist(self, prompt):
        """What of ``prompt`` an admission is expected to find in
        shared pages: for an ask after the first the document's whole
        pages (the question differs from its first token on)."""
        block = int(self.traffic["server"]["kv_block"])
        return prompt.doc // block * block if prompt.ask else 0

    def _model_flops(self, ok, c):
        return self.family.model_flops(self.config, [
            (len(d["prompt"]), len(d["tokens"]), self._hist(d["prompt"]))
            for d in ok])

    def window(self, seconds, tracer=None):
        record = super().window(seconds, tracer)
        ok = self.finished
        record["notes"].append(
            "requests answered: %d first asks, %d later asks; prompt "
            "tokens %d, of them expected from shared pages %d"
            % (sum(not d["prompt"].ask for d in ok),
               sum(bool(d["prompt"].ask) for d in ok),
               sum(len(d["prompt"]) for d in ok),
               sum(self._hist(d["prompt"]) for d in ok)))
        moved = record.get("counters") or {}
        if record.get("kernel_work") and moved.get("prefill_chunks"):
            # the prefill programs of the requests answered in the
            # window stand for those run in it
            chunk = max(self.traffic["server"]["prefill_buckets"])
            programs = [pair for d in ok for pair in self.family.chunks_of(
                self._hist(d["prompt"]),
                len(d["prompt"]) - self._hist(d["prompt"]), chunk)]
            n = moved["prefill_chunks"]
            programs = programs * (n // len(programs)) \
                + programs[:n % len(programs)]
            record["kernel_work"] = self.family.kernel_work(
                self.family.sizes(self.config),
                block=int(self.traffic["server"]["kv_block"]),
                ticks=record["ticks"], slot_ticks=record["slot_ticks"],
                contexts=[len(d["prompt"]) + j for d in ok
                          for j in range(1, len(d["tokens"]))],
                prompts=programs,
                pairs_held=moved["expert_assignments_held"],
                distinct_hits=moved["expert_distinct_hits"])
        return record

    # --------------------------------------------------------------- after
    def sample(self):
        """``check_requests`` of the finished requests, drawn from the
        seed: ``check_first_asks`` first asks, the longest always among
        them (cold admissions, in chunks where the document is longer
        than a bucket), ``check_later_asks`` later asks (prefix hits),
        the rest from whatever is left."""
        done = sorted(self.finished, key=lambda d: d["sent"])
        rng = np.random.default_rng([self.seed, 13])
        size = lambda i: len(done[i]["prompt"]) + len(done[i]["tokens"])
        firsts = sorted((i for i, d in enumerate(done)
                         if not d["prompt"].ask), key=size, reverse=True)
        firsts = firsts[:1] + [firsts[1:][j]
                               for j in rng.permutation(len(firsts[1:]))]
        laters = [i for i, d in enumerate(done) if d["prompt"].ask]
        laters = [laters[j] for j in rng.permutation(len(laters))]
        n_first = int(self.traffic["check_first_asks"])
        n_later = int(self.traffic["check_later_asks"])
        if len(firsts) < n_first or len(laters) < n_later:
            # a short (traced) window may answer none of a kind
            print("check: the window answered %d first asks and %d later "
                  "asks; the sample wants %d and %d and takes what there is"
                  % (len(firsts), len(laters), n_first, n_later), flush=True)
        picked = firsts[:n_first] + laters[:n_later]
        rest = [i for i in rng.permutation(len(done)) if i not in picked]
        picked += rest[:int(self.traffic["check_requests"]) - len(picked)]
        return [(done[i]["prompt"], done[i]["tokens"]) for i in picked]

    def check(self):
        """Two numbers of the sample's served tokens, teacher-forced
        through the float32 reference: the widest gap by which one
        token's logit lies below the reference's best, which a fault in
        the cache path or the scale moves fivefold and more, and the
        gap's mean over all the tokens, which the stated precision
        moves: an extreme of some hundred tokens a request swings
        tenfold from seed to seed and the float8 control passes it in
        some requests, its mean reads 30-180 times the program's
        (PERF.md section 6, "How ``serve_docqa_kimi`` decides")."""
        if not self.finished:
            raise RuntimeError("no request finished inside the window")
        sample = self.sample()
        t0 = time.perf_counter()
        out = self.family.served(
            self.config, self.seed, sample,
            pad_to=int(self.traffic["check_pad_to"]))["served"]
        print("check: served-token gaps by request %s, mean over the "
              "tokens %.5f (ask, prompt, tokens: %s) in %.1f s"
              % ([round(g, 5) for g in out["gaps"]], out["mean"],
                 [(p.ask, len(p), len(t)) for p, t in sample],
                 time.perf_counter() - t0), flush=True)
        return [("served_logit_gap", max(out["gaps"]),
                 self.cell.limits["served_logit_gap"]),
                ("served_gap_mean", out["mean"],
                 self.cell.limits["served_gap_mean"])]


# ----------------------------------------------------------- calibration
def faults():
    """``{name: (object, attribute, replacement)}`` of the planted
    faults, each in the program's own cache path or scale:
    ``history_left_out``: a prefill's tail attends over its own rows
    alone; ``hist_one_page_short``: every chunk behind a history is
    told a history one page shorter than it is (positions, page writes
    and the mask move with it); ``mscale_left_out``: the scores lose
    YaRN's ``m^2``."""
    from mxnet_tpu.models import kimi
    from mxnet_tpu.serving.paged_kv import PagedSlots, _PrefillView

    append, admit_chunk = _PrefillView.append, PagedSlots.admit_chunk

    def no_history(self, name, layer, rows):
        table, hist = append(self, name, layer, rows)
        return table, hist * 0

    def page_short(self, adm):
        off = self.block if adm.hist + adm.done else 0
        adm.hist -= off
        try:
            return admit_chunk(self, adm)
        finally:
            adm.hist += off

    return {"history_left_out": (_PrefillView, "append", no_history),
            "hist_one_page_short": (PagedSlots, "admit_chunk", page_short),
            "mscale_left_out": (kimi, "yarn_mscale", lambda *a: 1.0)}


def calibrate(cell, seed, seconds, others, rehearse=False):
    """Readings of one seed, each what a run compares (the widest gap)
    with the requests' gaps behind it and the gap's mean over the
    sample's tokens: a short window at the cell's own load judged by
    the float32 reference, and from the same call the control's (the
    token float8 puts first at each served position); on ``others``
    seeds also the faults', each in a run of its own with the fault
    planted in the program."""
    def run(kind):
        d = Driver(cell, seed, rehearse=rehearse)
        d.setup()
        record = d.window(seconds)
        d.free()
        sample = d.sample()
        out = d.family.served(
            d.config, d.seed, sample,
            compute="f32" if kind.startswith("fault") else "fp8",
            pad_to=int(d.traffic["check_pad_to"]))
        for which, row in out.items():
            yield {"kind": kind if which == "served" else "control_fp8",
                   "served_logit_gap": max(row["gaps"]),
                   "by_request": [round(g, 5) for g in row["gaps"]],
                   "mean_over_tokens": row["mean"],
                   "asks": [p.ask for p, _t in sample],
                   "requests": record["requests"],
                   "failed": record["failed"]}

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
