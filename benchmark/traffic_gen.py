"""The one generator of serving traffic, from a traffic file's
parameters and the run's seed.

Lengths: a pool of (prompt, output) length pairs is drawn once from the
traffic file's own ``lengths_seed`` -- log-normal, clipped -- so that
every run of a cell meets the same set of sizes; the run's seed only
shuffles the pool and draws the token ids.  Each client walks its own
slice of the shuffled pool.
"""
import numpy as np


def _lognormal(rng, spec, n):
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def length_pool(traffic):
    rng = np.random.default_rng(int(traffic["lengths_seed"]))
    n = int(traffic["lengths_pool"])
    return np.stack([_lognormal(rng, traffic["prompt_len"], n),
                     _lognormal(rng, traffic["output_len"], n)], axis=1)


class ClientScript:
    """The requests one client sends, in order, for as long as asked:
    ``next()`` gives (prompt token ids, max_tokens).  The first request
    is a short one, which leaves the clients out of step."""

    def __init__(self, traffic, seed, client, vocab):
        pool = length_pool(traffic)
        order = np.random.default_rng([int(seed), 7]).permutation(len(pool))
        clients = int(traffic["clients"])
        self._pairs = pool[order][client::clients]
        self._rng = np.random.default_rng([int(seed), 11, client])
        self._vocab = int(vocab)
        self._i = -1
        first = traffic["first_output_len"]
        self._first_out = int(self._rng.integers(first["min"],
                                                 first["max"] + 1))

    def next(self):
        self._i += 1
        p_len, out = self._pairs[self._i % len(self._pairs)]
        if self._i == 0:
            out = self._first_out
        prompt = self._rng.integers(0, self._vocab, int(p_len))
        return prompt.tolist(), int(out)
