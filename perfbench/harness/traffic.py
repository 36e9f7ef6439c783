"""The one general traffic generator: a mix is a data file of
parameters under traffic/, read here.

Serving mixes (`serve_closed`): a fixed multiset of (prompt length,
answer length) pairs, one per client, is drawn from the FILE's
`sizes_seed`; the run's --seed only chooses which client holds which
pair and draws the token ids. A client keeps its pair for the whole
run, so at every instant the live rows are the mix's own pairs, each
somewhere in its own cycle: the pages in use, the decode program a step
runs and the prefills a second are properties of the mix and the
system, the same on every seed, and runs differ by the system, not by
the draw.
"""
import math

import numpy as np


def _draw(rs, spec, n):
    if spec["dist"] == "lognormal":
        vals = rs.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        vals = rs.uniform(spec["min"], spec["max"], n)
    elif spec["dist"] == "fixed":
        vals = np.full((n,), spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def size_pairs(mix):
    """The mix's fixed (prompt, answer) lengths, one pair per client."""
    rs = np.random.RandomState(int(mix["sizes_seed"]))
    n = int(mix["clients"])
    return list(zip(_draw(rs, mix["prompt_tokens"], n).tolist(),
                    _draw(rs, mix["output_tokens"], n).tolist()))


class ClosedLoopPlan:
    """Which request a client sends in which round: the seed deals the
    mix's pairs to the clients once, and the token ids come from the
    seed, the round and the client."""

    def __init__(self, mix, seed, vocab):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.clients = int(mix["clients"])
        shared = int(mix.get("shared_prefix_tokens", 0))
        rs = np.random.RandomState(self.seed % (2 ** 32))
        self.prefix = rs.randint(2, self.vocab, shared).tolist()
        pairs = size_pairs(mix)
        self.pairs = [pairs[i] for i in rs.permutation(self.clients)]

    def request(self, client, rnd):
        """(prompt token ids, answer length) of a client's `rnd`-th
        request."""
        n_prompt, n_out = self.pairs[client]
        rs = np.random.RandomState(
            (self.seed * 69069 + rnd * 104729 + client * 31 + 5) % (2 ** 32))
        body = rs.randint(2, self.vocab,
                          max(1, n_prompt - len(self.prefix))).tolist()
        return (self.prefix + body)[:max(n_prompt, 1)], int(n_out)
