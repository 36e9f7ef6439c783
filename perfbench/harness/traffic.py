"""The one general traffic generator: a mix is a data file of
parameters under traffic/, read here.

Serving mixes (`serve_closed`): a fixed multiset of (prompt length,
answer length) pairs, one per client, is drawn from the FILE's
`sizes_seed`; the run's --seed only deals those pairs to the clients in
another order each round and draws the token ids. So every seed offers
the same work in another order, and runs differ by the system, not by
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
    """Which request a client sends in which round: round r deals the
    same pairs to the clients by a permutation drawn from the seed, and
    the token ids come from the seed, the round and the client."""

    def __init__(self, mix, seed, vocab):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.pairs = size_pairs(mix)
        self.clients = int(mix["clients"])
        shared = int(mix.get("shared_prefix_tokens", 0))
        rs = np.random.RandomState(self.seed % (2 ** 32))
        self.prefix = rs.randint(2, self.vocab, shared).tolist()
        self._perms = {}

    def _perm(self, rnd):
        if rnd not in self._perms:
            rs = np.random.RandomState(
                (self.seed * 1000003 + rnd * 7919 + 1) % (2 ** 32))
            self._perms[rnd] = rs.permutation(self.clients)
        return self._perms[rnd]

    def request(self, client, rnd):
        """(prompt token ids, answer length) of a client's `rnd`-th
        request."""
        n_prompt, n_out = self.pairs[int(self._perm(rnd)[client])]
        rs = np.random.RandomState(
            (self.seed * 69069 + rnd * 104729 + client * 31 + 5) % (2 ** 32))
        body = rs.randint(2, self.vocab,
                          max(1, n_prompt - len(self.prefix))).tolist()
        return (self.prefix + body)[:max(n_prompt, 1)], int(n_out)
