"""Class traffic (`serve_classes`): closed-loop callers of several
classes in ONE queue, each class with its own share of the callers and
its own prompt and answer lengths — long-context callers that generate
thousands of tokens beside ordinary short turns.

Like `traffic.ClosedLoopPlan`, the mix is dealt once: every class's
(prompt, answer) pairs, one per caller of the class, are drawn from the
FILE's `sizes_seed`, class after class; the run's --seed only chooses
which client holds which pair (and so which class) and draws the token
ids. A client keeps its pair for the whole run, so the live contexts,
the pages in use and the prefills a second are the mix's own on every
seed.
"""
import numpy as np

from perfbench.harness.traffic import _draw


def class_pairs(mix):
    """The mix's fixed sizes: [(class name, prompt length, answer
    length)], one per caller, class after class."""
    rs = np.random.RandomState(int(mix["sizes_seed"]))
    out = []
    for c in mix["classes"]:
        n = int(c["clients"])
        prompts = _draw(rs, c["prompt_tokens"], n).tolist()
        answers = _draw(rs, c["output_tokens"], n).tolist()
        out.extend((c["name"], p, a) for p, a in zip(prompts, answers))
    return out


def pages_for(mix, page_size):
    """Pages that hold every dealt pair at its full length (prompt and
    answer) at once: what a pool that never preempts has to offer."""
    return sum(-(-(p + a) // page_size) for _, p, a in class_pairs(mix))


class ClassPlan:
    """Which request a client sends in which round."""

    def __init__(self, mix, seed, vocab):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        pairs = class_pairs(mix)
        self.clients = len(pairs)
        if self.clients != int(mix["clients"]):
            raise ValueError(
                f"the classes deal {self.clients} callers, the mix states "
                f"{mix['clients']}")
        rs = np.random.RandomState(self.seed % (2 ** 32))
        self.pairs = [pairs[i] for i in rs.permutation(self.clients)]

    def klass(self, client):
        return self.pairs[client][0]

    def request(self, client, rnd):
        """(prompt token ids, answer length) of a client's `rnd`-th
        request: its pair's lengths, token ids from the seed, the round
        and the client."""
        _, n_prompt, n_out = self.pairs[client]
        rs = np.random.RandomState(
            (self.seed * 69069 + rnd * 104729 + client * 31 + 5) % (2 ** 32))
        return rs.randint(2, self.vocab, n_prompt).tolist(), int(n_out)

    def longest(self):
        """Tokens of the longest request the plan can deal."""
        return max(p + a for _, p, a in self.pairs)
