"""Session traffic (`serve_sessions`): every client holds ONE document
for the whole run and sends, round after round, that document followed
by a new question.

Like `traffic.ClosedLoopPlan`, the mix is dealt once: the document
lengths, one per client, and each client's question and answer lengths
for `rounds_dealt` rounds (then they repeat) are drawn from the FILE's
`sizes_seed`; the run's --seed only chooses which client holds which
slot and draws the token ids. The live contexts, the pages in use and
the prefills a second are therefore the mix's own on every seed.
"""
import numpy as np

from perfbench.harness.traffic import _draw


def session_sizes(mix):
    """The mix's fixed sizes: [(document length, [(question, answer)
    per dealt round])] per slot."""
    rs = np.random.RandomState(int(mix["sizes_seed"]))
    n, rounds = int(mix["clients"]), int(mix["rounds_dealt"])
    docs = _draw(rs, mix["document_tokens"], n).tolist()
    out = []
    for d in docs:
        q = _draw(rs, mix["question_tokens"], rounds).tolist()
        a = _draw(rs, mix["output_tokens"], rounds).tolist()
        out.append((d, list(zip(q, a))))
    return out


class SessionPlan:
    """Which request a client sends in which round."""

    def __init__(self, mix, seed, vocab):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.clients = int(mix["clients"])
        rs = np.random.RandomState(self.seed % (2 ** 32))
        sizes = session_sizes(mix)
        self.slots = [sizes[i] for i in rs.permutation(self.clients)]
        self._docs = {}

    def document(self, client):
        """The client's document: token ids from the seed and the
        client, the same in every round."""
        if client not in self._docs:
            rs = np.random.RandomState(
                (self.seed * 69069 + client * 7919 + 11) % (2 ** 32))
            self._docs[client] = rs.randint(
                2, self.vocab, self.slots[client][0]).tolist()
        return self._docs[client]

    def request(self, client, rnd):
        """(prompt token ids, answer length) of a client's `rnd`-th
        request: its document, then this round's question."""
        rounds = self.slots[client][1]
        n_q, n_out = rounds[rnd % len(rounds)]
        rs = np.random.RandomState(
            (self.seed * 69069 + rnd * 104729 + client * 31 + 5) % (2 ** 32))
        return self.document(client) + rs.randint(
            2, self.vocab, n_q).tolist(), int(n_out)

    def longest(self):
        """Tokens of the longest request the plan can deal."""
        return max(d + max(q + a for q, a in rounds)
                   for d, rounds in self.slots)
