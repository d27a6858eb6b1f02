"""Closed-loop ingest of a trigram stream: `ClosedIngest` over the unigram
t_i, bigram (t_i, t_i+1) and trigram (t_i, t_i+1, t_i+2) of every position
of a seeded corpus, one `enqueue_many` of `events_per_call` events per
tenant per call, walked cyclically.  After the window every tenant's table
is read back through the service at a hash-sampled 1/32 of the keys and
checked against exact counts, and the events the service's key routing
dropped are reported (`dropped`, from its `routed_dropped` counters)."""
import numpy as np

import harness
import streams


def trigram_events(tokens: np.ndarray) -> np.ndarray:
    """Unigram, bigram and trigram of each of the first len - 2 positions,
    in that order: events 3i, 3i+1, 3i+2.  Keys are the bigram combine
    applied twice, bigram_keys(bigram_keys(a, b), c)."""
    n = tokens.size - 2
    bi = streams.bigram_keys(tokens[:-1], tokens[1:])
    ev = np.empty(3 * n, np.uint32)
    ev[0::3] = tokens[:n]
    ev[1::3] = bi[:n]
    ev[2::3] = streams.bigram_keys(bi[:n], tokens[2:])
    return ev


class Ngram3Ingest(harness.ClosedIngest):

    def setup(self) -> None:
        c = self.cfg["corpus"]
        tokens = streams.corpus_tokens(
            self.rng, int(self.cfg["stream"]["corpus_tokens"]),
            int(c["vocab_size"]), float(c["zipf_s"]), float(c["zipf_q"]),
            float(c["p_copy"]), int(c["copy_len"]))
        self.base = trigram_events(tokens)
        # each tenant reads the stream rotated by a whole number of
        # positions of its own, its keys xor-salted (as corpus_streams)
        offs = self.rng.integers(0, self.base.size // 3,
                                 size=len(self.names)) * 3
        self.streams = np.stack([np.roll(self.base, -int(o))
                                 ^ streams.tenant_salt(t)
                                 for t, o in enumerate(offs)])
        self.n = int(self.trf["events_per_call"])
        if self.streams.shape[1] % self.n:
            raise ValueError("events_per_call must divide the stream")
        self.slices = self.streams.shape[1] // self.n
        self.calls = 0

    def compare(self) -> dict:
        out = super().compare()
        out["dropped"] = sum(
            int(self.svc.metrics.counter("routed_dropped",
                                         plane=p.label).value)
            for p in self.svc.planes)
        return out
