"""Seeded event streams of the benchmark's deployments.

The generator is a copy, so that no later change to the program can move
the yardstick: the n-gram corpus of `repro.data.corpus.generate` and
`repro.data.ngrams` (the paper's unigram + bigram workload, calibrated to
its 20newsgroups slice).  Everything is drawn from one numpy Generator, so
the same seed gives the same events.
"""
from __future__ import annotations

import numpy as np

_C1 = np.uint32(0x85EB_CA6B)
_C2 = np.uint32(0xC2B2_AE35)
_GOLDEN = np.uint32(0x9E37_79B1)


def fmix32(x: np.ndarray) -> np.ndarray:
    """Murmur3 finalizer on uint32 (wraps mod 2^32)."""
    with np.errstate(over="ignore"):
        x = np.asarray(x).astype(np.uint32)
        x = x ^ (x >> np.uint32(16))
        x = x * _C1
        x = x ^ (x >> np.uint32(13))
        x = x * _C2
        x = x ^ (x >> np.uint32(16))
    return x


def tenant_salt(t: int) -> np.uint32:
    """Per-tenant key salt: tenant t counts key ^ salt, a bijection of the
    32-bit key space, so every tenant has keys of its own with the same
    multiplicities."""
    return fmix32(np.array([t + 1], np.uint32) * _GOLDEN)[0]


# ---- the n-gram corpus (copy of repro.data.corpus / repro.data.ngrams) ----

def corpus_tokens(rng: np.random.Generator, n_tokens: int, vocab_size: int,
                  zipf_s: float, zipf_q: float, p_copy: float,
                  copy_len: int) -> np.ndarray:
    """Zipf-Mandelbrot tokens with LZ-style phrase copies (Markovian text:
    phrases repeat, so bigrams repeat as in real text).  Ids are frequency
    ranked (0 = most common)."""
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = 1.0 / (ranks + zipf_q) ** zipf_s
    fresh = rng.choice(vocab_size, size=n_tokens,
                       p=p / p.sum()).astype(np.uint32)
    out = np.empty(n_tokens + 64, dtype=np.uint32)
    out[:256] = fresh[:256]
    pos, fresh_pos = 256, 256
    while pos < n_tokens:
        if rng.random() < p_copy:
            ln = 2 + rng.geometric(1.0 / max(copy_len - 1, 1))
            start = rng.integers(0, pos - ln) if pos > ln else 0
            ln = min(ln, n_tokens + 64 - pos)
            out[pos:pos + ln] = out[start:start + ln]
            pos += ln
        else:
            out[pos] = fresh[fresh_pos % n_tokens]
            fresh_pos += 1
            pos += 1
    return out[:n_tokens]


def bigram_keys(left: np.ndarray, right: np.ndarray) -> np.ndarray:
    """uint32 bigram keys, the combine of `repro.core.hashing.combine2`."""
    a = left.astype(np.uint32)
    b = right.astype(np.uint32)
    with np.errstate(over="ignore"):
        return fmix32(a * _GOLDEN + fmix32(b ^ _C1))


def ngram_events(tokens: np.ndarray) -> np.ndarray:
    """The paper's update stream in arrival order: unigram t_i, then bigram
    (t_i, t_i+1), for each position i.  Event 2i is unigram i, event 2i+1
    the bigram that starts there, so a bigram and both its unigrams sit at
    2i, 2i+1, 2i+2."""
    ev = np.empty(2 * tokens.size - 1, np.uint32)
    ev[0::2] = tokens
    ev[1::2] = bigram_keys(tokens[:-1], tokens[1:])
    return ev

