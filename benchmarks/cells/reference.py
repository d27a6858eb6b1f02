"""The plain reference: exact counts of the seeded stream, and the comparison
that decides `correct`.

It imports nothing of the program.  Exact counts come from the pool's known
multiplicities: every cell cycles a pool of batches generated from the
seed, the harness counts how often each batch was sent, and a key's exact
count is that tally times the key's multiplicity in each batch.  So the
reference runs after the window, over a sample of keys, in seconds.

The sampling of keys by hash and the relative-error arithmetic are copied
from `repro.obs.AccuracyProbe`.
"""
from __future__ import annotations

import numpy as np

from streams import fmix32

SAMPLE_SALT = np.uint32(0xA11C_E5ED)


def hash_sampled(keys: np.ndarray, rate: float) -> np.ndarray:
    """Mask of the keys in the hash-sampled slice (the same keys every run:
    a key is in or out by its value alone)."""
    thr = np.uint32(min(int(rate * 2.0 ** 32), 2 ** 32 - 1))
    return (fmix32(keys) ^ SAMPLE_SALT) < thr


def counts_in(sorted_keys: np.ndarray, probes: np.ndarray) -> np.ndarray:
    """Exact multiplicity of each probe in a sorted key multiset."""
    lo = np.searchsorted(sorted_keys, probes, side="left")
    hi = np.searchsorted(sorted_keys, probes, side="right")
    return (hi - lo).astype(np.int64)


def multiplicities(batches: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """(P, K) counts of each of `keys` in each of the P batches (P, n)."""
    return np.stack([counts_in(np.sort(b), keys) for b in batches])


def relative_errors(est: np.ndarray, exact: np.ndarray) -> np.ndarray:
    """|estimate - exact| / exact, for keys with exact >= 1."""
    exact = np.asarray(exact, np.float64)
    if np.any(exact < 1):
        raise ValueError("the comparison is over keys that were sent")
    return np.abs(np.asarray(est, np.float64) - exact) / exact


def trimmed_mean(e: np.ndarray) -> float:
    """Mean of all but the 0.1% largest of `e` (at least one kept)."""
    return float(np.sort(e)[:max(1, int(e.size * 0.999))].mean())


def group_errors(groups) -> dict:
    """`are`: mean relative error over every compared answer; `worst_are`:
    the worst group's (one read, one step or one tenant) mean;
    `worst_median_re`: the worst group's median; `worst_trimmed_are`: the
    worst group's mean over all but its 0.1% largest errors.  A group
    whose answers were altered shows in the last three even when it is one
    of many; the last two are the ones that a single key colliding with
    heavy keys in every row cannot move."""
    errs = [relative_errors(e, x) for e, x in groups]
    if not errs:
        raise ValueError("nothing was compared")
    allv = np.concatenate(errs)
    return {"are": float(allv.mean()),
            "worst_are": float(max(e.mean() for e in errs)),
            "worst_median_re": float(max(np.median(e) for e in errs)),
            "worst_trimmed_are": max(trimmed_mean(e) for e in errs),
            "compared": int(allv.size)}
