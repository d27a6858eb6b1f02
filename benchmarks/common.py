"""Shared benchmark substrate: the paper's corpus + counting runs."""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_sketch import CFG as PAPER
from repro.core import sketch as sk
from repro.data import corpus, ngrams

def mode_methodology() -> dict:
    """Device fields every suite embeds in its methodology block: the
    platform and device kind JAX reports, and how many devices it sees.
    Off-TPU the Pallas kernels run in interpret mode (`kernels.ops`)."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def format_methodology(spec) -> dict:
    """Cell-format fields for a suite's methodology block.

    The kernels move tables as 32-bit device lanes, so an UNPACKED cell
    occupies a full 4-byte lane regardless of `counter.bits`; packed
    storage fits `cells_per_lane` cells per lane (1 byte/cell for log8,
    2 for log16).  `table_bytes_streamed` is what one full table sweep —
    a dense flush or whole-plane query — moves per tenant.
    """
    return {"counter_bits": spec.counter.bits, "packed": spec.packed,
            "bytes_per_cell": 4.0 / spec.cells_per_lane,
            "table_bytes_streamed": 4 * spec.depth * spec.storage_width}


@functools.lru_cache(maxsize=2)
def paper_corpus(n_tokens: int = 500_000):
    """The calibrated 500k-token corpus + exact reference counts."""
    toks = corpus.generate(corpus.CorpusSpec(n_tokens=n_tokens))
    events = ngrams.event_stream(toks)
    uniq, true = ngrams.exact_counts(events)
    return toks, events, uniq, true


def count_stream(spec, events: np.ndarray, mode: str = "exact",
                 seed: int = 0, chunk: int = 131_072):
    """Feed the event stream through a sketch (chunked to bound memory)."""
    s = sk.init(spec)
    upd = jax.jit(sk.update_exact if mode == "exact" else sk.update_batched)
    rng = jax.random.PRNGKey(seed)
    for i in range(0, len(events), chunk):
        rng, k = jax.random.split(rng)
        s = upd(s, jnp.asarray(events[i:i + chunk]), k)
    s.table.block_until_ready()
    return s


def are_of(s, uniq: np.ndarray, true: np.ndarray) -> float:
    est = np.asarray(sk.query(s, jnp.asarray(uniq)))
    return float(np.mean(np.abs(est - true) / true))


def timer(fn, *args, warmup: int = 1, iters: int = 3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters, out


def emit(rows: list[dict]) -> None:
    """Print the required CSV: name,us_per_call,derived."""
    for r in rows:
        print(f"{r['name']},{r.get('us_per_call', '')},{r.get('derived', '')}")
