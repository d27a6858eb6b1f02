"""Count-Min / Count-Min-Log sketch with conservative update.

Two update paths share one data structure:

  * `update_exact`   — lax.scan, one event at a time.  Bit-faithful to the
    paper's Algorithm 1 (each event observes every previous update).  Used
    for the paper-figure reproductions and as the oracle for everything else.
  * `update_batched` — TPU-native: sort keys, segment-dedup, per-unique-key
    n-fold Morris increment, conservative write via scatter-max.  Cross-key
    collisions inside one batch resolve by max, i.e. conservative update at
    batch granularity.  Statistical divergence from `update_exact` is
    measured in benchmarks/bench_batched_divergence.py.

The sketch is a pytree (table leaf + static spec), so it checkpoints, shards
and jits like any model state.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.counters import CounterSpec, pack_table, unpack_table
from repro.core.hashing import make_row_seeds, row_hashes

_KEY_MAX = 0xFFFF_FFFF


def as_uint32_keys(keys) -> np.ndarray:
    """Validate and normalize event/probe keys to a flat uint32 array.

    The shared API-boundary helper (`CountService.enqueue`/`query`,
    `admission.observe_and_admit`): floats, negatives, and values past 32
    bits are rejected instead of being silently truncated by a blind
    uint32 cast.  Host-side (NumPy) — callers inside a trace skip it.
    """
    arr = np.asarray(keys)
    if arr.dtype == np.uint32:
        return arr.ravel()
    if not np.issubdtype(arr.dtype, np.integer):
        raise TypeError(f"keys must be integers, got dtype {arr.dtype}")
    flat = arr.ravel()
    if flat.size:
        lo, hi = flat.min(), flat.max()
        if lo < 0:
            raise ValueError(f"keys must be non-negative, got {lo}")
        if hi > _KEY_MAX:
            raise ValueError(f"keys must fit in 32 bits, got {hi}")
    return flat.astype(np.uint32)


@dataclasses.dataclass(frozen=True)
class SketchSpec:
    """Static sketch geometry: d rows x w columns of `counter` cells.

    With packed=True the table is STORED as uint32 lanes holding
    `counter.cells_per_lane` cells each (4x uint8 / 2x uint16), so a log8
    cell really occupies one byte end-to-end; hashing, queries and
    estimates are unchanged — width stays the LOGICAL cell count and the
    packed path is bit-identical to the unpacked one.
    """

    width: int
    depth: int = 2
    counter: CounterSpec = CounterSpec()
    seed: int = 0x5EED
    packed: bool = False

    def __post_init__(self):
        if self.width >= 2 ** 31:
            raise ValueError(
                f"width {self.width} must be below 2^31: row_hashes casts "
                "columns to int32, so a wider row would index the wrong "
                "cells")
        if self.packed and self.width % self.counter.cells_per_lane:
            raise ValueError(
                f"packed width {self.width} must be a multiple of "
                f"cells_per_lane {self.counter.cells_per_lane}")

    @property
    def cells_per_lane(self) -> int:
        """Cells per uint32 storage lane (1 unless packed)."""
        return self.counter.cells_per_lane if self.packed else 1

    @property
    def storage_width(self) -> int:
        """Last-axis length of the stored table (lanes, not cells)."""
        return self.width // self.cells_per_lane

    @property
    def storage_dtype(self):
        return jnp.uint32 if self.packed else self.counter.dtype

    @property
    def memory_bytes(self) -> int:
        return self.width * self.depth * (self.counter.bits // 8)

    @classmethod
    def from_memory(cls, budget_bytes: int, depth: int = 2,
                    counter: CounterSpec = CounterSpec(), seed: int = 0x5EED,
                    packed: bool = False) -> "SketchSpec":
        """Paper-style sizing: fixed byte budget, width derived from cell size.

        Widths are rounded down to a lane-aligned multiple so the table
        fits the Pallas kernels (TPU vector lanes are 128 wide): 128 cells
        unpacked, 128 * cells_per_lane for packed formats (a packed lane
        row must hold a whole number of 128-lane vectors).  memory_bytes
        stays exact — the budget is met by the rounded width, never
        silently over-allocated.
        """
        cpl = counter.cells_per_lane if packed else 1
        align = 128 * cpl
        width = max(1, budget_bytes // (depth * (counter.bits // 8)))
        if width >= align:
            width -= width % align
        elif packed:
            width = max(cpl, width - width % cpl)
        return cls(width=width, depth=depth, counter=counter, seed=seed,
                   packed=packed)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Sketch:
    table: jnp.ndarray  # (depth, width) counter states
    spec: SketchSpec    # static

    def tree_flatten(self):
        return (self.table,), self.spec

    @classmethod
    def tree_unflatten(cls, spec, leaves):
        return cls(table=leaves[0], spec=spec)

    @property
    def row_seeds(self) -> jnp.ndarray:
        return make_row_seeds(self.spec.seed, self.spec.depth)


def init(spec: SketchSpec) -> Sketch:
    table = jnp.zeros((spec.depth, spec.storage_width),
                      dtype=spec.storage_dtype)
    return Sketch(table=table, spec=spec)


def logical_table(table: jnp.ndarray, spec: SketchSpec) -> jnp.ndarray:
    """Stored table -> (..., d, width) cell states in the counter dtype."""
    if not spec.packed:
        return table
    return unpack_table(table, spec.counter.bits).astype(spec.counter.dtype)


def storage_table(table: jnp.ndarray, spec: SketchSpec) -> jnp.ndarray:
    """Logical cell states -> the stored layout (uint32 lanes if packed)."""
    if not spec.packed:
        return table
    return pack_table(table, spec.counter.bits)


# --------------------------------------------------------------------------
# QUERY (paper Alg. 2)
# --------------------------------------------------------------------------

def query_state(sketch: Sketch, keys: jnp.ndarray) -> jnp.ndarray:
    """min_k sk[k, h_k(e)] — raw counter state per key, shape (N,)."""
    cols = row_hashes(keys, sketch.row_seeds, sketch.spec.width)  # (d, N)
    rows = jnp.arange(sketch.spec.depth)[:, None]
    table = logical_table(sketch.table, sketch.spec)
    return table[rows, cols].min(axis=0)


def query(sketch: Sketch, keys: jnp.ndarray) -> jnp.ndarray:
    """Estimated event counts (paper's VALUE of the min state), float32 (N,)."""
    return sketch.spec.counter.decode(query_state(sketch, keys))


def query_stacked(tables: jnp.ndarray, spec: SketchSpec, keys: jnp.ndarray
                  ) -> jnp.ndarray:
    """Vmapped multi-table query: tables (T, d, w), keys (T, N) -> (T, N).

    The pure-jnp reference for `kernels.ops.query_many` (and its fallback
    past the VMEM budget); T is tenants or window buckets.
    """
    def one(table, k):
        return query(Sketch(table=table, spec=spec), k)

    return jax.vmap(one)(tables, keys)


# --------------------------------------------------------------------------
# UPDATE — exact sequential semantics (paper Alg. 1)
# --------------------------------------------------------------------------

def update_exact(sketch: Sketch, keys: jnp.ndarray, rng: jax.Array) -> Sketch:
    """Process events one at a time with conservative update.

    keys: (N,) integer event ids. rng: PRNG key driving IncreaseDecision.
    """
    spec = sketch.spec
    counter = spec.counter
    seeds = sketch.row_seeds
    rows = jnp.arange(spec.depth)
    uniforms = jax.random.uniform(rng, (keys.shape[0],))

    sat = jnp.asarray(counter.max_state, dtype=counter.dtype)

    def step(table, ev):
        key, u = ev
        cols = row_hashes(key[None], seeds, spec.width)[:, 0]  # (d,)
        cur = table[rows, cols]                                # (d,)
        cmin = cur.min()
        inc = u < counter.increase_prob(cmin)
        # conservative update: only cells sitting at the min move, and only
        # if the probabilistic increase decision fired and we're not saturated.
        bump = inc & (cur == cmin) & (cmin != sat)
        new = jnp.where(bump, cur + 1, cur).astype(table.dtype)
        return table.at[rows, cols].set(new), None

    table, _ = jax.lax.scan(step, logical_table(sketch.table, spec),
                            (keys, uniforms))
    return Sketch(table=storage_table(table, spec), spec=spec)


# --------------------------------------------------------------------------
# UPDATE — batched TPU-native path
# --------------------------------------------------------------------------

def _dedup(keys: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort + segment-count. Returns (sorted_keys, n_at_first_occurrence).

    n is the multiplicity at each segment's first position and 0 elsewhere,
    so downstream writes become no-ops for duplicate rows (masked by n == 0).
    """
    return dedup_weighted(keys, jnp.ones(keys.shape, jnp.float32))


def dedup_weighted(keys: jnp.ndarray, weights: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Weighted dedup: sort keys, sum each key's weights at its first slot.

    Returns (sorted_keys, total_weight_at_first_occurrence); duplicates and
    zero-weight entries carry weight 0, i.e. they are no-ops downstream.
    vmap-safe, so stacked multi-tenant batches dedup in one shot.
    """
    n = keys.shape[0]
    order = jnp.argsort(keys)
    sorted_keys = keys[order]
    w_sorted = weights[order].astype(jnp.float32)
    start = jnp.concatenate([jnp.ones((1,), bool),
                             sorted_keys[1:] != sorted_keys[:-1]])
    seg = jnp.cumsum(start) - 1
    totals = jax.ops.segment_sum(w_sorted, seg, num_segments=n)
    return sorted_keys, jnp.where(start, totals[seg], 0.0)


def update_batched(sketch: Sketch, keys: jnp.ndarray, rng: jax.Array,
                   weights: jnp.ndarray | None = None,
                   damp_alpha: float = 0.0) -> Sketch:
    """Batch conservative update (sort -> dedup -> n-fold -> scatter-max).

    weights: optional per-event positive weights (e.g. pre-aggregated counts);
    default 1 per event.  Weighted events of equal keys sum before the n-fold
    Morris step, so the estimate stays unbiased.

    damp_alpha > 0 enables a PROTOTYPE of the paper's §4 perspective #2
    ("probabilistic update rule" using the smallest/second-smallest ratio):
    the added mass is scaled by (V(min)+1 / V(2nd-min)+1)^alpha — when the
    rows disagree, the min cell likely already carries collision mass, so
    the update is damped.  Evaluated in benchmarks/bench_damped_update.py;
    biased by construction (reported, not a default).
    """
    spec = sketch.spec
    counter = spec.counter
    n = keys.shape[0]
    if weights is None:
        sk_keys, mult = _dedup(keys)
    else:
        sk_keys, mult = dedup_weighted(keys, weights)

    cols = row_hashes(sk_keys, sketch.row_seeds, spec.width)     # (d, N)
    rows = jnp.arange(spec.depth)[:, None]
    tbl = logical_table(sketch.table, spec)
    cur = tbl[rows, cols]                                        # (d, N)
    cmin = cur.min(axis=0)                                       # (N,)
    if damp_alpha > 0.0 and spec.depth >= 2:
        srt = jnp.sort(cur, axis=0)
        v1 = counter.decode(srt[0])
        v2 = counter.decode(srt[1])
        damp = ((v1 + 1.0) / (v2 + 1.0)) ** damp_alpha
        mult = mult * damp
    u = jax.random.uniform(rng, (n,))
    new_state = counter.nfold(cmin, mult, u)                     # (N,) dtype cells
    # masked rows (mult == 0) write state 0 == a no-op under max
    write = jnp.where(mult > 0, new_state, jnp.zeros_like(new_state))
    write = jnp.broadcast_to(write[None, :], (spec.depth, n))
    tbl = tbl.at[rows, cols].max(write)
    return Sketch(table=storage_table(tbl, spec), spec=spec)


def update(sketch: Sketch, keys: jnp.ndarray, rng: jax.Array,
           mode: str = "batched") -> Sketch:
    if mode == "exact":
        return update_exact(sketch, keys, rng)
    if mode == "batched":
        return update_batched(sketch, keys, rng)
    raise ValueError(f"unknown update mode {mode!r}")


# --------------------------------------------------------------------------
# MERGE — mergeable-summary semantics for distribution
# --------------------------------------------------------------------------

def merge(a: Sketch, b: Sketch, mode: str = "max", rng: jax.Array | None = None
          ) -> Sketch:
    """Combine two sketches built with identical specs.

      max          — elementwise max of states.  For conservative-update
                     sketches this is the standard mergeable lower bound
                     (each cell stays >= either stream's cell).
      estimate_sum — decode both cells to estimate space, add, re-encode
                     (stochastic round if rng given, floor otherwise).
                     Tighter for disjoint streams; the right choice for
                     data-parallel shards that each saw different events.
    """
    if a.spec != b.spec:
        raise ValueError("cannot merge sketches with different specs")
    c = a.spec.counter
    # cell-wise, not lane-wise: a uint32 max over packed lanes is NOT the
    # per-cell max (a high sub-cell shadows the low ones), so both modes
    # operate on the logical table and repack.
    ta = logical_table(a.table, a.spec)
    tb = logical_table(b.table, b.spec)
    if mode == "max":
        table = jnp.maximum(ta, tb)
    elif mode == "estimate_sum":
        v = c.decode(ta) + c.decode(tb)
        table = c.reencode_stochastic(v, rng).astype(ta.dtype)
    else:
        raise ValueError(f"unknown merge mode {mode!r}")
    return Sketch(table=storage_table(table, a.spec), spec=a.spec)
