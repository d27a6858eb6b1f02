"""Jit'd public wrappers around the Pallas sketch kernels.

These take/return `repro.core.sketch.Sketch` pytrees and handle host-side
prep (dedup, RNG, padding) so callers can swap `core.sketch.query/update`
for the kernel path with one import.  On non-TPU backends the kernels run
in interpret mode (bit-identical semantics, used for validation).

Both halves of the hot path are fused across the leading axis: ingest via
`update_many` (T tenants, one launch) — or `update_rows` when only R of T
rows have pending work (the active-row flush: SMEM row map, grid (R,
chunk), bit-identical tables) — and the read path via `query_many`
(T tenants) / `window_query_tables` (B window buckets with the weighted
sum/max reduction — and lazy gamma^age decay — inside the kernel).  The
ingest queue itself is device-resident: `queue_append` lands microbatches
in the (T, capw) ring with one scatter-append launch (ring donated, fill
mirrored on the host), and `queue_weights` turns the host fill mirror into
the flush mask without ever shipping the ring back.

The flush itself is a SINGLE-LAUNCH EPOCH: `update_score_rows` fuses the
active-row conservative update with the heavy-hitter candidate re-query
(the table block is scored while still VMEM-resident), and
`window_query_stacked` refreshes every flushed window tenant's tracker
with one multi-ring launch.  Every wrapper here tallies its dispatches
into the active `audit_scope()` tallies (plus the default
`launch_counts()` scope) so launch-count claims are auditable.

Engine selection follows the platform and the table size, never a user
flag.  On TPU every "auto" choice takes the jitted XLA/jnp engine: none
of the Pallas kernels lowers for v5e yet (rank-1 VMEM gathers, (1, capw)
queue blocks, vector loads from SMEM), so a kernel returns to "auto"
only once it compiles there and measures faster.  Off-TPU the kernels
run in interpret mode and "auto" keeps the selection the parity tests
are written against: the kernel when the table fits VMEM, the jnp
fallback past it, and the XLA reference for the flush epoch and the
queue append (interpreter-mode Pallas would tax those hot paths with
per-block emulation cost).  An explicit `engine="kernel"` always runs
the kernel, and on TPU surfaces whatever the compiler raises.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sketch as sk
from repro.core.hashing import host_row_seeds
from repro.kernels import ref
from repro.kernels.sketch import (CHUNK, LANES, _shift_to_fill,
                                  fused_query_pallas, fused_update_pallas,
                                  fused_update_rows_pallas,
                                  fused_update_score_pallas, query_pallas,
                                  queue_append_dense_pallas,
                                  queue_append_pallas, update_pallas,
                                  window_query_pallas,
                                  window_query_stacked_pallas,
                                  window_query_stacked_rows_pallas)

# VMEM budget the resident-table strategy is valid for (per TPU core).
VMEM_TABLE_LIMIT = 12 * 1024 * 1024

# Per-op dispatch tally: every public wrapper below bumps its name once
# per successful call — AFTER argument validation, whichever engine
# (kernel, XLA reference, or past-VMEM jnp fallback) ends up serving the
# dispatch — so callers (the service, the benchmarks) can AUDIT dispatch
# counts: "the flush epoch is one launch" is a measured number in
# results/bench_topk.json, not prose.  Each dispatch is also tallied under
# the (op, engine) that served it, so a run can show which engine the
# platform and table size selected.
#
# Tallies are CONTEXT-SCOPED: `audit_scope()` pushes a fresh Counter that
# sees exactly the dispatches issued while it is active (scopes nest —
# every active scope is bumped), so two benchmark suites in one process
# audit independent windows instead of sharing one module global whose
# reset races between them.  `_SCOPES[0]` is the process-default scope;
# `launch_counts()` / `reset_launch_counts()` are thin views over it for
# callers that predate scoping.


class audit_scope:
    """Context manager scoping a dispatch tally to one with-block.

        with ops.audit_scope() as tally:
            svc.flush()
        assert dict(tally) == {"update_score_rows": 1}

    The yielded Counter keeps its final counts after exit (read it any
    time); concurrent/nested scopes each see every dispatch issued while
    they were active and nothing from outside their window.  `engines`
    counts the same dispatches keyed by (op, engine), engine one of
    "kernel", "xla" or "jnp".
    """

    def __init__(self):
        self.tally = collections.Counter()
        self.engines = collections.Counter()

    def __enter__(self) -> collections.Counter:
        _SCOPES.append(self)
        return self.tally

    def __exit__(self, *exc) -> None:
        _SCOPES.remove(self)  # scopes compare by identity


_SCOPES: list[audit_scope] = [audit_scope()]


def _launch(name: str, engine: str) -> None:
    for scope in _SCOPES:
        scope.tally[name] += 1
        scope.engines[name, engine] += 1


def note_dispatch(name: str, engine: str = "xla") -> None:
    """Tally a dispatch issued outside this module's wrappers (the
    key-routed plane's `shard_map` programs) into the active scopes."""
    _launch(name, engine)


def launch_counts() -> dict[str, int]:
    """Snapshot of the DEFAULT scope's {op: dispatches} since its last
    reset (prefer `audit_scope()` for isolated windows)."""
    return dict(_SCOPES[0].tally)


def reset_launch_counts() -> None:
    _SCOPES[0].tally.clear()
    _SCOPES[0].engines.clear()


def on_tpu() -> bool:
    """Whether the default backend is a TPU (the engine-selection probe)."""
    return jax.default_backend() == "tpu"


def fits_vmem(spec: sk.SketchSpec) -> bool:
    return spec.memory_bytes <= VMEM_TABLE_LIMIT


@functools.lru_cache(maxsize=None)
def _seeds_tuple(spec: sk.SketchSpec) -> tuple:
    # SketchSpec is a frozen dataclass, so the derived row seeds are cached
    # per spec instead of re-derived on every query/update call; computed
    # host-side so the wrappers stay callable under jit/shard_map traces.
    return host_row_seeds(spec.seed, spec.depth)


def _interpret() -> bool:
    return not on_tpu()


def _kernel_auto(spec: sk.SketchSpec) -> bool:
    """Whether "auto" takes a Pallas kernel: off-TPU only, and only for a
    table that fits the VMEM-resident block."""
    return fits_vmem(spec) and not on_tpu()


def query(sketch: sk.Sketch, keys: jnp.ndarray) -> jnp.ndarray:
    """Kernel-path sketch query; the jnp path past VMEM and on TPU."""
    if not _kernel_auto(sketch.spec):
        _launch("query", "jnp")
        return sk.query(sketch, keys)
    _launch("query", "kernel")
    return query_pallas(sketch.table, keys, seeds=_seeds_tuple(sketch.spec),
                        width=sketch.spec.width, counter=sketch.spec.counter,
                        interpret=_interpret(),
                        cpl=sketch.spec.cells_per_lane)


def query_many(tables: jnp.ndarray, spec: sk.SketchSpec, keys: jnp.ndarray
               ) -> jnp.ndarray:
    """Fused multi-tenant query: tables (T, d, w), keys (T, N) or (N,).

    1D keys are broadcast to every tenant (the common serving probe).  All
    T queries land in ONE kernel launch (the per-tenant table is the
    VMEM-resident grid block), bit-consistent with a per-tenant `query`
    loop.  The vmapped jnp query serves past the VMEM budget and on TPU.
    Returns float32 (T, N).
    """
    if keys.ndim == 1:
        keys = jnp.broadcast_to(keys[None, :], (tables.shape[0], keys.shape[0]))
    if keys.shape[0] != tables.shape[0]:
        # the kernel grids over tables.shape[0] and would leave the extra
        # output tiles unwritten — fail loudly instead
        raise ValueError(f"per-tenant keys need {tables.shape[0]} rows, "
                         f"got {keys.shape[0]}")
    if not _kernel_auto(spec):
        _launch("query_many", "jnp")
        return sk.query_stacked(tables, spec, keys)
    _launch("query_many", "kernel")
    return fused_query_pallas(tables, keys, seeds=_seeds_tuple(spec),
                              width=spec.width, counter=spec.counter,
                              interpret=_interpret(),
                              cpl=spec.cells_per_lane)


@functools.partial(jax.jit, static_argnames=("spec",))
def _query_row_xla(tables, row, keys, *, spec):
    # the eager `sk.query` arithmetic over row `row` of the stack, with the
    # row inside the gather: no per-row table is sliced out first
    cols = sk.row_hashes(keys, _row_seeds_array(spec), spec.width)  # (d, N)
    rows = jnp.arange(spec.depth)[:, None]
    if spec.packed:
        vals = sk.logical_table(tables[row], spec)[rows, cols]
    else:
        vals = tables[row, rows, cols]
    return spec.counter.decode(vals.min(axis=0))


@functools.lru_cache(maxsize=4096)
def _row_index(row: int) -> jax.Array:
    # a row index as a device int32 scalar, uploaded once: handed over as a
    # host scalar it would cost every read a transfer of its own
    return jax.device_put(np.int32(row))


def query_row(tables: jnp.ndarray, spec: sk.SketchSpec, row: int,
              keys: jnp.ndarray) -> jnp.ndarray:
    """One tenant's query over a (T, d, w) stack: keys (N,) -> float32 (N,).

    Off-TPU within VMEM the Pallas query over the row's table.  Otherwise
    ONE jitted XLA program that hashes, gathers `tables[row]`'s cells, takes
    the min and decodes; the row is a traced argument, so every row of a
    stack shares one compile per probe length.  Bit-identical to
    `sk.query` on `tables[row]`.  Tallied as "query".
    """
    if _kernel_auto(spec):
        _launch("query", "kernel")
        return query_pallas(tables[row], keys, seeds=_seeds_tuple(spec),
                            width=spec.width, counter=spec.counter,
                            interpret=_interpret(),
                            cpl=spec.cells_per_lane)
    _launch("query", "xla")
    if isinstance(row, (int, np.integer)):
        row = _row_index(int(row))
    return _query_row_xla(tables, row, keys, spec=spec)


def window_query_tables(tables: jnp.ndarray, spec: sk.SketchSpec,
                        keys: jnp.ndarray, weights: jnp.ndarray,
                        mode: str = "sum", engine: str = "auto"
                        ) -> jnp.ndarray:
    """Weighted window reduction over a bucket ring: ONE fused launch.

    tables (B, d, w) bucket ring, keys (N,), weights (B,) per-bucket
    estimate weights (0 = expired, gamma^age = lazy decay).  mode "sum"
    or "max".  engine: "kernel" forces the Pallas path, "jnp" the pure-jnp
    reference (used inside collectives), "auto" picks the kernel when the
    bucket table fits VMEM off-TPU and the jnp engine otherwise.  The jnp
    engine is the stacked reference at R=1 (`ref.window_query_stacked_ref`),
    so the per-ring fallback and the stacked tracker-refresh fallback
    share ONE accumulation order — in-order over buckets, matching the
    kernel grid.  Returns float32 (N,).
    """
    if mode not in ("sum", "max"):
        raise ValueError(f"unknown window query mode {mode!r}")
    if engine not in ("auto", "kernel", "jnp"):
        raise ValueError(f"unknown query engine {engine!r}")
    if weights.shape != (tables.shape[0],):
        raise ValueError(f"need one weight per bucket: weights "
                         f"{weights.shape} vs {tables.shape[0]} buckets")
    if engine == "auto":
        engine = "kernel" if _kernel_auto(spec) else "jnp"
    _launch("window_query", engine)
    if engine == "jnp":
        return ref.window_query_stacked_ref(
            tables[None], keys[None], weights[None], _row_seeds_array(spec),
            spec.counter, mode=mode, cpl=spec.cells_per_lane)[0]
    return window_query_pallas(tables, keys, weights,
                               seeds=_seeds_tuple(spec), width=spec.width,
                               counter=spec.counter, mode=mode,
                               interpret=_interpret(),
                               cpl=spec.cells_per_lane)


def update(sketch: sk.Sketch, keys: jnp.ndarray, rng: jax.Array) -> sk.Sketch:
    """Kernel-path batched conservative update (dedup + n-fold + scatter-max).

    Past the VMEM budget the one-shot jnp update serves; on TPU a table
    that fits takes the chunk-sequential XLA engine (`update_xla`)."""
    if not fits_vmem(sketch.spec):
        _launch("update", "jnp")
        return sk.update_batched(sketch, keys, rng)
    if on_tpu():
        return update_xla(sketch, keys, rng)
    _launch("update", "kernel")
    sorted_keys, mult = sk._dedup(keys)
    uniforms = jax.random.uniform(rng, sorted_keys.shape)
    table = update_pallas(sketch.table, sorted_keys, mult, uniforms,
                          seeds=_seeds_tuple(sketch.spec),
                          width=sketch.spec.width,
                          counter=sketch.spec.counter,
                          interpret=_interpret(),
                          cpl=sketch.spec.cells_per_lane)
    return sk.Sketch(table=table, spec=sketch.spec)


@functools.partial(jax.jit, static_argnames=("spec",))
def _update_xla_jit(table, keys, rng, *, spec):
    sorted_keys, mult = sk._dedup(keys)
    uniforms = jax.random.uniform(rng, sorted_keys.shape)
    return ref.update_chunked_ref(table, sorted_keys, mult, uniforms,
                                  _row_seeds_array(spec), spec.counter,
                                  CHUNK, cpl=spec.cells_per_lane)


def update_xla(sketch: sk.Sketch, keys: jnp.ndarray, rng: jax.Array
               ) -> sk.Sketch:
    """Bit-identical XLA engine of `update` (what it runs on TPU): same
    dedup and uniform draw, applied through the
    CHUNK-sequential reference so a key in chunk 2 sees chunk 1's writes
    exactly as the kernel grid does — `sk.update_batched`'s one-shot
    min-read would diverge on cross-chunk cell collisions.
    """
    _launch("update", "xla")
    table = _update_xla_jit(sketch.table, keys, rng, spec=sketch.spec)
    return sk.Sketch(table=table, spec=sketch.spec)


def _parity_uniforms(rng, n_cols: int, total: int, rows):
    """Uniforms for an R-row sub-stack update, bit-identical to the dense
    draw they replace: draw the full (total, n_cols) grid, gather `rows`.

    `total` is the dense row count the update is standing in for, `rows`
    the (R,) active-row subset.  The full-grid draw costs one fused
    computation; it is what makes the active-row flush land exactly the
    counters a dense flush would have.
    """
    return jax.random.uniform(rng, (total, n_cols))[rows]


def _update_stack_xla(tables, keys, weights, rng, urows, *, spec, total):
    """Chunk-sequential XLA engine of the fused update kernels over an
    (R, d, w) stack: vmapped dedup, the parity uniforms grid at `urows`,
    then `ref.update_chunked_ref` per row — bit-identical to the kernel
    grid."""
    sorted_keys, mult = jax.vmap(sk.dedup_weighted)(keys, weights)
    uniforms = _parity_uniforms(rng, keys.shape[1], total, urows)
    seeds = _row_seeds_array(spec)

    def one(table, k, m, u):
        return ref.update_chunked_ref(table, k, m, u, seeds, spec.counter,
                                      CHUNK, cpl=spec.cells_per_lane)
    return jax.vmap(one)(tables, sorted_keys, mult, uniforms)


_update_stack_xla_jit = jax.jit(_update_stack_xla,
                                static_argnames=("spec", "total"))


def _update_rows_xla(tables, keys, weights, rng, rows, urows, *, spec,
                     total):
    new = _update_stack_xla(tables[rows], keys, weights, rng, urows,
                            spec=spec, total=total)
    return tables.at[rows].set(new)


_update_rows_xla_jit = jax.jit(_update_rows_xla,
                               static_argnames=("spec", "total"))
_update_rows_xla_donated_jit = jax.jit(
    _update_rows_xla, static_argnames=("spec", "total"),
    donate_argnames=("tables",))


# The flush hot path — weighted dedup, uniform draw, fused kernel — runs
# as ONE jitted computation per variant: dispatching the vmapped dedup
# eagerly costs more than the whole (R, chunk) kernel sweep it feeds.

@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def _update_many_jit(tables, keys, weights, rng, *, spec, interpret):
    sorted_keys, mult = jax.vmap(sk.dedup_weighted)(keys, weights)
    uniforms = jax.random.uniform(rng, sorted_keys.shape)
    return fused_update_pallas(tables, sorted_keys, mult, uniforms,
                               seeds=_seeds_tuple(spec), width=spec.width,
                               counter=spec.counter, interpret=interpret,
                               cpl=spec.cells_per_lane)


@functools.partial(jax.jit, static_argnames=("spec", "total", "interpret"))
def _update_gathered_jit(tables, keys, weights, rng, rows, *, spec, total,
                         interpret):
    """Dense kernel over an already-gathered R-row stack (the window
    plane's active buckets), with the parity uniforms grid."""
    sorted_keys, mult = jax.vmap(sk.dedup_weighted)(keys, weights)
    uniforms = _parity_uniforms(rng, keys.shape[1], total, rows)
    return fused_update_pallas(tables, sorted_keys, mult, uniforms,
                               seeds=_seeds_tuple(spec), width=spec.width,
                               counter=spec.counter, interpret=interpret,
                               cpl=spec.cells_per_lane)


def _update_rows_impl(tables, keys, weights, rng, rows, urows, *, spec,
                      total, interpret):
    sorted_keys, mult = jax.vmap(sk.dedup_weighted)(keys, weights)
    uniforms = _parity_uniforms(rng, keys.shape[1], total, urows)
    return fused_update_rows_pallas(tables, sorted_keys, mult, uniforms,
                                    rows, seeds=_seeds_tuple(spec),
                                    width=spec.width, counter=spec.counter,
                                    interpret=interpret,
                                    cpl=spec.cells_per_lane)


_update_rows_jit = jax.jit(
    _update_rows_impl, static_argnames=("spec", "total", "interpret"))
# donated twin: the window plane flushes its resident (T*B, d, w) leaf
# through this — the old buffer is dead the moment the epoch lands, so
# donation lets XLA alias it in place instead of materializing a copy
_update_rows_donated_jit = jax.jit(
    _update_rows_impl, static_argnames=("spec", "total", "interpret"),
    donate_argnames=("tables",))


def update_many(tables: jnp.ndarray, spec: sk.SketchSpec, keys: jnp.ndarray,
                rng: jax.Array, weights: jnp.ndarray | None = None,
                uniform_rows=None) -> jnp.ndarray:
    """Fused multi-tenant update: tables (T, d, w), keys/weights (T, N).

    Dedups each tenant's stream (vmapped), then lands all T updates in ONE
    kernel launch (the per-tenant table is the VMEM-resident grid block);
    dedup + uniform draw + kernel run as a single jitted computation.
    Entries with weight 0 are no-ops — ragged tenant queues pad with them.
    Falls back to a vmapped jnp update for tables past the VMEM budget; on
    TPU a table that fits takes the chunk-sequential XLA engine, which
    lands the kernel's bits.

    uniform_rows: optional (total, rows) pair — draw the uniforms over a
    (total, N) grid and gather `rows`, so updating an R-row sub-stack
    (e.g. the gathered active window buckets of an active-row flush) is
    bit-identical to the dense total-row update it replaces.
    """
    if weights is None:
        weights = jnp.ones(keys.shape, jnp.float32)
    if not fits_vmem(spec):
        _launch("update_many", "jnp")
        if uniform_rows is None:
            rngs = jax.random.split(rng, tables.shape[0])
        else:
            total, rows = uniform_rows
            rngs = jax.random.split(rng, total)[np.asarray(rows)]

        def one(table, k, w, r):
            s = sk.Sketch(table=table, spec=spec)
            return sk.update_batched(s, k, r, weights=w).table
        return jax.vmap(one)(tables, keys, weights, rngs)
    if on_tpu():
        _launch("update_many", "xla")
        if uniform_rows is None:
            total, rows = tables.shape[0], np.arange(tables.shape[0])
        else:
            total, rows = uniform_rows
        return _update_stack_xla_jit(tables, keys, weights, rng,
                                     np.asarray(rows, np.int32), spec=spec,
                                     total=int(total))
    _launch("update_many", "kernel")
    if uniform_rows is None:
        return _update_many_jit(tables, keys, weights, rng, spec=spec,
                                interpret=_interpret())
    total, rows = uniform_rows
    return _update_gathered_jit(tables, keys, weights, rng,
                                np.asarray(rows, np.int32), spec=spec,
                                total=int(total), interpret=_interpret())


def update_rows(tables: jnp.ndarray, spec: sk.SketchSpec, keys: jnp.ndarray,
                rng: jax.Array, rows, weights: jnp.ndarray | None = None,
                uniform_rows=None, donate: bool = False) -> jnp.ndarray:
    """Active-row fused update: land R rows' batches without touching the
    other T - R tables.

    tables (T, d, w); keys/weights (R, N); rows (R,) int32 selecting each
    batch's target row (unique within a call).  The kernel grids over
    (R, chunk) with the row map in SMEM and the whole (T, d, w) stack
    aliased in place (`fused_update_rows_pallas`), so a skewed flush pays
    for the rows that actually have work.  Uniforms are drawn over the
    FULL (T, N) grid and gathered, making the result bit-identical to
    `update_many` fed the whole plane with the inactive rows' weights
    zeroed — the active-row flush can replace the dense flush without
    changing a single landed counter.  Falls back to a vmapped jnp update
    + row scatter past the VMEM budget; on TPU a table that fits takes the
    chunk-sequential XLA engine (gather, update, scatter in one jitted
    computation, bit-identical to the kernel).

    uniform_rows: optional (total, urows) pair decoupling the parity
    uniform draw from the kernel row map — the window plane updates flat
    rows `tenant * B + cursor` of its reshaped (T*B, d, w) leaf while
    drawing uniforms over the (T, N) TENANT grid gathered at `urows`, so
    the native flush lands bit-identical counters to the legacy
    restack-and-`update_many` epoch it replaces.

    donate=True donates `tables` to the computation (the caller must drop
    its reference): XLA aliases the update in place, which is what makes
    the resident window leaf's flush epoch zero-copy.
    """
    rows = np.asarray(rows, np.int32)
    if uniform_rows is None:
        total, urows = tables.shape[0], rows
    else:
        total, urows = uniform_rows
        urows = np.asarray(urows, np.int32)
    if weights is None:
        weights = jnp.ones(keys.shape, jnp.float32)
    if not fits_vmem(spec):
        _launch("update_rows", "jnp")
        rngs = jax.random.split(rng, int(total))[urows]

        def one(table, k, w, r):
            s = sk.Sketch(table=table, spec=spec)
            return sk.update_batched(s, k, r, weights=w).table
        new = jax.vmap(one)(tables[rows], keys, weights, rngs)
        return tables.at[rows].set(new)
    if on_tpu():
        _launch("update_rows", "xla")
        fn = _update_rows_xla_donated_jit if donate else _update_rows_xla_jit
        return fn(tables, keys, weights, rng, rows, urows, spec=spec,
                  total=int(total))
    _launch("update_rows", "kernel")
    fn = _update_rows_donated_jit if donate else _update_rows_jit
    return fn(tables, keys, weights, rng, rows, urows, spec=spec,
              total=int(total), interpret=_interpret())


# --------------------------------------------------------------------------
# single-launch flush epoch: fused update + candidate re-score
# --------------------------------------------------------------------------

def _row_seeds_array(spec: sk.SketchSpec) -> jnp.ndarray:
    return jnp.asarray(_seeds_tuple(spec), jnp.uint32)


@functools.partial(jax.jit, static_argnames=("spec", "total", "interpret"))
def _update_score_rows_kernel_jit(tables, keys, weights, rng, rows, urows,
                                  cand, *, spec, total, interpret):
    sorted_keys, mult = jax.vmap(sk.dedup_weighted)(keys, weights)
    uniforms = _parity_uniforms(rng, keys.shape[1], total, urows)
    return fused_update_score_pallas(tables, sorted_keys, mult, uniforms,
                                     cand, rows, seeds=_seeds_tuple(spec),
                                     width=spec.width, counter=spec.counter,
                                     interpret=interpret,
                                     cpl=spec.cells_per_lane)


@functools.partial(jax.jit, static_argnames=("spec", "total"))
def _update_score_rows_xla_jit(tables, keys, weights, rng, rows, urows, cand,
                               *, spec, total):
    sorted_keys, mult = jax.vmap(sk.dedup_weighted)(keys, weights)
    uniforms = _parity_uniforms(rng, keys.shape[1], total, urows)
    return ref.update_score_rows_ref(tables, sorted_keys, mult, uniforms,
                                     rows, cand, _row_seeds_array(spec),
                                     spec.counter, CHUNK,
                                     cpl=spec.cells_per_lane)


def _update_score_rows_inplace(tables, keys, weights, rng, rows, urows,
                               cand, *, spec, total):
    sorted_keys, mult = jax.vmap(sk.dedup_weighted)(keys, weights)
    uniforms = _parity_uniforms(rng, keys.shape[1], total, urows)
    return ref.update_score_rows_inplace_ref(tables, sorted_keys, mult,
                                             uniforms, rows, cand,
                                             _row_seeds_array(spec),
                                             spec.counter, CHUNK)


def update_score_rows(tables: jnp.ndarray, spec: sk.SketchSpec,
                      keys: jnp.ndarray, rng: jax.Array, rows,
                      cand: jnp.ndarray,
                      weights: jnp.ndarray | None = None,
                      uniform_rows=None, engine: str = "auto"):
    """Single-launch flush epoch: active-row conservative update PLUS the
    heavy-hitter candidate re-query, one fused computation.

    tables (T, d, w); keys/weights (R, N) active-row microbatches; rows
    (R,) int32 target rows (unique within a call); cand (R, M) each row's
    candidate keys (standing heap + flushed batch).  Tables update exactly
    as `update_rows` (full-grid parity uniforms — bit-identical to the
    dense flush), and the returned float32 (R, M) estimates equal a
    `query_many` over the updated gathered rows — but the table block is
    only fetched once: the kernel re-scores while it is still
    VMEM-resident (`fused_update_score_pallas`).

    uniform_rows: optional (total, urows) pair decoupling the parity
    uniform draw from the kernel row map, exactly as in `update_rows` —
    a tiered plane updates hot SLOTS of its (H, d, w) device stack while
    drawing uniforms over the full TENANT grid gathered at `urows`, so a
    hot-tier epoch lands bit-identical counters to the all-resident
    flush it replaces.  Default: the dense grid over `tables` at `rows`.

    engine: "kernel" forces the Pallas path, "xla" the jitted reference
    (`ref.update_score_rows_ref` — chunk-sequential, bit-identical), and
    "auto" takes the XLA reference on every platform (see the module
    docstring).  "inplace" is the key-routed epoch's engine, called inside
    its `shard_map` trace on one shard's stack (`rows` traced too), so no
    dispatch of its own: the same dedup, uniforms and chunk-sequential
    update, through `ref.update_score_rows_inplace_ref`, which writes the
    rows' cells where they lie instead of gathering the rows out and
    scattering them back, so a donated stack too large to copy is updated
    in place; bit-identical to "xla" (unpacked stacks only).  Returns
    (new_tables, estimates).
    """
    if engine not in ("auto", "kernel", "xla", "inplace"):
        raise ValueError(f"unknown update_score engine {engine!r}")
    if engine == "inplace":
        if spec.packed:
            raise ValueError("the in-place engine updates unpacked stacks")
        total, urows = ((tables.shape[0], rows) if uniform_rows is None
                        else uniform_rows)
        if weights is None:
            weights = jnp.ones(keys.shape, jnp.float32)
        return _update_score_rows_inplace(tables, keys, weights, rng, rows,
                                          urows, cand, spec=spec,
                                          total=int(total))
    rows = np.asarray(rows, np.int32)
    if uniform_rows is None:
        total, urows = tables.shape[0], rows
    else:
        total, urows = uniform_rows
        urows = np.asarray(urows, np.int32)
    if weights is None:
        weights = jnp.ones(keys.shape, jnp.float32)
    if engine == "auto":
        engine = "xla"
    if engine == "kernel" and not fits_vmem(spec):
        raise ValueError("table exceeds the VMEM budget; use engine='xla'")
    _launch("update_score_rows", engine)
    if engine == "xla":
        return _update_score_rows_xla_jit(tables, keys, weights, rng, rows,
                                          urows, cand, spec=spec,
                                          total=int(total))
    return _update_score_rows_kernel_jit(tables, keys, weights, rng, rows,
                                         urows, cand, spec=spec,
                                         total=int(total),
                                         interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("spec", "mode"))
def _window_query_stacked_xla_jit(tables, keys, weights, *, spec, mode):
    return ref.window_query_stacked_ref(tables, keys, weights,
                                        _row_seeds_array(spec), spec.counter,
                                        mode=mode, cpl=spec.cells_per_lane)


@functools.partial(jax.jit, static_argnames=("spec", "mode"))
def _window_query_stacked_rows_xla_jit(tables, keys, weights, rows, *, spec,
                                       mode):
    return ref.window_query_stacked_rows_ref(
        tables, keys, weights, rows, _row_seeds_array(spec), spec.counter,
        mode=mode, cpl=spec.cells_per_lane)


def window_query_stacked(tables: jnp.ndarray, spec: sk.SketchSpec,
                         keys: jnp.ndarray, weights: jnp.ndarray,
                         mode: str = "sum", engine: str = "auto",
                         rows=None) -> jnp.ndarray:
    """Stacked multi-ring window reduction: R rings, ONE fused launch.

    tables (R, B, d, w) bucket rings; keys (R, N) per-ring probes; weights
    (R, B) per-ring per-bucket estimate weights (0 = expired, gamma^age =
    lazy decay).  The WindowPlane tracker refresh calls this once per
    flush epoch no matter how many tenants flushed — previously one
    `window_query` launch per flushed tenant.

    rows: optional (R,) int32 — query R tenant rings straight off a native
    (T, B, d, w) window-plane leaf (tables' leading axis is then T, keys/
    weights stay R-indexed).  The kernel variant steers its table blocks
    through a scalar-prefetch row map (`window_query_stacked_rows_pallas`)
    and the XLA engine gathers inside the jitted computation, so neither
    path ever restacks rings on the host.

    engine: "auto" follows the per-ring `window_query_tables` policy —
    the kernel whenever the bucket table fits VMEM off-TPU, the reference
    (`ref.window_query_stacked_ref`, which the per-ring jnp fallback also
    runs at R=1) past it and on TPU — NOT the queue-append XLA choice: the
    in-order weighted float accumulation is only bitwise reproducible
    within one engine family (mode="max" and the bucket estimates
    themselves ARE cross-engine bit-identical; the "sum" rounding is
    fusion-dependent at one ulp), and the tracker's stored estimates must
    equal the read path's `window_query` answers exactly.  Returns
    float32 (R, N), bit-identical to R per-ring `window_query` calls.
    """
    if mode not in ("sum", "max"):
        raise ValueError(f"unknown window query mode {mode!r}")
    if engine not in ("auto", "kernel", "xla"):
        raise ValueError(f"unknown window_query_stacked engine {engine!r}")
    n_rings = tables.shape[0] if rows is None else len(rows)
    if keys.shape[0] != n_rings:
        raise ValueError(f"per-ring keys need {n_rings} rows, "
                         f"got {keys.shape[0]}")
    if weights.shape != (n_rings, tables.shape[1]):
        raise ValueError(f"need (R, B) weights: {weights.shape} vs "
                         f"{(n_rings, tables.shape[1])}")
    interpret = _interpret()
    if engine == "auto":
        engine = "kernel" if _kernel_auto(spec) else "xla"
    if engine == "kernel" and not fits_vmem(spec):
        raise ValueError("table exceeds the VMEM budget; use engine='xla'")
    _launch("window_query_stacked", engine)
    if rows is not None:
        rows = jnp.asarray(np.asarray(rows, np.int32))
        if engine == "xla":
            return _window_query_stacked_rows_xla_jit(tables, keys, weights,
                                                      rows, spec=spec,
                                                      mode=mode)
        return window_query_stacked_rows_pallas(
            tables, keys, weights, rows, seeds=_seeds_tuple(spec),
            width=spec.width, counter=spec.counter, mode=mode,
            interpret=interpret, cpl=spec.cells_per_lane)
    if engine == "xla":
        return _window_query_stacked_xla_jit(tables, keys, weights,
                                             spec=spec, mode=mode)
    return window_query_stacked_pallas(tables, keys, weights,
                                       seeds=_seeds_tuple(spec),
                                       width=spec.width, counter=spec.counter,
                                       mode=mode, interpret=interpret,
                                       cpl=spec.cells_per_lane)


@functools.partial(jax.jit, donate_argnames=("tables",))
def _window_advance_rows_jit(tables, cursors, steps):
    b = tables.shape[1]
    off = (jnp.arange(b, dtype=jnp.int32)[None, :] - cursors[:, None] - 1) % b
    cleared = (off < steps[:, None]) | (steps[:, None] >= b)
    return jnp.where(cleared[:, :, None, None], 0, tables)


def window_advance_rows(tables: jnp.ndarray, cursors, steps) -> jnp.ndarray:
    """Watermark rotation on the native (T, B, d, w) window leaf: advance
    every tenant's ring by its own step count in ONE masked device op.

    tables (T, B, d, w storage) is DONATED (the caller reassigns its
    leaf); cursors/steps (T,) int32 — `steps[t] == 0` leaves tenant t
    untouched, so a mixed advance (only some tenants' watermarks moved)
    is still one dispatch instead of one `window_advance_steps` per
    tenant.  Per row the cleared-bucket mask is exactly
    `stream.window.window_advance_steps`'s: the `steps` buckets after the
    cursor (the ones rotation will reuse) zero, everything clears when
    steps >= B.  The caller owns the host cursor mirror:
    `cursor' = (cursor + steps) % B`.
    """
    _launch("window_advance_rows", "xla")
    return _window_advance_rows_jit(tables,
                                    jnp.asarray(np.asarray(cursors, np.int32)),
                                    jnp.asarray(np.asarray(steps, np.int32)))


# --------------------------------------------------------------------------
# device-resident ingest queue
# --------------------------------------------------------------------------

def ring_width(capacity: int) -> int:
    """Lane-aligned device ring width for a logical queue capacity."""
    return max(LANES, LANES * -(-int(capacity) // LANES))


def queue_init(tenants: int, capacity: int) -> jnp.ndarray:
    """Fresh (T, capw) device ring (uint32 keys, lane-aligned width)."""
    return jnp.zeros((tenants, ring_width(capacity)), jnp.uint32)


@functools.partial(jax.jit, static_argnames=("aligned",),
                   donate_argnames=("queue",))
def _queue_append_rows_xla(queue, keys, meta, *, aligned):
    """XLA reference of `queue_append_pallas`: gather target rows, masked-
    merge the shifted batches, scatter the rows back (ring donated, so XLA
    updates it in place)."""
    rows, fill, count = meta[0], meta[1], meta[2]
    capw = queue.shape[1]
    buf = _shift_to_fill(keys, fill, capw, queue.dtype, aligned)
    cols = jnp.arange(capw, dtype=jnp.int32)[None, :]
    valid = (cols >= fill[:, None]) & (cols < (fill + count)[:, None])
    return queue.at[rows].set(jnp.where(valid, buf, queue[rows]))


# the row append as a building block of a traced program (one shard's ring
# inside the key-routed plane's shard_map append)
queue_append_local = _queue_append_rows_xla


@functools.partial(jax.jit, static_argnames=("aligned",),
                   donate_argnames=("queue",))
def _queue_append_dense_xla(queue, keys, meta, *, aligned):
    """XLA reference of `queue_append_dense_pallas` (whole-plane append)."""
    fill, count = meta[0], meta[1]
    buf = _shift_to_fill(keys, fill, queue.shape[1], queue.dtype, aligned)
    cols = jnp.arange(queue.shape[1], dtype=jnp.int32)[None, :]
    valid = (cols >= fill[:, None]) & (cols < (fill + count)[:, None])
    return jnp.where(valid, buf, queue)


def queue_append(queue: jnp.ndarray, keys: jnp.ndarray, rows, fill, count,
                 engine: str = "auto") -> jnp.ndarray:
    """Append R tenant microbatches to the device ring in ONE launch.

    queue (T, capw) is donated (mutated in place on device); keys (R, N)
    ragged per `count`; rows/fill/count (R,) int32, packed into ONE (3, R)
    scalar array so an append costs a single small host->device transfer
    next to the keys.  The caller tracks fill on the host (it is
    deterministic), so the ring never crosses back to the host — see
    `kernels.sketch.queue_append_pallas`.  A whole-plane append (rows ==
    0..T-1, the batched `enqueue_many` regime) takes the dense whole-block
    variant instead of the row-indirected one.

    engine: "kernel" forces the Pallas path, "xla" the jitted gather/
    merge/scatter reference (bit-identical; what tests cross-check), and
    "auto" takes the XLA reference on every platform (see the module
    docstring).
    """
    if engine not in ("auto", "kernel", "xla"):
        raise ValueError(f"unknown queue_append engine {engine!r}")
    if engine == "auto":
        engine = "xla"
    _launch("queue_append", engine)
    rows = np.asarray(rows, np.int32)
    fill = np.asarray(fill, np.int32)
    count = np.asarray(count, np.int32)
    interpret = _interpret()
    aligned = not fill.any()  # append-right-after-flush: plain masked copy
    if rows.shape[0] == queue.shape[0] and \
            np.array_equal(rows, np.arange(queue.shape[0], dtype=np.int32)):
        meta = np.stack([fill, count])
        if engine == "xla":
            return _queue_append_dense_xla(queue, keys, meta, aligned=aligned)
        return queue_append_dense_pallas(queue, keys, meta,
                                         interpret=interpret,
                                         aligned=aligned)
    meta = np.stack([rows, fill, count])
    if engine == "xla":
        return _queue_append_rows_xla(queue, keys, meta, aligned=aligned)
    return queue_append_pallas(queue, keys, meta, interpret=interpret,
                               aligned=aligned)


@functools.partial(jax.jit, static_argnames=("cols",))
def flush_inputs(queue: jnp.ndarray, fill: jnp.ndarray, cols: int):
    """(queue[:, :cols], (T, cols) float32 live-slot mask) in ONE dispatch.

    The host-queue path built the mask with NumPy and shipped queue AND
    mask up every flush; here only the (T,) fill vector crosses to the
    device and both flush inputs come out of a single fused computation.
    """
    weights = (jnp.arange(cols, dtype=jnp.int32)[None, :]
               < fill[:, None].astype(jnp.int32)).astype(jnp.float32)
    return queue[:, :cols], weights


@functools.partial(jax.jit, static_argnames=("cols",))
def flush_rows_inputs(queue: jnp.ndarray, fill: jnp.ndarray,
                      rows: jnp.ndarray, cols: int):
    """Active-row flush inputs: (queue[rows, :cols], (R, cols) mask), ONE
    dispatch.  The row gather, column trim, and live-slot weight mask fuse
    into a single computation — only the small (R,) fill and row vectors
    cross to the device, never the ring itself.
    """
    weights = (jnp.arange(cols, dtype=jnp.int32)[None, :]
               < fill[:, None].astype(jnp.int32)).astype(jnp.float32)
    return queue[rows, :cols], weights


# --------------------------------------------------------------------------
# tiered hot/cold plane storage (stream.tiering)
#
# The cold tier lives in HOST memory as numpy arrays in packed storage
# layout; these helpers are its device-side interface.  Spills and queries
# run through the XLA reference engines (`kernels/ref.py`) — bit-identical
# to the hot-tier kernels by the established parity — and every helper
# tallies under its OWN op name, so the audited claim "a hot-tier flush
# epoch is ONE update_score_rows dispatch" stays a measured number even
# when cold tenants spill in the same epoch.
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("spec", "total"))
def _tier_spill_score_jit(tables, keys, weights, rng, urows, cand, *, spec,
                          total):
    sorted_keys, mult = jax.vmap(sk.dedup_weighted)(keys, weights)
    uniforms = _parity_uniforms(rng, keys.shape[1], total, urows)
    rows = jnp.arange(tables.shape[0], dtype=jnp.int32)
    return ref.update_score_rows_ref(tables, sorted_keys, mult, uniforms,
                                     rows, cand, _row_seeds_array(spec),
                                     spec.counter, CHUNK,
                                     cpl=spec.cells_per_lane)


def tier_spill(tables: jnp.ndarray, spec: sk.SketchSpec, keys: jnp.ndarray,
               rng: jax.Array, weights: jnp.ndarray,
               uniform_rows, cand: jnp.ndarray | None = None):
    """Cold-tier spill: land C cold tenants' buffered batches on their
    host-gathered (C, d, w) table stack (uploaded by the caller).

    keys/weights (C, N) are the tenants' host queue-mirror slices; the
    dedup, chunk order, and parity-uniforms grid — `uniform_rows` is the
    REQUIRED (total, urows) pair naming each stack row's tenant index in
    the full tenant grid — are exactly the hot path's, so a spilled row's
    counters are bit-identical to what `update_score_rows`/`update_rows`
    would have landed had the tenant been device-resident.  With `cand`
    (C, M) the spill also re-scores the candidate union against the
    just-updated rows and returns (new_tables, estimates); without it,
    just new_tables.  Tallied as "tier_spill" — never as the audited hot
    ops.
    """
    _launch("tier_spill", "xla")
    total, urows = uniform_rows
    urows = np.asarray(urows, np.int32)
    if cand is None:
        return _update_stack_xla_jit(tables, keys, weights, rng, urows,
                                     spec=spec, total=int(total))
    return _tier_spill_score_jit(tables, keys, weights, rng, urows, cand,
                                 spec=spec, total=int(total))


@functools.partial(jax.jit, static_argnames=("spec",))
def _tier_query_jit(tables, keys, *, spec):
    seeds = _row_seeds_array(spec)

    def one(table, k):
        return ref.query_ref(table, k, seeds, spec.counter,
                             cpl=spec.cells_per_lane)
    return jax.vmap(one)(tables, keys)


def tier_query(tables, spec: sk.SketchSpec, keys) -> jnp.ndarray:
    """Cold-tier read path: float32 (C, N) estimates over a host-gathered
    (C, d, w) stack, through the XLA reference engine (`ref.query_ref` —
    estimates bit-identical to the `query_many` kernel, so hot and cold
    tenants answer a `query_all` identically).  1D keys broadcast to
    every row.  Tallied as "tier_query"."""
    tables = jnp.asarray(tables)
    keys = jnp.asarray(keys)
    if keys.ndim == 1:
        keys = jnp.broadcast_to(keys[None, :],
                                (tables.shape[0], keys.shape[0]))
    if keys.shape[0] != tables.shape[0]:
        raise ValueError(f"per-tenant keys need {tables.shape[0]} rows, "
                         f"got {keys.shape[0]}")
    _launch("tier_query", "xla")
    return _tier_query_jit(tables, keys, spec=spec)


@jax.jit
def _tier_demote_jit(tables, rows):
    return tables[rows]


def tier_demote(tables: jnp.ndarray, rows) -> jnp.ndarray:
    """Demotion gather: slice the demoted slots' tables out of the hot
    stack in ONE device computation (the caller's host copy lands them in
    the cold store).  The device ring needs NO read-back — the host queue
    mirror is authoritative for ring contents.  Tallied "tier_demote"."""
    _launch("tier_demote", "xla")
    return _tier_demote_jit(tables, jnp.asarray(np.asarray(rows, np.int32)))


@functools.partial(jax.jit, donate_argnames=("tables", "queue"))
def _tier_promote_jit(tables, queue, rows, new_tables, new_queue):
    return (tables.at[rows].set(new_tables),
            queue.at[rows].set(new_queue))


def tier_promote(tables: jnp.ndarray, queue: jnp.ndarray, rows,
                 new_tables, new_queue):
    """Promotion scatter: land the promoted tenants' cold tables AND their
    ring-mirror rows in the hot stacks with ONE jitted computation (both
    stacks donated, aliased in place) — the single device round-trip a
    cold tenant pays to become hot.  Tallied "tier_promote"; the extended
    launch audit allows at most one per flush epoch."""
    _launch("tier_promote", "xla")
    rows = jnp.asarray(np.asarray(rows, np.int32))
    return _tier_promote_jit(tables, queue, rows, jnp.asarray(new_tables),
                             jnp.asarray(new_queue))
