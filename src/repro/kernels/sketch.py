"""Pallas TPU kernels for the sketch hot path.

TPU adaptation (DESIGN.md §3): the paper's sketches are a few MB — they fit
entirely in VMEM.  Both kernels therefore hold the full (d, w) table as a
single VMEM-resident block across every grid step and walk the *key stream*
with the grid:

  * query:  hash -> in-VMEM gather -> min over rows -> Morris decode, fused.
    Multi-tenant (`fused_query_pallas`) grids over (tenant, key-chunk);
    windowed (`window_query_pallas`) grids over (key-chunk, bucket) with the
    bucket axis innermost and does the weighted sum/max window reduction
    in-kernel (lazy decay = gamma^age bucket weights).
  * update: sequential grid over key chunks; the table is input/output
    aliased, so each chunk's conservative scatter-max is visible to the
    next chunk (TPU grids execute sequentially on a core — the legal place
    for read-modify-write).  The active-row variant
    (`fused_update_rows_pallas`) grids over (R, chunk) instead of
    (T, chunk): an SMEM row map (scalar prefetch, as in the queue append)
    steers each batch to its tenant's table block while the whole
    (T, d, w) stack stays aliased in place — a skewed flush pays for the
    rows that have work, bit-identically to the dense sweep.
  * queue append (`queue_append_pallas`): the ingest queue itself lives on
    device as a (T, capw) ring; appends grid over the batched tenant rows,
    with the per-row fill counters in SMEM (scalar prefetch drives the
    block index map) and the ring input/output aliased, so `enqueue` is a
    device call that never ships the queue back to the host.
  * flush epoch (`fused_update_score_pallas`): the active-row update and
    the heavy-hitter candidate re-query fused into ONE launch — each
    row's chunk axis runs its update sweep first, then scores the
    candidate set against the same still-resident aliased table block.
    `window_query_stacked_pallas` is the windowed read-side analogue: R
    flushed tenants' bucket rings, grid (ring, chunk, bucket), one launch
    for the whole tracker refresh.

Keys are laid out as (8k, 128) tiles to match the 8x128 vector lanes; the
per-row hash/gather/scatter loop is unrolled in Python over the small depth
d, so each row touch is a rank-1 VMEM gather/scatter.

Validated in interpret=True mode on CPU against kernels/ref.py (see
tests/test_kernels.py for the shape/dtype sweep).  None of these kernels
lowers for TPU v5e yet: Mosaic supports only 2D gathers, so the rank-1
`row[cols]` gather and `.at[cols].max` scatter fail ("Only 2D gather is
supported"), the row-indirected queue append's (1, capw) block breaks the
(8, 128) tiling rule, and the dense append loads vectors from SMEM.  On
TPU, `kernels.ops` therefore selects the XLA engines (`kernels/ref.py`)
for every "auto" call; these kernels run there only when a caller asks
for `engine="kernel"`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.counters import CounterSpec

LANES = 128
SUBLANES = 8
CHUNK = SUBLANES * LANES  # keys per grid step

def _mix32(x):
    # murmur3 fmix32, identical to repro.core.hashing.mix32 (kept inline so
    # the kernel body has no external calls for Mosaic; literals must be
    # built inside the traced body, not captured).
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EB_CA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2_AE35)
    x = x ^ (x >> 16)
    return x


def _hash_cols(keys, seed, width):
    """Logical column index per key: hashing always runs on the LOGICAL
    width, so packed and unpacked tables address the same cells with the
    same seeds."""
    return (_mix32(keys ^ jnp.uint32(seed)) % jnp.uint32(width)).astype(jnp.int32)


def _unpack_cells(lane_vals, sub, cpl):
    """Packed uint32 lanes -> uint32 cell states at sub-slot `sub`."""
    bits = 32 // cpl
    shift = (sub * bits).astype(jnp.uint32)
    return (lane_vals >> shift) & jnp.uint32((1 << bits) - 1)


def _table_min(table_ref, keys, *, seeds, width, t=None, pre=None, cpl=1):
    """min over rows of the hashed cells: the shared read of every query
    kernel.  table_ref block is (d, w), (1, d, w) with leading index t, or
    any deeper nesting via the explicit `pre` index prefix (e.g. (0, 0) for
    a (1, 1, d, w) ring block).  With cpl > 1 the block's last axis is
    packed uint32 lanes (cpl cells each): the gather lands on lane
    cols // cpl and the cell state is shift/masked out of the lane, so the
    min runs on the same uint32 cell VALUES the unpacked path reads."""
    if pre is None:
        pre = () if t is None else (t,)
    cmin = None
    for k, seed in enumerate(seeds):
        cols = _hash_cols(keys, seed, width)
        row = table_ref[(*pre, k, slice(None))]
        if cpl == 1:
            vals = row[cols.reshape(-1)].reshape(cols.shape)  # rank-1 gather
        else:
            lanes = row[(cols // cpl).reshape(-1)].reshape(cols.shape)
            vals = _unpack_cells(lanes, cols % cpl, cpl)
        cmin = vals if cmin is None else jnp.minimum(cmin, vals)
    return cmin


def _fused_query_kernel(tables_ref, keys_ref, out_ref, *, seeds, width,
                        counter, cpl=1):
    """One (tenant, key-chunk) grid step of the multi-tenant query.

    Blocks: tables (1, d, w) — tenant t's table, VMEM-resident across that
    tenant's chunk sweep; keys/out (1, 8, 128) key tiles.  hash -> in-VMEM
    gather -> min over rows -> Morris decode, fused; T tenants cost one
    launch instead of T (the same amortization as `_fused_update_kernel`).
    """
    keys = keys_ref[0].astype(jnp.uint32)                # (8, 128)
    cmin = _table_min(tables_ref, keys, seeds=seeds, width=width, t=0,
                      cpl=cpl)
    out_ref[0] = counter.decode(cmin)


def _window_query_kernel(tables_ref, keys_ref, w_ref, out_ref, *, seeds,
                         width, counter, mode, cpl=1):
    """One (key-chunk, bucket) grid step of the windowed query.

    The bucket ring is the leading axis of `tables`; the grid's *last* axis
    walks it, so for a fixed key chunk the output block stays resident while
    every bucket streams through VMEM, and the window reduction (weighted
    sum, or max) happens in-kernel — B buckets cost one launch and one
    output write instead of B queries plus a host-side reduce.  w_ref holds
    that bucket's weight (0 for expired buckets; gamma^age for lazy decay),
    applied to the *estimate*, never the counter state.
    """
    b = pl.program_id(1)
    keys = keys_ref[...].astype(jnp.uint32)              # (8, 128)
    cmin = _table_min(tables_ref, keys, seeds=seeds, width=width, t=0,
                      cpl=cpl)
    est = counter.decode(cmin) * w_ref[0, 0]

    @pl.when(b == 0)
    def _init():
        out_ref[...] = est

    @pl.when(b != 0)
    def _reduce():
        if mode == "sum":
            out_ref[...] = out_ref[...] + est
        else:
            out_ref[...] = jnp.maximum(out_ref[...], est)


def _fused_update_kernel(tables_ref, keys_ref, mult_ref, unif_ref, out_ref, *,
                         seeds, width, counter, cpl=1):
    """One (tenant, key-chunk) grid step of the multi-tenant ingest.

    Blocks: tables/out (1, d, w) — tenant t's table, VMEM-resident across
    that tenant's chunk sweep; keys/mult/unif (1, 8, 128) key tiles.  The
    grid's last axis (chunks) varies fastest, so for a fixed tenant the
    aliased output block stays resident and each chunk sees the previous
    chunk's conservative writes — the same sequential-grid contract as
    `_update_kernel`, now amortized over T tenants in ONE launch.

    With cpl > 1 the table block is packed uint32 lanes: the read
    shift/masks cell states out of the gathered lanes, nfold runs on the
    same uint32 state VALUES, and the conservative write becomes a
    per-sub-slot masked scatter-max followed by a shift/OR repack — cell
    for cell the max the unpacked path lands (mult == 0 entries still
    write state 0, a no-op under max).
    """
    keys = keys_ref[0].astype(jnp.uint32)                # (8, 128)
    mult = mult_ref[0]
    unif = unif_ref[0]
    all_cols = []
    cmin = None
    for k, seed in enumerate(seeds):
        cols = _hash_cols(keys, seed, width)
        all_cols.append(cols.reshape(-1))
        row = out_ref[0, k, :]  # aliased output: sees this tenant's prior chunks
        if cpl == 1:
            vals = row[cols.reshape(-1)].reshape(cols.shape)
        else:
            lanes = row[(cols // cpl).reshape(-1)].reshape(cols.shape)
            vals = _unpack_cells(lanes, cols % cpl, cpl)
        cmin = vals if cmin is None else jnp.minimum(cmin, vals)
    new_state = counter.nfold(cmin, mult, unif)
    write = jnp.where(mult > 0, new_state, jnp.zeros_like(new_state)).reshape(-1)
    if cpl == 1:
        for k in range(len(seeds)):
            row = out_ref[0, k, :]
            out_ref[0, k, :] = row.at[all_cols[k]].max(write)
        return
    bits = 32 // cpl
    mask = jnp.uint32((1 << bits) - 1)
    for k in range(len(seeds)):
        lane_idx = all_cols[k] // cpl
        sub_idx = all_cols[k] % cpl
        row = out_ref[0, k, :]
        new_row = jnp.zeros_like(row)
        for s in range(cpl):
            sub_state = (row >> jnp.uint32(s * bits)) & mask
            w_s = jnp.where(sub_idx == s, write, jnp.uint32(0))
            sub_state = sub_state.at[lane_idx].max(w_s)
            new_row = new_row | (sub_state << jnp.uint32(s * bits))
        out_ref[0, k, :] = new_row


def _pad_tiles(x, pad_value):
    """Pad a 1D array to a CHUNK multiple and tile to (8n, 128)."""
    n = x.shape[0]
    padded = CHUNK * max(1, math.ceil(n / CHUNK))
    x = jnp.pad(x, (0, padded - n), constant_values=pad_value)
    return x.reshape(padded // LANES, LANES), padded


@functools.partial(jax.jit, static_argnames=("width", "counter", "seeds",
                                             "interpret", "cpl"))
def query_pallas(table, keys, *, seeds: tuple, width: int,
                 counter: CounterSpec, interpret: bool = True, cpl: int = 1):
    """Fused sketch query. table (d, w); keys (N,) -> float32 (N,).

    The single-tenant case IS the fused kernel at T=1 (one source of truth
    for the query logic), exactly as `update_pallas` wraps the fused update.
    """
    return fused_query_pallas(table[None], keys[None], seeds=seeds,
                              width=width, counter=counter,
                              interpret=interpret, cpl=cpl)[0]


@functools.partial(jax.jit, static_argnames=("width", "counter", "seeds",
                                             "interpret", "cpl"))
def update_pallas(table, keys, mult, uniforms, *, seeds: tuple, width: int,
                  counter: CounterSpec, interpret: bool = True, cpl: int = 1):
    """Batched conservative update. Entries with mult == 0 are no-ops.

    table (d, w); keys/mult/uniforms (N,).  Returns the new table (the input
    buffer is donated via input_output_aliases — in-place on device).
    The single-tenant case IS the fused kernel at T=1 (one source of truth
    for the conservative-update logic)."""
    return fused_update_pallas(table[None], keys[None], mult[None],
                               uniforms[None], seeds=seeds, width=width,
                               counter=counter, interpret=interpret,
                               cpl=cpl)[0]


def _pad_tiles_2d(x, pad_value):
    """Pad (T, N) per-tenant streams to a CHUNK multiple and tile each
    tenant's row to (rows, 128): returns (T, rows, 128) with rows % 8 == 0."""
    t, n = x.shape
    padded = CHUNK * max(1, math.ceil(n / CHUNK))
    x = jnp.pad(x, ((0, 0), (0, padded - n)), constant_values=pad_value)
    return x.reshape(t, padded // LANES, LANES), padded


@functools.partial(jax.jit, static_argnames=("width", "counter", "seeds",
                                             "interpret", "cpl"))
def fused_update_pallas(tables, keys, mult, uniforms, *, seeds: tuple,
                        width: int, counter: CounterSpec,
                        interpret: bool = True, cpl: int = 1):
    """Multi-tenant batched conservative update in ONE kernel launch.

    tables (T, d, w): stacked per-tenant sketch tables (identical spec);
    keys/mult/uniforms (T, N): each tenant's pre-deduplicated microbatch
    (entries with mult == 0 are no-ops, which is how ragged queues pad).
    Grids over (tenant, key-chunk) with tenant t's (d, w) table the
    VMEM-resident block, so T tenants cost one launch instead of T.
    Returns the new (T, d, w) tables (input buffer donated/aliased).

    With cpl > 1 the stored last axis is width // cpl uint32 lanes (cpl
    packed cells each); `width` stays the LOGICAL cell count.
    """
    t, d, sw = tables.shape
    key_t, padded = _pad_tiles_2d(keys.astype(jnp.uint32), 0)
    mult_t, _ = _pad_tiles_2d(mult.astype(jnp.float32), 0.0)
    unif_t, _ = _pad_tiles_2d(uniforms.astype(jnp.float32), 1.0)
    chunks = padded // CHUNK
    return pl.pallas_call(
        functools.partial(_fused_update_kernel, seeds=seeds, width=width,
                          counter=counter, cpl=cpl),
        grid=(t, chunks),
        in_specs=[
            pl.BlockSpec((1, d, sw), lambda ti, ci: (ti, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda ti, ci: (ti, ci, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda ti, ci: (ti, ci, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda ti, ci: (ti, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, d, sw), lambda ti, ci: (ti, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(tables.shape, tables.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(tables, key_t, mult_t, unif_t)


@functools.partial(jax.jit, static_argnames=("width", "counter", "seeds",
                                             "interpret", "cpl"))
def fused_query_pallas(tables, keys, *, seeds: tuple, width: int,
                       counter: CounterSpec, interpret: bool = True,
                       cpl: int = 1):
    """Multi-tenant batched query in ONE kernel launch.

    tables (T, d, w): stacked per-tenant sketch tables (identical spec);
    keys (T, N): each tenant's probe keys.  Grids over (tenant, key-chunk)
    with tenant t's (d, w) table the VMEM-resident block.  Returns float32
    (T, N) estimates, bit-identical to T per-tenant `query_pallas` calls.
    """
    t, d, sw = tables.shape
    n = keys.shape[1]
    tiles, padded = _pad_tiles_2d(keys.astype(jnp.uint32), 0)
    chunks = padded // CHUNK
    out = pl.pallas_call(
        functools.partial(_fused_query_kernel, seeds=seeds, width=width,
                          counter=counter, cpl=cpl),
        grid=(t, chunks),
        in_specs=[
            pl.BlockSpec((1, d, sw), lambda ti, ci: (ti, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda ti, ci: (ti, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, SUBLANES, LANES), lambda ti, ci: (ti, ci, 0)),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.float32),
        interpret=interpret,
    )(tables, tiles)
    return out.reshape(t, -1)[:, :n]


def _fused_update_rows_kernel(meta_ref, tables_ref, keys_ref, mult_ref,
                              unif_ref, out_ref, *, seeds, width, counter,
                              cpl=1):
    """One (active-row, key-chunk) grid step of the active-row ingest.

    Identical body to `_fused_update_kernel`: the (R,) row map rides in
    SMEM (scalar prefetch) and is consumed by the block index maps — the
    kernel body itself never needs it, it just sees "its" tenant's (1, d,
    w) table block wherever the map pointed.
    """
    del meta_ref
    _fused_update_kernel(tables_ref, keys_ref, mult_ref, unif_ref, out_ref,
                         seeds=seeds, width=width, counter=counter, cpl=cpl)


@functools.partial(jax.jit, static_argnames=("width", "counter", "seeds",
                                             "interpret", "cpl"))
def fused_update_rows_pallas(tables, keys, mult, uniforms, rows, *,
                             seeds: tuple, width: int, counter: CounterSpec,
                             interpret: bool = True, cpl: int = 1):
    """Active-row multi-tenant update: grid (R, chunk) instead of (T, chunk).

    tables (T, d, w): the WHOLE plane's stacked tables; keys/mult/uniforms
    (R, N): only the R rows with pending work — batch i lands in tenant
    rows[i]'s table, selected by the SMEM row map (rows (R,) int32, scalar
    prefetch driving the block index map — the same pattern as
    `queue_append_pallas`).  The tables buffer is input/output aliased, so
    the T - R unlisted rows persist in place and a skewed flush costs R
    table-resident sweeps instead of T.  Within one row the chunk axis is
    innermost, so conservative writes stay sequential exactly as in the
    dense kernel.  Caller contract: rows unique within a call.  Returns
    the updated (T, d, w) tables — bit-identical to `fused_update_pallas`
    over the full grid with the unlisted rows' mult zeroed.
    """
    r = keys.shape[0]
    _, d, sw = tables.shape
    key_t, padded = _pad_tiles_2d(keys.astype(jnp.uint32), 0)
    mult_t, _ = _pad_tiles_2d(mult.astype(jnp.float32), 0.0)
    unif_t, _ = _pad_tiles_2d(uniforms.astype(jnp.float32), 1.0)
    chunks = padded // CHUNK
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, chunks),
        in_specs=[
            pl.BlockSpec((1, d, sw), lambda ri, ci, meta: (meta[ri], 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda ri, ci, meta: (ri, ci, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda ri, ci, meta: (ri, ci, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda ri, ci, meta: (ri, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, d, sw),
                               lambda ri, ci, meta: (meta[ri], 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_fused_update_rows_kernel, seeds=seeds, width=width,
                          counter=counter, cpl=cpl),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(tables.shape, tables.dtype),
        input_output_aliases={1: 0},  # tables aliased past the meta scalars
        interpret=interpret,
    )(rows, tables, key_t, mult_t, unif_t)


def _fused_update_score_kernel(meta_ref, tables_ref, keys_ref, mult_ref,
                               unif_ref, cand_ref, out_ref, est_ref, *,
                               seeds, width, counter, upd_chunks, cpl=1):
    """One (active-row, chunk) grid step of the single-launch flush epoch.

    The chunk axis is split in two phases: steps 0..upd_chunks-1 run the
    conservative update (identical body to `_fused_update_rows_kernel`),
    the remaining steps re-query the row's tracker candidate set against
    the SAME aliased table block — which is still VMEM-resident, because
    the block index map keeps pointing at meta[ri] for the whole row.  The
    grid executes sequentially with the chunk axis innermost, so every
    candidate score observes every update chunk of its row: one launch
    lands the flush AND refreshes the heavy-hitter estimates.
    """
    del meta_ref
    ci = pl.program_id(1)

    @pl.when(ci < upd_chunks)
    def _update():
        _fused_update_kernel(tables_ref, keys_ref, mult_ref, unif_ref,
                             out_ref, seeds=seeds, width=width,
                             counter=counter, cpl=cpl)

    @pl.when(ci >= upd_chunks)
    def _score():
        keys = cand_ref[0].astype(jnp.uint32)            # (8, 128)
        cmin = _table_min(out_ref, keys, seeds=seeds, width=width, t=0,
                          cpl=cpl)
        est_ref[0] = counter.decode(cmin)


@functools.partial(jax.jit, static_argnames=("width", "counter", "seeds",
                                             "interpret", "cpl"))
def fused_update_score_pallas(tables, keys, mult, uniforms, cand, rows, *,
                              seeds: tuple, width: int, counter: CounterSpec,
                              interpret: bool = True, cpl: int = 1):
    """Single-launch flush epoch: conservative update THEN candidate
    re-score, while each active row's (d, w) table block is VMEM-resident.

    tables (T, d, w): the whole plane's stacked tables (input/output
    aliased — unlisted rows persist in place); keys/mult/uniforms (R, N):
    the active rows' pre-deduplicated microbatches; cand (R, M): each
    row's heavy-hitter candidate set (standing heap + just-flushed keys);
    rows (R,) int32 SMEM row map (scalar prefetch), unique within a call.
    Grid (R, upd_chunks + cand_chunks): the first upd_chunks steps of each
    row are exactly `fused_update_rows_pallas`'s update sweep, the rest
    read the freshly-written aliased block and emit float32 estimates —
    bit-identical to that update launch followed by a `fused_query_pallas`
    launch over the gathered updated rows, minus the second launch and the
    second table fetch.  Returns (new_tables (T, d, w), est (R, M)).
    """
    r = keys.shape[0]
    _, d, sw = tables.shape
    m = cand.shape[1]
    key_t, padded = _pad_tiles_2d(keys.astype(jnp.uint32), 0)
    mult_t, _ = _pad_tiles_2d(mult.astype(jnp.float32), 0.0)
    unif_t, _ = _pad_tiles_2d(uniforms.astype(jnp.float32), 1.0)
    cand_t, cand_padded = _pad_tiles_2d(cand.astype(jnp.uint32), 0)
    uc = padded // CHUNK            # update chunks
    qc = cand_padded // CHUNK       # candidate-score chunks
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, uc + qc),
        in_specs=[
            pl.BlockSpec((1, d, sw), lambda ri, ci, meta: (meta[ri], 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES),
                         lambda ri, ci, meta: (ri, jnp.minimum(ci, uc - 1), 0)),
            pl.BlockSpec((1, SUBLANES, LANES),
                         lambda ri, ci, meta: (ri, jnp.minimum(ci, uc - 1), 0)),
            pl.BlockSpec((1, SUBLANES, LANES),
                         lambda ri, ci, meta: (ri, jnp.minimum(ci, uc - 1), 0)),
            pl.BlockSpec((1, SUBLANES, LANES),
                         lambda ri, ci, meta: (ri, jnp.maximum(ci - uc, 0), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, d, sw), lambda ri, ci, meta: (meta[ri], 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES),
                         lambda ri, ci, meta: (ri, jnp.maximum(ci - uc, 0), 0)),
        ],
    )
    new_tables, est = pl.pallas_call(
        functools.partial(_fused_update_score_kernel, seeds=seeds,
                          width=width, counter=counter, upd_chunks=uc,
                          cpl=cpl),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(tables.shape, tables.dtype),
                   jax.ShapeDtypeStruct(cand_t.shape, jnp.float32)),
        input_output_aliases={1: 0},  # tables aliased past the meta scalars
        interpret=interpret,
    )(rows, tables, key_t, mult_t, unif_t, cand_t)
    return new_tables, est.reshape(r, -1)[:, :m]


def _queue_append_kernel(meta_ref, queue_ref, buf_ref, out_ref):
    """One row of the device-ring scatter append.

    The ingest queue lives on device as a (T, capw) ring; appending tenant
    row r's microbatch is a masked copy of the pre-shifted key buffer into
    that row: cell c takes buf[c] iff fill <= c < fill + count.  The
    (3, R) meta scalars — target row / fill / count — ride in SMEM (scalar
    prefetch), so the block index map can pick the target tenant row before
    the body runs; the ring is input/output aliased, so untouched rows (and
    the live prefix of this row) persist in place — `enqueue` never
    round-trips the queue through the host.
    """
    ri = pl.program_id(0)
    w = out_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)[0]
    fill, count = meta_ref[1, ri], meta_ref[2, ri]
    valid = (cols >= fill) & (cols < fill + count)
    out_ref[0, :] = jnp.where(valid, buf_ref[0, :], out_ref[0, :])


def _shift_to_fill(keys, fill, capw, dtype, aligned):
    """(R, capw) key buffers with row i's batch starting at fill[i].

    `aligned` (static) asserts every fill is 0 — the common append-right-
    after-flush case — turning the shift into a plain pad/cast.  Otherwise
    the landing pad is capw + n wide so the dynamic_update_slice start
    never clamps (fill <= capw by the caller contract), then trimmed.
    """
    n = keys.shape[1]
    if aligned:
        out = keys.astype(dtype)
        if n < capw:  # batches narrower than the ring: zero-extend
            return jnp.pad(out, ((0, 0), (0, capw - n)))
        return out[:, :capw]  # CHUNK-quantized staging may overshoot capw

    def one(k, f):
        pad = jnp.zeros((capw + n,), dtype)
        return jax.lax.dynamic_update_slice(pad, k.astype(dtype), (f,))[:capw]

    return jax.vmap(one)(keys, fill)


@functools.partial(jax.jit, static_argnames=("interpret", "aligned"),
                   donate_argnames=("queue",))
def queue_append_pallas(queue, keys, meta, *, interpret: bool = True,
                        aligned: bool = False):
    """Scatter-append R tenant microbatches into the device ring: ONE launch.

    queue (T, capw) uint32: the device-resident ring (capw lane-aligned);
    keys (R, N): per-row microbatches, ragged via the counts; meta (3, R)
    int32 rows: target tenant row, its current fill, and the number of live
    keys in that row's batch (entries past the count are padding) — packed
    into one array so an append costs a single small host->device transfer.
    Each grid step appends one batch at its row's fill offset: the keys are
    shifted to the fill position with one dynamic_update_slice (outside the
    kernel, so the kernel body is a pure masked lane copy — no
    gather/scatter for Mosaic to choke on) and merged into the aliased row
    block.  The ring is donated: appends mutate it in place on device, and
    the caller is responsible for tracking fill on the host (it knows
    exactly what it appended, so no device sync is ever needed).

    Caller contract: fill[i] + count[i] <= capw, rows unique within a call.
    Returns the updated (T, capw) ring.
    """
    r = keys.shape[0]
    capw = queue.shape[1]
    buf = _shift_to_fill(keys, meta[1], capw, queue.dtype, aligned)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, capw), lambda ri, meta: (meta[0, ri], 0)),
            pl.BlockSpec((1, capw), lambda ri, meta: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((1, capw), lambda ri, meta: (meta[0, ri], 0)),
    )
    return pl.pallas_call(
        _queue_append_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(queue.shape, queue.dtype),
        input_output_aliases={1: 0},  # ring aliased past the meta scalars
        interpret=interpret,
    )(meta, queue, buf)


def _queue_append_dense_kernel(meta_ref, queue_ref, buf_ref, out_ref):
    """Whole-plane append: every tenant row in ONE grid step.

    The full (T, capw) ring is the resident block; the (2, T) fill/count
    scalars are read from SMEM as whole-row slices (one vector read per
    scalar row, not a Python loop over T) and the masked copy lands all
    rows at once — the batched-ingest fast path `enqueue_many` hits when a
    microbatch covers the whole plane.  The single block covers the whole
    output, so this variant is functional (no in-kernel aliasing): the jit
    wrapper donates the ring instead.
    """
    fill = meta_ref[0, :]
    count = meta_ref[1, :]
    cols = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    valid = (cols >= fill[:, None]) & (cols < (fill + count)[:, None])
    out_ref[...] = jnp.where(valid, buf_ref[...], queue_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret", "aligned"),
                   donate_argnames=("queue",))
def queue_append_dense_pallas(queue, keys, meta, *, interpret: bool = True,
                              aligned: bool = False):
    """Append one microbatch per tenant row (row i -> tenant i): ONE grid
    step over the whole (T, capw) ring.  Same contract as
    `queue_append_pallas` with rows == arange(T) and meta (2, T) =
    [fill; count], minus the row indirection; the block is the full plane,
    so T * capw is bounded by VMEM exactly like the stacked tables the
    plane already keeps resident.
    """
    t, capw = queue.shape
    buf = _shift_to_fill(keys, meta[0], capw, queue.dtype, aligned)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((t, capw), lambda i, meta: (0, 0)),
            pl.BlockSpec((t, capw), lambda i, meta: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t, capw), lambda i, meta: (0, 0)),
    )
    return pl.pallas_call(
        _queue_append_dense_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(queue.shape, queue.dtype),
        interpret=interpret,
    )(meta, queue, buf)


@functools.partial(jax.jit,
                   static_argnames=("width", "counter", "seeds", "mode",
                                    "interpret", "cpl"))
def window_query_pallas(tables, keys, weights, *, seeds: tuple, width: int,
                        counter: CounterSpec, mode: str = "sum",
                        interpret: bool = True, cpl: int = 1):
    """Windowed query with the in-kernel bucket reduction.

    tables (B, d, w): the bucket ring (leading axis = bucket); keys (N,);
    weights (B,): per-bucket estimate weights — 0 for buckets outside the
    window, gamma^age for lazy decay, 1 for a plain window sum.  Grids over
    (key-chunk, bucket) with the bucket axis innermost, so each key chunk's
    output block stays resident while the B bucket tables stream through
    VMEM and the weighted sum (mode="sum") or max (mode="max") reduction
    happens in-kernel.  Returns float32 (N,).
    """
    if mode not in ("sum", "max"):
        raise ValueError(f"unknown window query mode {mode!r}")
    b, d, sw = tables.shape
    n = keys.shape[0]
    tiles, padded = _pad_tiles(keys.astype(jnp.uint32), 0)
    w_tiles = jnp.broadcast_to(weights.astype(jnp.float32)[:, None],
                               (b, LANES))
    out = pl.pallas_call(
        functools.partial(_window_query_kernel, seeds=seeds, width=width,
                          counter=counter, mode=mode, cpl=cpl),
        grid=(padded // CHUNK, b),
        in_specs=[
            pl.BlockSpec((1, d, sw), lambda ci, bi: (bi, 0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda ci, bi: (ci, 0)),
            pl.BlockSpec((1, LANES), lambda ci, bi: (bi, 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda ci, bi: (ci, 0)),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.float32),
        interpret=interpret,
    )(tables, tiles, w_tiles)
    return out.reshape(-1)[:n]


def _window_query_stacked_kernel(tables_ref, keys_ref, w_ref, out_ref, *,
                                 seeds, width, counter, mode, cpl=1):
    """One (ring, key-chunk, bucket) grid step of the multi-ring query.

    Same reduction as `_window_query_kernel` with a leading ring axis: the
    bucket axis is innermost, so for a fixed (ring, chunk) the output
    block stays resident while ring r's B bucket tables stream through
    VMEM — R rings cost ONE launch instead of R, the read-side analogue
    of the fused multi-tenant query.  w_ref holds ring r's weight for
    bucket b (0 expired / gamma^age decay), applied to the estimate.
    """
    b = pl.program_id(2)
    keys = keys_ref[0].astype(jnp.uint32)                # (8, 128)
    cmin = _table_min(tables_ref, keys, seeds=seeds, width=width,
                      pre=(0, 0), cpl=cpl)
    est = counter.decode(cmin) * w_ref[0, 0, 0]

    @pl.when(b == 0)
    def _init():
        out_ref[0] = est

    @pl.when(b != 0)
    def _reduce():
        if mode == "sum":
            out_ref[0] = out_ref[0] + est
        else:
            out_ref[0] = jnp.maximum(out_ref[0], est)


@functools.partial(jax.jit,
                   static_argnames=("width", "counter", "seeds", "mode",
                                    "interpret", "cpl"))
def window_query_stacked_pallas(tables, keys, weights, *, seeds: tuple,
                                width: int, counter: CounterSpec,
                                mode: str = "sum", interpret: bool = True,
                                cpl: int = 1):
    """Stacked multi-ring windowed query: R bucket rings, ONE launch.

    tables (R, B, d, w): one bucket ring per flushed window tenant; keys
    (R, N): each ring's probe keys; weights (R, B): per-ring per-bucket
    estimate weights.  Grids over (ring, key-chunk, bucket) with the
    bucket axis innermost; the in-kernel weighted sum/max reduction is
    bit-identical to R separate `window_query_pallas` launches.  Returns
    float32 (R, N).
    """
    if mode not in ("sum", "max"):
        raise ValueError(f"unknown window query mode {mode!r}")
    r, b, d, sw = tables.shape
    n = keys.shape[1]
    tiles, padded = _pad_tiles_2d(keys.astype(jnp.uint32), 0)
    w_tiles = jnp.broadcast_to(weights.astype(jnp.float32)[:, :, None],
                               (r, b, LANES))
    out = pl.pallas_call(
        functools.partial(_window_query_stacked_kernel, seeds=seeds,
                          width=width, counter=counter, mode=mode, cpl=cpl),
        grid=(r, padded // CHUNK, b),
        in_specs=[
            pl.BlockSpec((1, 1, d, sw), lambda ri, ci, bi: (ri, bi, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda ri, ci, bi: (ri, ci, 0)),
            pl.BlockSpec((1, 1, LANES), lambda ri, ci, bi: (ri, bi, 0)),
        ],
        out_specs=pl.BlockSpec((1, SUBLANES, LANES),
                               lambda ri, ci, bi: (ri, ci, 0)),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.float32),
        interpret=interpret,
    )(tables, tiles, w_tiles)
    return out.reshape(r, -1)[:, :n]


def _window_query_stacked_rows_kernel(meta_ref, tables_ref, keys_ref, w_ref,
                                      out_ref, *, seeds, width, counter,
                                      mode, cpl=1):
    """Row-mapped variant of `_window_query_stacked_kernel`.

    Identical reduction; the scalar-prefetch row map already steered the
    table BlockSpec at the plane's tenant row, so the body never touches
    meta itself.
    """
    del meta_ref
    _window_query_stacked_kernel(tables_ref, keys_ref, w_ref, out_ref,
                                 seeds=seeds, width=width, counter=counter,
                                 mode=mode, cpl=cpl)


@functools.partial(jax.jit,
                   static_argnames=("width", "counter", "seeds", "mode",
                                    "interpret", "cpl"))
def window_query_stacked_rows_pallas(tables, keys, weights, rows, *,
                                     seeds: tuple, width: int,
                                     counter: CounterSpec, mode: str = "sum",
                                     interpret: bool = True, cpl: int = 1):
    """Stacked windowed query straight off a native (T, B, d, w) plane.

    tables (T, B, d, w): the resident window-plane leaf; rows (R,) int32:
    which tenant rows to query; keys (R, N) / weights (R, B) are indexed
    by the R *query* rows, not by tenant.  The scalar-prefetch row map
    steers each grid step's table block at `tables[rows[ri], bi]`, so the
    R-ring launch reads the plane zero-copy — no `tables[rows]` gather,
    no host restack.  Reduction is bit-identical to
    `window_query_stacked_pallas(tables[rows], ...)`.  Returns (R, N).
    """
    if mode not in ("sum", "max"):
        raise ValueError(f"unknown window query mode {mode!r}")
    _, b, d, sw = tables.shape
    r, n = keys.shape
    tiles, padded = _pad_tiles_2d(keys.astype(jnp.uint32), 0)
    w_tiles = jnp.broadcast_to(weights.astype(jnp.float32)[:, :, None],
                               (r, b, LANES))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, padded // CHUNK, b),
        in_specs=[
            pl.BlockSpec((1, 1, d, sw),
                         lambda ri, ci, bi, meta: (meta[ri], bi, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES),
                         lambda ri, ci, bi, meta: (ri, ci, 0)),
            pl.BlockSpec((1, 1, LANES), lambda ri, ci, bi, meta: (ri, bi, 0)),
        ],
        out_specs=pl.BlockSpec((1, SUBLANES, LANES),
                               lambda ri, ci, bi, meta: (ri, ci, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_window_query_stacked_rows_kernel, seeds=seeds,
                          width=width, counter=counter, mode=mode, cpl=cpl),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.float32),
        interpret=interpret,
    )(rows.astype(jnp.int32), tables, tiles, w_tiles)
    return out.reshape(r, -1)[:, :n]
