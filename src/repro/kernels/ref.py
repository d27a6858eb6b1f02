"""Pure-jnp oracles for the Pallas sketch kernels.

Standalone (no pallas import) so kernel tests compare two independent code
paths.  Semantics are identical to `repro.core.sketch`'s query/batched-update
given the same (pre-deduplicated) inputs.

The `*_rows_ref` / `*_stacked_ref` functions double as the jitted XLA
*engines* behind `kernels.ops`'s `engine="auto"` selection: they mirror
the kernels' grid semantics exactly, including the sequential chunk sweep
of the update (a key in chunk 2 sees chunk 1's writes) and the in-order
bucket accumulation of the window reduction.  Counter states (integers)
and raw query estimates are bit-identical to the kernels; the window
"sum" reduction's float rounding is fusion-dependent across engines (one
ulp), which is why `ops.window_query_stacked`'s auto stays on the kernel
family while `ops.update_score_rows`'s auto takes this path off-TPU (the
queue-append pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.counters import CounterSpec, pack_table, unpack_table
from repro.core.hashing import row_hashes


def query_ref(table: jnp.ndarray, keys: jnp.ndarray, row_seeds: jnp.ndarray,
              counter: CounterSpec, cpl: int = 1) -> jnp.ndarray:
    """min over rows + Morris decode; returns float32 estimates (N,).

    With cpl > 1 `table`'s last axis is packed uint32 lanes (cpl cells
    each); the unpack yields the same uint32 state VALUES the unpacked
    path reads, so the estimates are bit-identical.
    """
    if cpl > 1:
        table = unpack_table(table, 32 // cpl)
    d, w = table.shape
    cols = row_hashes(keys, row_seeds, w)                 # (d, N)
    vals = table[jnp.arange(d)[:, None], cols]            # (d, N)
    return counter.decode(vals.min(axis=0))


def update_ref(table: jnp.ndarray, keys: jnp.ndarray, mult: jnp.ndarray,
               uniforms: jnp.ndarray, row_seeds: jnp.ndarray,
               counter: CounterSpec, cpl: int = 1) -> jnp.ndarray:
    """Batched conservative update.

    keys/mult/uniforms: (N,); entries with mult == 0 are no-ops (this is how
    padding and intra-batch duplicates are expressed).  Returns new table
    (packed back into lanes when cpl > 1).
    """
    if cpl > 1:
        table = unpack_table(table, 32 // cpl)
    d, w = table.shape
    cols = row_hashes(keys, row_seeds, w)                 # (d, N)
    rows = jnp.arange(d)[:, None]
    table = conservative_pass(table, (rows, cols), mult, uniforms, counter)
    return pack_table(table, 32 // cpl) if cpl > 1 else table


def conservative_pass(table: jnp.ndarray, cells: tuple, mult: jnp.ndarray,
                      uniforms: jnp.ndarray, counter: CounterSpec
                      ) -> jnp.ndarray:
    """One pass of the conservative update over the cells `table[cells]`.

    `cells` indexes (..., d, N) cells: each key's d cells on the
    second-to-last axis.  Every key reads its cells' minimum, moves `mult`
    units from it (the n-fold Morris step, `uniforms` drawing the
    rounding), and writes the new state to every one of its cells by max.
    mult == 0 writes 0, a no-op.  The core of `update_ref` and of
    `update_score_rows_inplace_ref`.
    """
    cur = table[cells]
    cmin = cur.min(axis=-2)
    new_state = counter.nfold(cmin, mult, uniforms)
    write = jnp.where(mult > 0, new_state, jnp.zeros_like(new_state))
    return table.at[cells].max(
        jnp.broadcast_to(jnp.expand_dims(write, -2), cur.shape))


def update_chunked_ref(table: jnp.ndarray, keys: jnp.ndarray,
                       mult: jnp.ndarray, uniforms: jnp.ndarray,
                       row_seeds: jnp.ndarray, counter: CounterSpec,
                       chunk: int, cpl: int = 1) -> jnp.ndarray:
    """`update_ref` applied in `chunk`-sized slices, sequentially.

    Mirrors the kernels' grid contract: each chunk's conservative
    scatter-max is visible to the next chunk (two distinct keys colliding
    on a cell across a chunk boundary read different minima than a
    one-shot update would), so this — not a single `update_ref` over the
    whole batch — is the bit-identical oracle for multi-chunk launches.
    Packed tables unpack ONCE here, sweep the chunks on cell states, and
    repack once at the end.
    """
    if cpl > 1:
        table = unpack_table(table, 32 // cpl)
    n = keys.shape[0]
    pad = -n % chunk
    keys = jnp.pad(keys, (0, pad))
    mult = jnp.pad(mult, (0, pad))
    uniforms = jnp.pad(uniforms, (0, pad), constant_values=1.0)
    for i in range((n + pad) // chunk):
        sl = slice(i * chunk, (i + 1) * chunk)
        table = update_ref(table, keys[sl], mult[sl], uniforms[sl],
                           row_seeds, counter)
    return pack_table(table, 32 // cpl) if cpl > 1 else table


def update_score_rows_ref(tables: jnp.ndarray, keys: jnp.ndarray,
                          mult: jnp.ndarray, uniforms: jnp.ndarray,
                          rows: jnp.ndarray, cand: jnp.ndarray,
                          row_seeds: jnp.ndarray, counter: CounterSpec,
                          chunk: int, cpl: int = 1):
    """XLA engine of `fused_update_score_pallas`: active-row update, then
    candidate re-query against the just-updated rows.

    tables (T, d, w); keys/mult/uniforms (R, N); rows (R,) target rows;
    cand (R, M) candidate keys.  Returns (new_tables (T, d, w), float32
    estimates (R, M)) — bit-identical to the single-launch kernel epoch
    (the update runs chunk-sequentially per row; the scores read the new
    state, exactly as the kernel's score phase reads the aliased block).
    Only the R gathered rows unpack/repack when cpl > 1.
    """
    gathered = tables[rows]
    if cpl > 1:
        gathered = unpack_table(gathered, 32 // cpl)

    def one(table, k, m, u):
        return update_chunked_ref(table, k, m, u, row_seeds, counter, chunk)

    new_rows = jax.vmap(one)(gathered, keys, mult, uniforms)
    est = jax.vmap(lambda t, c: query_ref(t, c, row_seeds, counter))(
        new_rows, cand)
    if cpl > 1:
        new_rows = pack_table(new_rows, 32 // cpl)
    return tables.at[rows].set(new_rows), est


def update_score_rows_inplace_ref(tables: jnp.ndarray, keys: jnp.ndarray,
                                  mult: jnp.ndarray, uniforms: jnp.ndarray,
                                  rows: jnp.ndarray, cand: jnp.ndarray,
                                  row_seeds: jnp.ndarray,
                                  counter: CounterSpec, chunk: int):
    """`update_score_rows_ref` landed in place: each chunk's pass reads and
    writes the R rows' cells straight in the (T, d, w) stack, so no row is
    gathered out and scattered back, and a donated stack is updated where
    it lies.  Bit-identical to it (unpacked tables).

    Chunks run in order, one loop step each, all R rows at once (rows are
    distinct tables); the steps run only over the chunks from the first to
    the last that carries an event (mult > 0 in some row), since a chunk
    of no-ops changes nothing.  Returns (tables, float32 (R, M) estimates
    of `cand` read from the updated rows).
    """
    r, n = keys.shape
    pad = -n % chunk
    keys = jnp.pad(keys, ((0, 0), (0, pad)))
    mult = jnp.pad(mult, ((0, 0), (0, pad)))
    uniforms = jnp.pad(uniforms, ((0, 0), (0, pad)), constant_values=1.0)
    n += pad
    d, w = tables.shape[1:]
    at_row = rows[:, None, None]
    at_depth = jnp.arange(d)[None, :, None]
    hashes = jax.vmap(lambda k: row_hashes(k, row_seeds, w))

    pos = jnp.arange(n)
    live = (mult > 0).any(axis=0)
    first = jnp.min(jnp.where(live, pos, n)) // chunk
    last = (jnp.max(jnp.where(live, pos, -1)) + chunk) // chunk

    def step(i, tables):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=i * chunk, slice_size=chunk,
                               axis=1)
        cells = (at_row, at_depth, hashes(sl(keys)))
        return conservative_pass(tables, cells, sl(mult), sl(uniforms),
                                 counter)

    tables = jax.lax.fori_loop(first, last, step, tables)
    cells = tables[at_row, at_depth, hashes(cand)]
    return tables, counter.decode(cells.min(axis=1))


def window_query_stacked_ref(tables: jnp.ndarray, keys: jnp.ndarray,
                             weights: jnp.ndarray, row_seeds: jnp.ndarray,
                             counter: CounterSpec, mode: str = "sum",
                             cpl: int = 1) -> jnp.ndarray:
    """XLA engine of `window_query_stacked_pallas`: R bucket rings reduced
    bucket-by-bucket IN ORDER (b ascending), matching the kernel's
    innermost-bucket accumulation bit for bit.

    tables (R, B, d, w); keys (R, N); weights (R, B).  Returns (R, N).
    """
    if cpl > 1:
        tables = unpack_table(tables, 32 // cpl)
    b = tables.shape[1]

    def one(ring, k, w):
        out = None
        for i in range(b):  # in-order accumulation == kernel grid order
            est = query_ref(ring[i], k, row_seeds, counter) * w[i]
            if out is None:
                out = est
            elif mode == "sum":
                out = out + est
            else:
                out = jnp.maximum(out, est)
        return out

    return jax.vmap(one)(tables, keys, weights)


def window_query_stacked_rows_ref(tables: jnp.ndarray, keys: jnp.ndarray,
                                  weights: jnp.ndarray, rows: jnp.ndarray,
                                  row_seeds: jnp.ndarray,
                                  counter: CounterSpec, mode: str = "sum",
                                  cpl: int = 1) -> jnp.ndarray:
    """XLA engine of `window_query_stacked_rows_pallas`: gather the R
    tenant rings out of the native (T, B, d, w) plane, then run the same
    in-order bucket reduction.  The gather is XLA-internal (one fused
    dispatch) — the host never restacks.  Returns (R, N).
    """
    return window_query_stacked_ref(tables[rows], keys, weights, row_seeds,
                                    counter, mode=mode, cpl=cpl)
