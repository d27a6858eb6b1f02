"""Distributed sketches over a device mesh (shard_map building blocks).

Two deployment modes, matching how counting planes are run at scale:

  * REPLICATED-LAZY  — every data-parallel worker owns a full local sketch,
    updates it locally every step, and the fleet max-merges (lax.pmax) every
    `merge_every` steps.  Communication-avoiding: a slow worker never blocks
    the counting plane, and the merge is associative/commutative so the
    schedule is free to drift (straggler tolerance).  Merged state is a
    valid conservative-update sketch of the union stream.

  * KEY-ROUTED       — the key space is partitioned over an axis by a
    routing hash; each shard owns a full (d, w_local) sketch for its
    partition.  Updates/queries are dispatched with a fixed-capacity
    all_to_all (MoE-style), which keeps the collective statically shaped.
    This is the mode for sketches too large for one chip's memory.

All functions here are written to run *inside* shard_map with the named
axes given; they are pure and statically shaped, so they lower cleanly at
any mesh size (the multi-pod dry-run exercises them on 512 devices).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import admission
from repro.core import sketch as sk
from repro.core import topk
from repro.core.hashing import host_row_seeds, mix32, row_hashes

SENTINEL = jnp.uint32(0xFFFF_FFFF)
_ROUTE_SALT = jnp.uint32(0x60D5)
_LANE_SALT = 0x1A4E5


# --------------------------------------------------------------------------
# replicated-lazy mode
# --------------------------------------------------------------------------

def pmax_merge(sketch: sk.Sketch, axis_names) -> sk.Sketch:
    """Max-merge local sketches across mesh axes (inside shard_map).

    Packed storage unpacks around the collective: a lane-wise uint32 pmax
    would take the max of 4-cell bit patterns, not of each cell."""
    states = sk.logical_table(sketch.table, sketch.spec)
    merged = sk.storage_table(jax.lax.pmax(states, axis_names), sketch.spec)
    return sk.Sketch(table=merged, spec=sketch.spec)


def lazy_update(sketch: sk.Sketch, keys: jnp.ndarray, rng: jax.Array,
                step: jnp.ndarray, merge_every: int, axis_names) -> sk.Sketch:
    """Local update + periodic fleet merge, branch decided by `step`."""
    sketch = sk.update_batched(sketch, keys, rng)
    do_merge = (step % merge_every) == (merge_every - 1)
    merged = pmax_merge(sketch, axis_names)
    table = jnp.where(do_merge, merged.table, sketch.table)
    return sk.Sketch(table=table, spec=sketch.spec)


def pmax_merge_window_stack(tables: jnp.ndarray, spec, axis_names
                            ) -> jnp.ndarray:
    """Max-merge a stacked window leaf across mesh axes (inside shard_map).

    tables: the native (T, B, d, w) window-plane leaf (or any leading-dim
    stack of bucket rings) — `logical_table`/`storage_table` act on the
    trailing (d, w) axes, so the whole plane merges in one collective,
    zero-copy from the resident array.  spec: the rings' SketchSpec
    (packed storage unpacks around the collective like `pmax_merge`)."""
    states = sk.logical_table(tables, spec)
    return sk.storage_table(jax.lax.pmax(states, axis_names), spec)


def tier_assemble(hot_tables: jnp.ndarray, slot_tenant,
                  cold_tables) -> jnp.ndarray:
    """Reassemble a tiered plane's full tenant-ordered stack: scatter the
    (H, ...) hot slots into the (T, ...) cold store copy at their tenant
    rows (`slot_tenant` is the hot slot -> tenant map).  One device
    scatter; the result is the all-resident layout every stack-shaped
    consumer (parity oracles, cross-shard merges) expects."""
    stack = jnp.asarray(cold_tables)
    slot_tenant = jnp.asarray(np.asarray(slot_tenant, np.int32))
    if slot_tenant.size == 0:
        return stack
    return stack.at[slot_tenant].set(hot_tables)


def pmax_merge_tier_stack(hot_tables: jnp.ndarray, slot_tenant,
                          cold_tables, spec, axis_names
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Max-merge a TIERED plane across mesh axes (inside shard_map):
    reassemble the full (T, ...) tenant stack from both tiers, unpack
    around the collective like `pmax_merge`, and return (merged hot
    slice, merged full stack) — the hot slice scatters straight back into
    the device stack, the full stack is the caller's source for refreshed
    cold rows.  Shards must agree on tier membership (it is deterministic
    given the same traffic; checkpoint restore re-applies it)."""
    stack = tier_assemble(hot_tables, slot_tenant, cold_tables)
    states = sk.logical_table(stack, spec)
    merged = sk.storage_table(jax.lax.pmax(states, axis_names), spec)
    slot_tenant = jnp.asarray(np.asarray(slot_tenant, np.int32))
    return merged[slot_tenant], merged


def pmax_merge_window(win, axis_names):
    """Max-merge per-shard bucket rings across mesh axes (inside shard_map).

    Every worker rotates on the same schedule (rotation is driven by the
    host step counter or a shared watermark, replicated by construction),
    so bucket b means the same time slice on every shard and the ring
    merges bucket-wise exactly like a plain sketch (per-cell, so packed
    rings unpack around the collective like `pmax_merge`).  The (B, d, w)
    ring is the T=1 case of `pmax_merge_window_stack`, which merges a
    whole window plane's native leaf at once."""
    merged = pmax_merge_window_stack(win.tables, win.spec.sketch, axis_names)
    return dataclasses.replace(win, tables=merged)


def lazy_update_window(win, keys: jnp.ndarray, rng: jax.Array,
                       step: jnp.ndarray, merge_every: int, axis_names):
    """Windowed analogue of `lazy_update`: local active-bucket update plus a
    periodic fleet-wide bucket-wise pmax merge.  (repro.stream is imported
    lazily here and in the routed-window functions so core stays a leaf
    package at import time.)"""
    import repro.stream.window as w
    win = w.window_update(win, keys, rng)
    do_merge = (step % merge_every) == (merge_every - 1)
    merged = pmax_merge_window(win, axis_names)
    tables = jnp.where(do_merge, merged.tables, win.tables)
    return dataclasses.replace(win, tables=tables)


# --------------------------------------------------------------------------
# key-routed mode
# --------------------------------------------------------------------------

def route_of(keys: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """Owning shard of each key (independent of the row hashes)."""
    return (mix32(keys.astype(jnp.uint32) ^ _ROUTE_SALT)
            % jnp.uint32(n_shards)).astype(jnp.int32)


def _dispatch_layout(keys: jnp.ndarray, n_shards: int, capacity: int):
    """Pack keys into a (n_shards, capacity) send buffer.

    Returns (buffer, slot_of_key, kept_mask); overflowing keys beyond
    `capacity` per destination are dropped (counted by the caller if needed,
    same contract as capacity-factor MoE dispatch).
    """
    n = keys.shape[0]
    dest = route_of(keys, n_shards)
    order = jnp.argsort(dest)
    sorted_dest = dest[order]
    counts = jnp.bincount(dest, length=n_shards)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(n) - offsets[sorted_dest]
    keep = rank < capacity
    slot = sorted_dest * capacity + rank
    slot = jnp.where(keep, slot, n_shards * capacity)  # OOB -> dropped
    buf = jnp.full((n_shards * capacity,), SENTINEL, jnp.uint32)
    buf = buf.at[slot].set(keys[order].astype(jnp.uint32), mode="drop")
    # slot of each original key (or capacity overflow marker)
    slot_of_key = jnp.full((n,), n_shards * capacity, jnp.int32)
    slot_of_key = slot_of_key.at[order].set(jnp.where(keep, slot, n_shards * capacity))
    kept = jnp.zeros((n,), bool).at[order].set(keep)
    return buf.reshape(n_shards, capacity), slot_of_key, kept


def routed_update(local: sk.Sketch, keys: jnp.ndarray, rng: jax.Array,
                  axis_name: str, capacity: int) -> sk.Sketch:
    """Update a key-routed sketch (call inside shard_map over `axis_name`)."""
    n_shards = jax.lax.axis_size(axis_name)
    buf, _, _ = _dispatch_layout(keys, n_shards, capacity)
    # (n_shards, cap) -> received (n_shards, cap): row j came from device j
    recv = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0)
    flat = recv.reshape(-1)
    valid = flat != SENTINEL
    # sentinel keys carry weight 0 -> no-op inside the batched update
    return sk.update_batched(local, flat, rng, weights=valid.astype(jnp.float32))


def shard_seeds(seed: int, n_shards: int) -> tuple:
    """Seeds of the uniform lanes of a key-routed plane's shards: shard s
    draws epoch f's uniforms from the raw key (seeds[s], f), so no two
    shards draw the same randoms (host ints, like `host_row_seeds`)."""
    return host_row_seeds(seed ^ _LANE_SALT, n_shards)


def route_rows(keys: jnp.ndarray, weights: jnp.ndarray, n_shards: int):
    """Send buffers of a key-routed exchange of stacked rows.

    keys/weights (R, N): each row's (key, weight) entries; those with
    weight > 0 are packed by owner (`route_of`) into (n_shards, R, N)
    buffers, padding (key 0, weight 0) after them.  Each owner has N slots
    per row, as many as the row has entries, so no owner's share can
    overflow and no entry is dropped.  Returns (keys, weights, dropped),
    `dropped` the weight of the entries that found no slot (counted all
    the same)."""
    r, n = keys.shape
    live = weights > 0
    dest = jnp.where(live, route_of(keys, n_shards), n_shards)
    order = jnp.argsort(dest, axis=1, stable=True)
    to = jnp.take_along_axis(dest, order, axis=1)
    counts = jax.vmap(lambda d: jnp.bincount(d, length=n_shards + 1))(dest)
    start = jnp.cumsum(counts, axis=1) - counts
    rank = jnp.arange(n)[None, :] - jnp.take_along_axis(start, to, axis=1)
    keep = (to < n_shards) & (rank < n)
    slot = jnp.where(keep, to * n + rank, n_shards * n)   # past the end: off
    at = jnp.arange(r)[:, None]
    buf_k = jnp.zeros((r, n_shards * n), jnp.uint32).at[at, slot].set(
        jnp.take_along_axis(keys, order, axis=1).astype(jnp.uint32),
        mode="drop")
    w_sorted = jnp.take_along_axis(weights, order, axis=1)
    buf_w = jnp.zeros((r, n_shards * n), weights.dtype).at[at, slot].set(
        w_sorted, mode="drop")
    dropped = jnp.where((to < n_shards) & ~keep, w_sorted, 0).sum()
    split = lambda b: b.reshape(r, n_shards, n).transpose(1, 0, 2)  # noqa: E731
    return split(buf_k), split(buf_w), dropped


def routed_exchange(keys: jnp.ndarray, weights: jnp.ndarray,
                    axis_name: str):
    """Route each row's (key, weight) entries to their owners (call inside
    shard_map over `axis_name`): pack them by `route_of` (`route_rows`)
    and swap the buffers with one `all_to_all` each.  Returns what this
    shard owns, (R, n_shards * N) keys and weights (the block from shard j
    at columns j*N..), and the weight dropped at this source (0)."""
    n_shards = jax.lax.axis_size(axis_name)
    send_k, send_w, dropped = route_rows(keys, weights, n_shards)
    recv_k = jax.lax.all_to_all(send_k, axis_name, 0, 0)
    recv_w = jax.lax.all_to_all(send_w, axis_name, 0, 0)
    r = keys.shape[0]
    join = lambda b: b.transpose(1, 0, 2).reshape(r, -1)  # noqa: E731
    return join(recv_k), join(recv_w), dropped


def routed_epoch(tables, tracker, queue, fill, rows, rng, *, spec,
                 cols: int, axis_name: str):
    """One flush epoch of a key-routed plane, on one shard (call inside
    shard_map over `axis_name`; every argument but `rows` is this shard's
    block, with a leading axis of 1).

    tables (1, T, d, w) this shard's key partition; tracker a (1, T, K)
    `TopK` of the keys it owns (K may be 0); queue (1, T, capw) its column
    share of the ring, fill (1, R) the rows' live slots in it; rows (R,)
    the active rows; rng (1, 2) this shard's uniform key.  In order: dedup
    the local share, route each key to its owner with its weight
    (`routed_exchange`), land the conservative update and re-score the
    candidates on the owned stack (`ops.update_score_rows`, in place), and
    re-select the heaps.  Returns (tables, tracker, stats), stats (1, 2):
    the weight this shard received and the weight dropped at it.
    """
    from repro.kernels import ops
    live = jnp.arange(cols)[None, :] < fill[0][:, None]
    keys, mult = jax.vmap(sk.dedup_weighted)(queue[0][rows, :cols],
                                             live.astype(jnp.float32))
    recv_k, recv_w, dropped = routed_exchange(keys, mult, axis_name)
    heap = topk.TopK(keys=tracker.keys[0][rows],
                     estimates=tracker.estimates[0][rows],
                     filled=tracker.filled[0][rows])
    cand, valid = topk.candidates(heap, recv_k, recv_w > 0)
    stack, est = ops.update_score_rows(tables[0], spec, recv_k, rng[0], rows,
                                       cand, weights=recv_w, engine="inplace")
    k = heap.keys.shape[1]
    if k:
        new = topk.reselect(cand, valid, est, k)
        tracker = topk.TopK(
            keys=tracker.keys.at[0, rows].set(new.keys),
            estimates=tracker.estimates.at[0, rows].set(new.estimates),
            filled=tracker.filled.at[0, rows].set(new.filled))
    stats = jnp.stack([recv_w.sum(), dropped.astype(recv_w.dtype)])
    return stack[None], tracker, stats[None]


def routed_query_rows(tables, rows, probes, *, spec, axis_name: str):
    """(R, P) estimates of `probes` (R, P) in rows `rows` (R,) of a
    key-routed plane (call inside shard_map over `axis_name`, tables this
    shard's (1, T, d, w) block, rows and probes replicated): each probe is
    read by its owner (`route_of`) from its own stack, the other shards
    add 0, and a `psum` gives every shard the answers."""
    n_shards = jax.lax.axis_size(axis_name)
    mine = route_of(probes, n_shards) == jax.lax.axis_index(axis_name)
    seeds = jnp.asarray(host_row_seeds(spec.seed, spec.depth), jnp.uint32)
    cols = jax.vmap(lambda k: row_hashes(k, seeds, spec.width))(probes)
    cells = tables[0][rows[:, None, None],
                      jnp.arange(spec.depth)[None, :, None], cols]
    est = spec.counter.decode(cells.min(axis=1))
    return jax.lax.psum(jnp.where(mine, est, 0.0), axis_name)


def _route_estimates_back(est: jnp.ndarray, recv_keys: jnp.ndarray,
                          slot_of_key: jnp.ndarray, kept: jnp.ndarray,
                          axis_name: str, n_shards: int, capacity: int
                          ) -> jnp.ndarray:
    """Return each shard's local estimates to the shards that asked.

    est/recv_keys: flattened received probes and their local estimates;
    sentinel (fill) probes are zeroed, estimates all_to_all back to their
    origin, and each origin re-orders them to align with its original
    keys.  Keys dropped by capacity overflow come back as -1.0.
    """
    est = jnp.where(recv_keys == SENTINEL, 0.0, est)
    back = jax.lax.all_to_all(est.reshape(n_shards, capacity), axis_name,
                              split_axis=0, concat_axis=0).reshape(-1)
    padded = jnp.concatenate([back, jnp.full((1,), -1.0, back.dtype)])
    out = padded[jnp.minimum(slot_of_key, n_shards * capacity)]
    return jnp.where(kept, out, -1.0)


def routed_query(local: sk.Sketch, keys: jnp.ndarray, axis_name: str,
                 capacity: int) -> jnp.ndarray:
    """Query a key-routed sketch; returns estimates aligned with `keys`.

    Keys dropped by capacity overflow return -1.0 (caller may retry or fall
    back to a replicated sketch; overflow is sized away in practice).
    """
    n_shards = jax.lax.axis_size(axis_name)
    buf, slot_of_key, kept = _dispatch_layout(keys, n_shards, capacity)
    recv = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0)
    flat = recv.reshape(-1)
    est = sk.query(local, flat)
    return _route_estimates_back(est, flat, slot_of_key, kept, axis_name,
                                 n_shards, capacity)


# --------------------------------------------------------------------------
# key-routed windows: bucket ring x routed dispatch, for windows too large
# for one chip.  Each shard owns a full ring for its key partition; every
# shard rotates on the same (replicated) schedule, so bucket b is the same
# time slice fleet-wide and window semantics survive the sharding.
# --------------------------------------------------------------------------

def routed_window_update(win, keys: jnp.ndarray, rng: jax.Array,
                         axis_name: str, capacity: int, epoch=None):
    """Update a key-routed bucket ring (call inside shard_map).

    Dispatches each key to its owning shard with the fixed-capacity
    all_to_all, then conservative-updates that shard's ACTIVE bucket
    (sentinel fill carries weight 0 -> no-op).

    epoch: optional event-time watermark (the interval index the batch
    belongs to, e.g. `CountService.epoch_of` or floor(ts / interval)) —
    a replicated device scalar.  When given, every shard first advances
    its ring by (epoch - win.epoch) rotations via the traced
    `window_advance_steps` (clamped at 0, so a stale epoch is a no-op
    rather than an error inside the collective), which replaces the
    caller-cadence `window_rotate` schedule: the stream's own timestamps
    keep every shard's bucket b meaning the same time slice.  Requires a
    ring initialized with a concrete epoch (`window_init(spec, epoch=0)`).
    """
    import repro.stream.window as w
    if epoch is not None:
        if win.epoch is None:
            raise ValueError("epoch-driven routed updates need a ring with "
                             "an initialized watermark: window_init(spec, "
                             "epoch=...)")
        steps = jnp.maximum(jnp.asarray(epoch, jnp.int32) - win.epoch, 0)
        win = w.window_advance_steps(win, steps)
    n_shards = jax.lax.axis_size(axis_name)
    buf, _, _ = _dispatch_layout(keys, n_shards, capacity)
    recv = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0)
    flat = recv.reshape(-1)
    valid = flat != SENTINEL
    return w.window_update(win, flat, rng,
                           weights=valid.astype(jnp.float32))


def routed_topk(tracker, axis_name: str, k: int | None = None):
    """Global heavy hitters over key-routed shards: candidate-set merge.

    Each shard refreshes a local `core.topk.TopK` against its own
    partition's sketch (its estimates are authoritative — the routing hash
    gives shards disjoint key sets), so the fleet-wide top-k is a pure
    merge: all_gather every shard's (K,) candidates + estimates + masks
    and re-select with one top_k.  The read-side analogue of `pmax_merge`
    — candidates are merged instead of counters, in O(shards * K) instead
    of O(d * w).  Call inside shard_map over `axis_name`; returns a
    replicated TopK of width `k` (default: the local tracker width).

    Replicated-lazy deployments (every worker counts the full stream)
    should pmax-merge tables first and refresh one tracker on the merged
    sketch instead: their candidate keys overlap, and this merge does not
    dedup across shards.
    """
    k = tracker.keys.shape[0] if k is None else k
    keys, est, filled = _gathered_candidates(tracker, axis_name)
    est = jnp.where(filled, est, -jnp.inf)
    top_est, idx = jax.lax.top_k(est, k)
    return topk.TopK(keys=keys[idx], estimates=top_est,
                     filled=top_est > -jnp.inf)


def _gathered_candidates(tracker, axis_name: str):
    """All-gather every shard's (K,) tracker row into flat fleet-wide
    candidate arrays — the merge step shared by `routed_topk` (re-select)
    and `routed_admit` (admission masks)."""
    keys = jax.lax.all_gather(tracker.keys, axis_name).reshape(-1)
    filled = jax.lax.all_gather(tracker.filled, axis_name).reshape(-1)
    est = jax.lax.all_gather(tracker.estimates, axis_name).reshape(-1)
    return keys, est, filled


def routed_admit(tracker, ids: jnp.ndarray, spec, axis_name: str):
    """Tracker-fed admission over key-routed shards: the all-gather
    candidate merge of `routed_topk` extended to admission masks.

    Each shard refreshes a local tracker against its own key partition
    (its estimates are authoritative — the routing hash gives shards
    disjoint key sets), so the fleet-wide hot set is the plain union of
    shard candidates: all_gather the (K,) rows, then admit each id iff it
    matches a gathered candidate whose estimate clears `spec.threshold`
    (`admission.admit_tracked` — same row-mapping policy as the
    single-chip plane, so shards and single-host serving agree on
    embedding layout).  `ids` is this shard's lookup batch; decisions are
    replicated because the gathered candidate set is.  Call inside
    shard_map over `axis_name`; returns (rows, admitted) aligned with
    ids.  spec: `admission.AdmissionSpec`.
    """
    keys, est, filled = _gathered_candidates(tracker, axis_name)
    return admission.admit_tracked(keys, est, filled, ids, spec)


def merged_metrics(values: jnp.ndarray, axis_name: str,
                   mode: str = "sum") -> jnp.ndarray:
    """Fleet-wide reduction of per-shard metric values (inside shard_map).

    The device half of `obs.registry.merge_snapshots`: each shard packs
    its local instrument values into a flat array (counters and histogram
    buckets under mode="sum", gauges/high-water under mode="max"), this
    all-gathers the per-shard rows and reduces them, and every shard gets
    the replicated fleet view to load back into a registry snapshot.
    all_gather + reduce rather than psum/pmax so the same helper also
    returns per-shard breakdowns if the caller keeps the gathered axis.
    """
    gathered = jax.lax.all_gather(values, axis_name)
    if mode == "sum":
        return gathered.sum(axis=0)
    if mode == "max":
        return gathered.max(axis=0)
    raise ValueError(f"unknown metric merge mode: {mode!r}")


def routed_window_query(win, keys: jnp.ndarray, axis_name: str,
                        capacity: int, n_buckets: int | None = None,
                        mode: str = "sum", gamma: float | None = None,
                        engine: str = "auto") -> jnp.ndarray:
    """Query a key-routed bucket ring; estimates aligned with `keys`.

    Each shard answers its partition's keys with ONE window-query
    dispatch (bucket reduction + lazy gamma^age decay weights, the same
    engine selection as the single-chip `window_query`), then routes the
    estimates back.  Keys dropped by capacity overflow return -1.0, as in
    `routed_query`.

    shard_map has no replication rule for pallas_call, so where "auto"
    takes the fused kernel (off-TPU, tables within VMEM) the enclosing
    shard_map must pass `check_vma=False`; engine="jnp" stays on the
    jitted reference under a replication-checked shard_map.
    """
    import repro.stream.window as w
    n_shards = jax.lax.axis_size(axis_name)
    buf, slot_of_key, kept = _dispatch_layout(keys, n_shards, capacity)
    recv = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0)
    flat = recv.reshape(-1)
    est = w.window_query(win, flat, n_buckets=n_buckets, mode=mode,
                         gamma=gamma, engine=engine)
    return _route_estimates_back(est, flat, slot_of_key, kept, axis_name,
                                 n_shards, capacity)
