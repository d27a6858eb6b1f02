"""Multi-tenant counting service: spec-bucketed planes + device-resident ingest.

A production counting plane serves many *logical* sketches — one per
product surface, per model, per experiment arm — and they do not all agree
on geometry.  `CountService` is therefore a registry of **planes**:

  * tenants sharing one `SketchSpec` stack into a `TenantPlane` whose
    tables form a single (T, d, w) device array, flushed and queried with
    ONE fused kernel launch each (`fused_update_pallas` /
    `fused_query_pallas`, grid (tenant, key-chunk), per-tenant table
    VMEM-resident, table buffer input/output aliased);
  * tenants with a *different* spec land in their own plane — heterogeneous
    widths/depths/counter kinds coexist in one service, each plane paying
    one launch, and `query_all` fans across planes and reassembles the
    per-tenant dict;
  * time-scoped tenants register with a `WindowSpec` and live in a
    `WindowPlane` storing every tenant's bucket ring natively as ONE
    resident (T, B, d, w) device leaf (per-tenant `WindowedSketch`es are
    views sliced at the API edge): `enqueue(name, keys, ts=...)` drives
    watermark rotation from event time — all crossing tenants rotate in
    ONE masked dispatch (`ops.window_advance_rows`) — and a flush
    reshapes the leaf to (T*B, d, w) (free) and lands every pending
    tenant's active bucket through the row-mapped fused kernel with the
    leaf donated and aliased in place: zero host-side ring restacks.

The ingest queue is **device-resident**: each plane owns a (T, capw)
uint32 ring appended by `kernels.ops.queue_append` — ONE scatter-append
launch per plane (`queue_append_pallas` on TPU: ring input/output
aliased, fill counters in SMEM; its bit-identical jitted XLA reference
elsewhere), so `enqueue` is a device call with no host round-trip — the
host keeps a deterministic fill mirror (it knows exactly what it
appended) and `flush` feeds `fused_update_pallas` straight from device
memory.  Keys are validated at the API boundary (integers in [0, 2^32) —
no silent truncation).

The flush is a **single-launch epoch**: the host fill mirror knows which
R of T rows have pending work, and with `track_top=K` the fused kernel
(`ops.update_score_rows`) grids over (R, chunk) via the SMEM row map,
lands the conservative update, AND re-scores each row's heavy-hitter
candidate union (standing heap + just-flushed keys) while the table block
is still VMEM-resident — one launch where the PR 4 pipeline paid an
update launch plus a fused-query launch, bit-identical to that pair (and
to the dense whole-plane flush: shared uniforms grid, skipped rows were
weight-0 no-ops).  The re-scored candidates re-select into a stacked
(T, K) device `TopK` tracker; windowed planes refresh through the stacked
multi-ring window query (`window_query_many` — ONE launch regardless of
flushed-tenant count, expiry/decay weights per ring).
`CountService.topk(name, k)` serves the heaps, and the tracker also feeds
the **admission plane**: `add_tenant(admission=AdmissionSpec(...))` +
`svc.admit(name, ids)` map raw ids to embedding rows, admitting exactly
the tracked candidates whose estimates clear the threshold — decisions
refresh with every flush epoch for free (`core/admission.admit_tracked`).

Construction with `tier=TierSpec(max_hot_tenants=N, policy=...)` turns on
**tiered hot/cold storage** (`stream.tiering`): each plane keeps only its
N most active tenants resident in the device stack and parks the rest in
a host-side numpy cold store (packed storage layout).  Cold tenants'
events accumulate in the host queue mirror and land through one batched
XLA-reference spill per epoch (`ops.tier_spill`, bit-identical to the hot
path); promotion/demotion rides the flush's active-row signal and swaps
via one gather→host copy + one host→device scatter per epoch.  The
hot-tier flush epoch stays ONE `update_score_rows` dispatch, and
`query_all`/`topk` answers are bit-identical to an all-resident service.

Construction with `mesh=` (a 1-D `jax.sharding.Mesh`) makes every plain
plane a `RoutedPlane`: its tables sharded by key over the mesh's axis
(shard s holds the (T, d, w) stack of the keys `sharded.route_of` gives
it), its ring sharded by column, and each flush epoch one `shard_map`
program that routes every key to its owner by `all_to_all` and updates
the owner's stack in place — the deployment for tables past one chip's
memory.  Windows, tiers, admission and snapshots are single-chip only.

Queries are read-your-writes: they flush pending events first.  The whole
service (tables + rings + fill mirrors + RNG lane + stats + trackers +
admission registry) snapshots and restores via `train/checkpoint`; the
manifest metadata records the plane layout (schema v8 — v2 adds
multi-plane, v3 the tracker state, v4 the admission policies, v5 the
metrics snapshot, v6 the packed-storage flag, v7 the native window leaf,
v8 the tier membership + cold store) and restore still accepts every
earlier version down to the v1 single-plane layout; `restore(track_top=K')`
re-arms the heaps at a different width (shrink keeps the best K', grow
cold-masks new slots).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import obs
from repro.core import admission as adm
from repro.core import sharded
from repro.core import sketch as sk
from repro.core import topk
from repro.core.counters import CounterSpec
from repro.core.sketch import Sketch, SketchSpec
from repro.kernels import ops
from repro.stream import tiering
from repro.stream import window as w
from repro.stream.tiering import TierSpec
from repro.train import checkpoint

# key validation is shared with core.admission (the same contract at every
# API boundary): floats/negatives/>32-bit raise instead of truncating
_as_keys = sk.as_uint32_keys


def _spec_meta(spec: SketchSpec) -> dict:
    c = spec.counter
    return {"width": spec.width, "depth": spec.depth, "seed": spec.seed,
            "packed": spec.packed,
            "counter": {"kind": c.kind, "base": c.base, "bits": c.bits}}


def _spec_from_meta(meta: dict) -> SketchSpec:
    # pre-v6 manifests carry no "packed" flag: those tables were stored
    # one-cell-per-lane, which is exactly packed=False
    return SketchSpec(width=meta["width"], depth=meta["depth"],
                      seed=meta["seed"],
                      packed=meta.get("packed", False),
                      counter=CounterSpec(**meta["counter"]))


class _RngLane:
    """Per-plane counter-based PRNG lane: flush number f draws the raw
    threefry key (seed, f).

    Distinct raw keys give independent threefry streams (the same
    guarantee `fold_in` provides, computed host-side for free), so a flush
    costs zero RNG dispatches and no device traffic.  Each plane counts
    its own flushes from the service seed, exactly as a dedicated
    single-spec service would — which is what makes a heterogeneous
    service bit-consistent with one service per spec.  The lane state is
    one integer, so it snapshots into the manifest metadata.
    """

    def __init__(self, seed: int, draws: int = 0):
        self.seed = int(seed) & 0xFFFF_FFFF
        self.draws = int(draws)

    def next(self) -> np.ndarray:
        key = np.asarray([self.seed, self.draws], np.uint32)
        self.draws += 1
        return key


class _DeviceRing:
    """(T, capw) device ring + deterministic host fill mirror.

    The ring only ever moves host->device (key microbatches) — the mirror
    is advanced by the same arithmetic the kernel applies, so no read-back
    is needed for control flow, flush trimming, or snapshots of `fill`.
    """

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self.queue = ops.queue_init(0, capacity)
        self.fill = np.zeros((0,), np.int64)

    def add_row(self) -> int:
        t = self.queue.shape[0]
        self.queue = jnp.concatenate(
            [self.queue, ops.queue_init(1, self.capacity)])
        self.fill = np.concatenate([self.fill, np.zeros((1,), np.int64)])
        return t

    def free(self, row: int) -> int:
        return self.capacity - int(self.fill[row])

    def append(self, rows: Sequence[int], batches: Sequence[np.ndarray]
               ) -> None:
        """Append per-row microbatches (caller guarantees they fit): one
        host-side staging pass, then ONE scatter-append launch."""
        n = max(b.size for b in batches)
        n_pad = ops.CHUNK * -(-n // ops.CHUNK)  # CHUNK-quantized launches
        keys = np.zeros((len(rows), n_pad), np.uint32)
        count = np.empty(len(rows), np.int32)
        for i, b in enumerate(batches):
            keys[i, :b.size] = b
            count[i] = b.size
        fill = self.fill[list(rows)].astype(np.int32)
        self.queue = ops.queue_append(self.queue, keys,
                                      np.asarray(rows, np.int32), fill, count)
        for r, b in zip(rows, batches):
            self.fill[r] += b.size

    def live_slice(self, rows=None):
        """(queue[:, :cols], weights (T, cols)) for a flush, device-side.

        cols is the fullest row's fill rounded up to the kernel CHUNK (so
        launch shapes stay quantized); stale slots ride along with weight
        0.  Only the (T,) fill vector crosses to the device (ONE fused
        dispatch, `ops.flush_inputs`).

        rows: optional (R,) active-row subset — gathers just those rows'
        queue slices and weights (`ops.flush_rows_inputs`, still one
        dispatch), the input side of the active-row flush.
        """
        fill = self.fill if rows is None else self.fill[rows]
        cols = min(self.queue.shape[1],
                   ops.CHUNK * -(-int(fill.max()) // ops.CHUNK))
        if rows is None:
            return ops.flush_inputs(self.queue, fill.astype(np.int32), cols)
        return ops.flush_rows_inputs(self.queue, fill.astype(np.int32),
                                     jnp.asarray(rows), cols)

    def class_slice(self, rows, cols: int):
        """`live_slice` for one fill class of the per-row flush trim: the
        caller (via `tiering.fill_classes`) groups active rows by their
        OWN CHUNK-rounded fill and gathers each class at its class width,
        so a skewed plane's upload bytes scale with each row's fill
        instead of the batch max."""
        return ops.flush_rows_inputs(self.queue,
                                     self.fill[rows].astype(np.int32),
                                     jnp.asarray(rows), cols)

    def reset(self) -> None:
        self.fill[:] = 0


class _TelemetryMixin:
    """Per-plane instruments, shared by both plane kinds.

    Every plane owns a label in its service's `MetricsRegistry` and keeps
    its ring-occupancy gauge (with automatic high-water), event/flush
    counters, and tenant-count gauge current from the host control path —
    zero device work.  A plane constructed standalone (tests, benchmarks)
    gets a private registry, so the instrument code never branches.  The
    label also names the plane in its `cms.*` host spans.
    """

    def _init_telemetry(self, metrics: Optional[obs.MetricsRegistry],
                        label: str) -> None:
        self.metrics = metrics if metrics is not None else obs.MetricsRegistry()
        self.label = label
        self._m_events = self.metrics.counter("plane_events", plane=label)
        self._m_flushes = self.metrics.counter("plane_flushes", plane=label)
        self._g_fill = self.metrics.gauge("ring_fill", plane=label)
        self._g_tenants = self.metrics.gauge("plane_tenants", plane=label)

    def note_append(self) -> None:
        """Refresh the ring-occupancy gauge after an append (the gauge's
        high-water mark records the worst queue pressure ever seen)."""
        self._g_fill.set(self.pending())

    def _note_flush(self, pending: int) -> None:
        self._m_events.inc(int(pending))
        self._m_flushes.inc()
        self._g_fill.set(0)


class _TrackerMixin:
    """Stacked (T, K) heavy-hitter tracker shared by both plane kinds."""

    track_top: Optional[int]
    tracker: Optional[topk.TopK]

    def _init_tracker(self, track_top: Optional[int]) -> None:
        self.track_top = track_top
        self.tracker = (None if track_top is None
                        else topk.init_stacked(0, track_top))

    def _grow_tracker(self) -> None:
        if self.tracker is not None:
            self.tracker = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b]), self.tracker,
                topk.init_stacked(1, self.track_top))

    def _scatter_tracker(self, rows, new: topk.TopK) -> None:
        tk = self.tracker
        self.tracker = topk.TopK(
            keys=tk.keys.at[rows].set(new.keys),
            estimates=tk.estimates.at[rows].set(new.estimates),
            filled=tk.filled.at[rows].set(new.filled))

    def _tracker_rows(self, rows) -> topk.TopK:
        tk = self.tracker
        return topk.TopK(keys=tk.keys[rows], estimates=tk.estimates[rows],
                         filled=tk.filled[rows])


class _TierMixin:
    """Hot/cold tier plumbing shared by both plane kinds.

    With `tier=None` every method degenerates to the all-resident
    behavior (device arrays indexed by tenant row, the `_DeviceRing` the
    only queue).  With a `TierSpec`, the device stacks are SLOT-indexed
    (H = min(max_hot_tenants, T) rows), the `tiering.PlaneTier` keeps the
    tenant-indexed host state (cold tables, queue mirror, fill mirror,
    recency/frequency signals), and the mixin routes queue traffic and
    runs the per-epoch rebalance swap."""

    tier: Optional[tiering.PlaneTier]

    def _init_tier(self, tspec: Optional[TierSpec], row_shape) -> None:
        if tspec is None:
            self.tier = None
            return
        self.tier = tiering.PlaneTier(tspec, row_shape,
                                      np.dtype(self.spec.storage_dtype),
                                      self.ring.capacity)
        self._g_hot = self.metrics.gauge("tier_hot_tenants",
                                         plane=self.label)
        self._g_cold = self.metrics.gauge("tier_cold_tenants",
                                          plane=self.label)
        self._m_promotions = self.metrics.counter("tier_promotions",
                                                  plane=self.label)
        self._m_demotions = self.metrics.counter("tier_demotions",
                                                 plane=self.label)
        self._m_spills = self.metrics.counter("tier_spill_events",
                                              plane=self.label)
        self._m_spill_bytes = self.metrics.counter("tier_spill_bytes",
                                                   plane=self.label)

    def _tier_gauges(self) -> None:
        if self.tier is not None:
            self._g_hot.set(self.tier.hot_count)
            self._g_cold.set(self.tier.cold_count)

    def pending(self) -> int:
        if self.tier is None:
            return int(self.ring.fill.sum())
        return self.tier.pending()

    def queue_free(self, row: int) -> int:
        """Free queue slots for one tenant (cold tenants buffer in the
        host mirror at the same capacity as the device ring)."""
        if self.tier is None:
            return self.ring.free(row)
        return self.tier.free(row)

    def queue_append_rows(self, rows, batches) -> None:
        """Route tenant microbatches into the queue: hot tenants append
        to the device ring at their slots (one scatter-append launch) AND
        to the host mirror (the mirror stages every append anyway, and
        keeping it authoritative for ALL tenants is what makes demotion
        free of device read-backs); cold tenants touch only the mirror —
        zero device work until they are promoted."""
        with obs.span("queue_append", plane=self.label,
                      rows=len(rows)) as sp:
            if obs.recording():
                sp.set_metadata(events=sum(int(b.size) for b in batches))
            if self.tier is None:
                self.ring.append(rows, batches)
                return
            t = self.tier
            hot = [i for i, r in enumerate(rows) if t.slot[r] >= 0]
            if hot:
                self.ring.append([int(t.slot[rows[i]]) for i in hot],
                                 [batches[i] for i in hot])
            t.mirror_append(rows, batches)

    def _tier_rebalance(self) -> None:
        """Post-flush swap: promote the hottest just-active cold tenants
        into idle victims' slots — ONE demotion gather + ONE promotion
        scatter per epoch, however many tenants swap.  The gather's host
        copy is the design's sanctioned device→host transfer (explicit
        `transfer_guard` allowance, so a pinned ingest path keeps its
        disallow guard)."""
        t = self.tier
        demote, promote = t.plan_swap()
        if demote.size:
            slots = t.slot[demote].copy()
            with jax.transfer_guard_device_to_host("allow"):
                t.cold[demote] = np.asarray(
                    ops.tier_demote(self.tables, slots))
            self.tables, self.ring.queue = ops.tier_promote(
                self.tables, self.ring.queue, slots,
                t.cold[promote], t.hqueue[promote])
            t.swap(demote, promote)
            self.ring.fill[slots] = t.hfill[promote]
            self._m_promotions.inc(int(promote.size))
            self._m_demotions.inc(int(demote.size))
        self._tier_gauges()

    def stacked_tables(self) -> jnp.ndarray:
        """Full tenant-ordered table stack reassembled across tiers (the
        all-resident layout — parity tests and cross-shard merges; see
        `sharded.tier_assemble`)."""
        if self.tier is None:
            return self.tables
        return sharded.tier_assemble(self.tables, self.tier.slot_tenant,
                                     self.tier.cold)


class TenantPlane(_TierMixin, _TrackerMixin, _TelemetryMixin):
    """Tenants sharing one SketchSpec: stacked (T, d, w) tables + ring."""

    def __init__(self, spec: SketchSpec, queue_capacity: int, seed: int = 0,
                 track_top: Optional[int] = None,
                 metrics: Optional[obs.MetricsRegistry] = None,
                 label: str = "p0",
                 tier: Optional[TierSpec] = None):
        self.spec = spec
        self.tables = jnp.zeros((0, spec.depth, spec.storage_width),
                                spec.storage_dtype)
        self.ring = _DeviceRing(queue_capacity)
        self.rng = _RngLane(seed)
        self.names: list[str] = []
        self._init_tracker(track_top)
        self._init_telemetry(metrics, label)
        self._init_tier(tier, (spec.depth, spec.storage_width))

    @property
    def queue_capacity(self) -> int:
        return self.ring.capacity

    def add(self, name: str) -> int:
        self.names.append(name)
        self._grow_tracker()
        self._g_tenants.set(len(self.names))
        if self.tier is None:
            zero = jnp.zeros((1, self.spec.depth, self.spec.storage_width),
                             self.spec.storage_dtype)
            self.tables = jnp.concatenate([self.tables, zero], axis=0)
            return self.ring.add_row()
        row, goes_hot = self.tier.add_row()
        if goes_hot:
            zero = jnp.zeros((1, self.spec.depth, self.spec.storage_width),
                             self.spec.storage_dtype)
            self.tables = jnp.concatenate([self.tables, zero], axis=0)
            self.ring.add_row()
        self._tier_gauges()
        return row

    def flush(self, dense: bool = False, reason: str = "explicit") -> int:
        """Land every tenant's pending events: ONE launch, update + refresh.

        The host fill mirror names the R rows with pending fill, and with
        tracking on the whole flush is a SINGLE-LAUNCH EPOCH
        (`ops.update_score_rows`): the fused kernel grids over (R, chunk)
        via the SMEM row map, runs the conservative update, then re-scores
        each row's candidate union — standing heap + flushed queue slice —
        against its still-VMEM-resident table block.  Tables land
        bit-identically to the dense whole-plane flush (shared uniforms
        grid; skipped rows were weight-0 no-ops) and the estimates equal
        a separate fused query over the updated tables, so the epoch is
        bit-identical to the old update-launch-then-query-launch pair
        minus a launch and a second table fetch.  Without tracking the
        update-only active-row path (`ops.update_rows`) remains.
        Active rows are grouped by their OWN CHUNK-rounded fill
        (`tiering.fill_classes`) so one hot tenant no longer inflates
        every cold-ish tenant's upload to the batch max; with uniform
        fills there is exactly one class and the epoch is the same single
        dispatch as before.  `dense=True` forces the legacy two-launch
        whole-plane pipeline (the benchmark baseline and the parity-test
        oracle).  `reason` (explicit / pressure / read / watermark) only
        labels the epoch's `cms.flush_epoch` span.
        """
        pending = self.pending()
        if pending == 0:
            return 0
        if self.tier is not None:
            if dense:
                raise ValueError("dense flush is the all-resident baseline "
                                 "pipeline; tiered planes have no resident "
                                 "whole-plane layout to run it on")
            return self._flush_tiered(pending, reason)
        rng = self.rng.next()
        active = np.flatnonzero(self.ring.fill).astype(np.int32)
        with obs.span("flush_epoch", cpu=True, plane=self.label,
                      rows=int(active.size), events=pending,
                      reason=reason) as ep:
            classes = tiering.fill_classes(self.ring.fill, active,
                                           self.ring.queue.shape[1])
            whole = dense or (self.tracker is None and len(classes) == 1
                              and active.size == len(self.names))
            if whole:
                # whole-plane update (the dense baseline, or an untracked
                # plane whose every row is pending at one fill class)
                with obs.span("flush.gather", rows=len(self.names),
                              cols=classes[-1][0]):
                    keys, weights = self.ring.live_slice()
                with obs.span("flush.update"):
                    self.tables = ops.update_many(self.tables, self.spec,
                                                  keys, rng, weights=weights)
                if dense and self.tracker is not None:
                    # two-launch baseline: a fused query refresh over the
                    # gathered rows
                    with obs.span("flush.reselect"):
                        sel = jnp.asarray(active)
                        self._refresh_topk(active, keys[sel], weights[sel])
            else:
                for cols, rows_g in classes:
                    with obs.span("flush.gather", rows=int(rows_g.size),
                                  cols=cols):
                        keys, weights = self.ring.class_slice(rows_g, cols)
                    if self.tracker is None:
                        with obs.span("flush.update"):
                            self.tables = ops.update_rows(
                                self.tables, self.spec, keys, rng, rows_g,
                                weights=weights)
                        continue
                    with obs.span("flush.candidates"):
                        rows_d = jnp.asarray(rows_g)
                        cand, valid = topk.candidates(
                            self._tracker_rows(rows_d), keys, weights > 0)
                    with obs.span("flush.update"):
                        self.tables, est = ops.update_score_rows(
                            self.tables, self.spec, keys, rng, rows_g, cand,
                            weights=weights)
                    with obs.span("flush.reselect"):
                        self._scatter_tracker(
                            rows_d, topk.reselect(cand, valid, est,
                                                  self.track_top))
            self.ring.reset()
            if obs.recording():
                ep.set_metadata(classes=1 if whole else len(classes))
        self._note_flush(pending)
        return pending

    def _flush_tiered(self, pending: int, reason: str) -> int:
        """Tiered flush epoch: per fill class, hot tenants land through
        the SAME fused dispatch an all-resident plane issues (uniforms
        drawn from the full-tenant grid via `uniform_rows`, rows mapped
        tenant→slot) and cold tenants through one batched XLA-reference
        spill (`ops.tier_spill`, identical dedup + uniforms grid) — so
        every tenant's table lands bit-identical to the resident service.
        The epoch ends with the recency stamp and the rebalance swap."""
        t = self.tier
        rng = self.rng.next()
        total = len(self.names)
        active = np.flatnonzero(t.hfill).astype(np.int32)
        classes = tiering.fill_classes(t.hfill, active, t.capw)
        with obs.span("flush_epoch", cpu=True, plane=self.label,
                      rows=int(active.size), events=pending,
                      classes=len(classes), reason=reason):
            for cols, rows_g in classes:
                slot_g = t.slot[rows_g]
                hot_g = rows_g[slot_g >= 0]
                cold_g = rows_g[slot_g < 0]
                if hot_g.size:
                    slots = t.slot[hot_g].astype(np.int32)
                    with obs.span("flush.gather", rows=int(hot_g.size),
                                  cols=cols):
                        keys, weights = ops.flush_rows_inputs(
                            self.ring.queue,
                            t.hfill[hot_g].astype(np.int32),
                            jnp.asarray(slots), cols)
                    if self.tracker is not None:
                        with obs.span("flush.candidates"):
                            rows_d = jnp.asarray(hot_g)
                            cand, valid = topk.candidates(
                                self._tracker_rows(rows_d), keys,
                                weights > 0)
                        with obs.span("flush.update"):
                            self.tables, est = ops.update_score_rows(
                                self.tables, self.spec, keys, rng, slots,
                                cand, weights=weights,
                                uniform_rows=(total, hot_g))
                        with obs.span("flush.reselect"):
                            self._scatter_tracker(
                                rows_d, topk.reselect(cand, valid, est,
                                                      self.track_top))
                    else:
                        with obs.span("flush.update"):
                            self.tables = ops.update_rows(
                                self.tables, self.spec, keys, rng, slots,
                                weights=weights,
                                uniform_rows=(total, hot_g))
                if cold_g.size:
                    with obs.span("tier_spill", plane=self.label,
                                  rows=int(cold_g.size)):
                        self._tier_spill(cold_g, cols, rng, total)
            self.ring.reset()
            t.note_flush(active)
            self._tier_rebalance()
        self._note_flush(pending)
        return pending

    def _tier_spill(self, rows_g: np.ndarray, cols: int, rng, total: int
                    ) -> None:
        """Land one fill class of cold tenants from the host queue mirror
        into the cold store (buffered spill): batched dedup + Morris
        update through the jitted XLA reference engine, uniforms drawn
        from the SAME (T, cols) grid rows the hot dispatch consumes —
        per-row bit-identical to flushing the tenant resident."""
        t = self.tier
        keys = jnp.asarray(t.hqueue[rows_g, :cols])
        weights = jnp.asarray(
            (np.arange(cols) < t.hfill[rows_g, None]).astype(np.float32))
        stack = jnp.asarray(t.cold[rows_g])
        with jax.transfer_guard_device_to_host("allow"):
            if self.tracker is not None:
                rows_d = jnp.asarray(rows_g)
                cand, valid = topk.candidates(self._tracker_rows(rows_d),
                                              keys, weights > 0)
                new, est = ops.tier_spill(stack, self.spec, keys, rng,
                                          weights, (total, rows_g),
                                          cand=cand)
                self._scatter_tracker(rows_d,
                                      topk.reselect(cand, valid, est,
                                                    self.track_top))
            else:
                new = ops.tier_spill(stack, self.spec, keys, rng, weights,
                                     (total, rows_g))
            t.cold[rows_g] = np.asarray(new)
        self._m_spills.inc(int(rows_g.size))
        self._m_spill_bytes.inc(2 * int(rows_g.size)
                                * self.spec.memory_bytes)

    def _refresh_topk(self, rows, keys, weights) -> None:
        """Two-launch tracker refresh (the dense-baseline path): candidate
        union scored with a separate fused query launch over the gathered
        active tables; stale queue slots (weight 0) masked out of
        candidacy.  The default flush path instead gets these estimates
        from the update kernel itself."""
        rows_d = jnp.asarray(rows)
        tables = self.tables[rows_d]
        new = topk.refresh_stacked(
            self._tracker_rows(rows_d), keys, weights > 0,
            lambda ck: ops.query_many(tables, self.spec, ck))
        self._scatter_tracker(rows_d, new)

    def topk_row(self, row: int):
        """(keys, estimates, filled) of one tenant's heap, estimate-sorted.

        Plain tables only change on flush, and every flush refreshes the
        rows it touched, so the stored estimates ARE the current query
        answers — no rescore needed on the read path."""
        tk = self.tracker
        return (np.asarray(tk.keys[row]), np.asarray(tk.estimates[row]),
                np.asarray(tk.filled[row]))

    def query_rows(self, keys: jnp.ndarray) -> jnp.ndarray:
        """(T, N) estimates, tenant-ordered.  All-resident: ONE fused
        launch (keys (N,) broadcast or (T, N)).  Tiered: the fused launch
        serves the hot slots and the XLA reference engine serves the cold
        stack (bit-identical estimators), reassembled in tenant order."""
        if self.tier is None:
            return ops.query_many(self.tables, self.spec, keys)
        t = self.tier
        keys = jnp.asarray(keys)
        per_tenant = keys.ndim == 2
        out = np.zeros((len(self.names), keys.shape[-1]), np.float32)
        st = t.slot_tenant
        cold = np.flatnonzero(t.slot < 0).astype(np.int32)
        with jax.transfer_guard_device_to_host("allow"):
            if st.size:
                hk = keys[jnp.asarray(st)] if per_tenant else keys
                out[st] = np.asarray(
                    ops.query_many(self.tables, self.spec, hk))
            if cold.size:
                ck = keys[jnp.asarray(cold)] if per_tenant else keys
                out[cold] = np.asarray(ops.tier_query(
                    jnp.asarray(t.cold[cold]), self.spec, ck))
        return jnp.asarray(out)

    def query_row(self, row: int, keys: jnp.ndarray) -> jnp.ndarray:
        """Estimates for one tenant: ONE `ops.query_row` over the device
        stack at the tenant's row (its slot, when tiered); a cold tenant's
        host row goes up as a one-row stack and is read as row 0."""
        tables, at = self.tables, row
        if self.tier is not None:
            at = int(self.tier.slot[row])
            if at < 0:
                tables, at = jnp.asarray(self.tier.cold[row][None]), 0
        return ops.query_row(tables, self.spec, at, keys)

    def table_row(self, row: int) -> jnp.ndarray:
        """One tenant's table in the all-resident layout (hot tenants
        slice the device stack at their slot; cold tenants upload their
        host row on demand)."""
        if self.tier is None:
            return self.tables[row]
        slot = int(self.tier.slot[row])
        if slot >= 0:
            return self.tables[slot]
        return jnp.asarray(self.tier.cold[row])


class WindowPlane(_TierMixin, _TrackerMixin, _TelemetryMixin):
    """Watermark-windowed tenants sharing one WindowSpec, stored natively
    as ONE resident (T, B, d, w) device leaf.

    Per-tenant `WindowedSketch`es are sliced views at the API edge
    (`win_view` / the `wins` property); every hot-path operation runs on
    the stacked leaf directly.  A flush reshapes the leaf (T, B, d, w) ->
    (T*B, d, w) — free, no copy — and lands the R pending tenants' events
    in their active buckets (flat row `tenant*B + cursor`) through the
    row-mapped fused kernel with the leaf DONATED and aliased in place:
    zero host-side ring restacks, unlisted tenants' cells persist.  The
    tracker refresh reads the leaf through the row-mapped stacked window
    query, and watermark rotation clears every crossing tenant's expired
    buckets in ONE masked device op (`ops.window_advance_rows`) instead
    of one dispatch per tenant.  Event time (`ts`) drives rotation:
    crossing an interval boundary flushes buffered events into their own
    interval's bucket first, then advances the ring (so bucket b still
    holds exactly the events of one interval, as in the single-tenant
    watermark path).  Cursors/watermarks are host mirrors — the control
    path never reads a device scalar back.
    """

    def __init__(self, wspec: w.WindowSpec, queue_capacity: int,
                 seed: int = 0, track_top: Optional[int] = None,
                 metrics: Optional[obs.MetricsRegistry] = None,
                 label: str = "w0",
                 tier: Optional[TierSpec] = None):
        self.wspec = wspec
        s = wspec.sketch
        # the native window leaf: (T, B, d, w_storage), all tenants' rings
        self.tables = jnp.zeros((0, wspec.buckets, s.depth, s.storage_width),
                                s.storage_dtype)
        # host mirror of each tenant's active-bucket cursor (rotation is
        # host-deterministic, so flush/rotation never read device scalars)
        self.cursors = np.zeros((0,), np.int32)
        self.ring = _DeviceRing(queue_capacity)
        self.rng = _RngLane(seed)
        self.names: list[str] = []
        # host mirror of each ring's watermark interval: enqueue-time
        # watermark checks must not read a device scalar back on the
        # ingest hot path
        self.epochs: list[Optional[int]] = []
        self._init_tracker(track_top)
        self._init_telemetry(metrics, label)
        self._m_rotations = self.metrics.counter("plane_rotations",
                                                 plane=label)
        # one masked device op per advance_many that rotated anything —
        # the gauge pair that proves multi-tenant rotation is ONE dispatch
        self._m_rotation_dispatches = self.metrics.counter(
            "rotation_dispatches", plane=label)
        self._g_leaf_bytes = self.metrics.gauge("window_leaf_bytes",
                                                plane=label)
        # per-tenant watermark gauges, cached so a timestamped enqueue
        # costs two attribute pokes, not a registry lookup
        self._g_epoch: list = []
        self._g_lag: list = []
        self._init_tier(tier, (wspec.buckets, s.depth, s.storage_width))

    @property
    def spec(self) -> SketchSpec:
        return self.wspec.sketch

    @property
    def queue_capacity(self) -> int:
        return self.ring.capacity

    def win_view(self, row: int) -> w.WindowedSketch:
        """One tenant's ring as a `WindowedSketch` view (API edge only:
        snapshot inspection, per-tenant query/merge — the hot paths stay
        on the stacked leaf)."""
        ep = self.epochs[row]
        if self.tier is None:
            tb = self.tables[row]
        else:
            slot = int(self.tier.slot[row])
            tb = (self.tables[slot] if slot >= 0
                  else jnp.asarray(self.tier.cold[row]))
        return w.WindowedSketch(
            tables=tb,
            cursor=jnp.asarray(self.cursors[row], jnp.int32),
            spec=self.wspec,
            epoch=None if ep is None else jnp.asarray(ep, jnp.int32))

    @property
    def wins(self) -> list:
        """Per-tenant `WindowedSketch` views (read-only convenience; the
        plane's state of record is the stacked leaf + host mirrors)."""
        return [self.win_view(r) for r in range(len(self.names))]

    def add(self, name: str) -> int:
        s = self.spec
        self.cursors = np.concatenate(
            [self.cursors, np.zeros((1,), np.int32)])
        self.names.append(name)
        self.epochs.append(None)
        self._grow_tracker()
        self._g_tenants.set(len(self.names))
        self._g_epoch.append(self.metrics.gauge("watermark_epoch",
                                                plane=self.label, tenant=name))
        self._g_lag.append(self.metrics.gauge("watermark_lag",
                                              plane=self.label, tenant=name))
        zero = jnp.zeros((1, self.wspec.buckets, s.depth, s.storage_width),
                         s.storage_dtype)
        if self.tier is None:
            self.tables = jnp.concatenate([self.tables, zero], axis=0)
            self._g_leaf_bytes.set(self.tables.size
                                   * self.tables.dtype.itemsize)
            return self.ring.add_row()
        row, goes_hot = self.tier.add_row()
        if goes_hot:
            self.tables = jnp.concatenate([self.tables, zero], axis=0)
            self.ring.add_row()
        self._g_leaf_bytes.set(self.tables.size * self.tables.dtype.itemsize)
        self._tier_gauges()
        return row

    def advance(self, row: int, ts, flush_cb) -> None:
        """Advance one tenant's watermark to own `ts` (see `advance_many`)."""
        self.advance_many([(row, ts)], flush_cb)

    def advance_many(self, items, flush_cb) -> None:
        """Advance tenants' watermarks to own their timestamps, flushing
        first if buffered events would otherwise leak into new intervals.

        items: [(row, ts)] pairs.  Watermark comparisons run against the
        host epoch mirror, so same-interval enqueues (the common case)
        cost no device work and no read-back.  All boundary crossings are
        collected and applied to the stacked leaf in ONE masked rotation
        dispatch (`ops.window_advance_rows`, steps == 0 rows untouched) —
        multi-tenant rotation no longer pays one `window_advance_steps`
        per tenant.  If any rotating row has buffered fill, everything
        flushes ONCE before the rotation (into the pre-rotation buckets,
        exactly as the per-tenant path did)."""
        t = len(self.names)
        steps = np.zeros(t, np.int32)
        for row, ts in items:
            target = w.interval_epoch(self.wspec, ts)
            have = self.epochs[row]
            if have is None:
                self.epochs[row] = target
                self._g_epoch[row].set(target)
                continue
            have += int(steps[row])  # earlier items in this same call
            if target < have:
                raise ValueError(
                    f"non-monotone watermark: ts {ts} (interval {target}) "
                    f"is behind the ring's watermark interval {have}")
            # the lag gauge reads how far ahead of the standing watermark
            # this batch arrived (0 = same interval); its high-water is the
            # worst rotation fast-forward the tenant has ever forced
            self._g_lag[row].set(target - have)
            steps[row] += target - have
        rot = np.flatnonzero(steps).astype(np.int32)
        if rot.size == 0:
            return
        pend = (self.ring.fill[rot].any() if self.tier is None
                else self.tier.hfill[rot].any())
        if pend:
            flush_cb()  # rebinds self.tables: rotation reads the new leaf
        if self.tier is None:
            with obs.span("window_rotate", plane=self.label,
                          rows=int(rot.size)):
                self.tables = ops.window_advance_rows(
                    self.tables, self.cursors, steps)
            self._m_rotation_dispatches.inc()
        else:
            # hot tenants rotate on the slot-indexed device leaf in one
            # masked dispatch; cold tenants rotate their host leaves with
            # the bit-identical numpy mirror of the rotation mask
            t_ = self.tier
            st = t_.slot_tenant
            if st.size and steps[st].any():
                with obs.span("window_rotate", plane=self.label,
                              rows=int(rot.size)):
                    self.tables = ops.window_advance_rows(
                        self.tables, self.cursors[st], steps[st])
                self._m_rotation_dispatches.inc()
            for row in rot:
                if t_.slot[row] < 0:
                    t_.cold[row] = w.cold_advance(t_.cold[row],
                                                  int(self.cursors[row]),
                                                  int(steps[row]))
        self.cursors = (self.cursors + steps) % self.wspec.buckets
        for row in rot:
            self.epochs[row] += int(steps[row])
            self._g_epoch[row].set(self.epochs[row])
        self._m_rotations.inc(int(steps.sum()))

    def flush(self, dense: bool = False, reason: str = "explicit") -> int:
        """Land every pending tenant's events in its ACTIVE bucket —
        straight on the native leaf, zero restack copies.

        The (T, B, d, w) leaf reshapes to (T*B, d, w) — free, same buffer
        — and the R pending tenants' batches land at flat rows
        `tenant*B + cursor` through the row-mapped fused kernel
        (`ops.update_rows`) with the leaf DONATED and in/out aliased:
        no active-bucket gather, no per-tenant scatter-back loop, and
        unlisted rows' cells persist by the aliasing contract.  The
        uniforms grid spans the full tenant plane (`uniform_rows`), so
        the result is bit-identical to the dense restack flush
        (`dense=True` — the legacy gather/`update_many`/scatter pipeline,
        kept as the parity oracle and benchmark baseline).  The tracker
        refresh scores candidates through the row-mapped stacked window
        query, so rotation, expiry, and decay reorder the heap alongside
        the new mass.  `reason` only labels the `cms.flush_epoch` span.
        """
        pending = self.pending()
        if pending == 0:
            return 0
        if self.tier is not None:
            if dense:
                raise ValueError("dense flush is the all-resident baseline "
                                 "pipeline; tiered planes have no resident "
                                 "whole-plane layout to run it on")
            return self._flush_tiered(pending, reason)
        rng = self.rng.next()
        t = len(self.names)
        b = self.wspec.buckets
        rows = (np.arange(t, dtype=np.int32) if dense
                else np.flatnonzero(self.ring.fill).astype(np.int32))
        with obs.span("flush_epoch", cpu=True, plane=self.label,
                      rows=int(rows.size), events=pending,
                      reason=reason) as ep:
            kw = None
            if dense:
                with obs.span("flush.gather", rows=t):
                    keys, weights = self.ring.live_slice()
                # legacy restack pipeline: gather active buckets into an
                # (R, d, w) stack, dense launch, scatter each bucket back
                with obs.span("flush.update"):
                    stack = jnp.stack([self.tables[r, self.cursors[r]]
                                       for r in rows])
                    stack = ops.update_many(stack, self.spec, keys, rng,
                                            weights=weights,
                                            uniform_rows=(t, rows))
                    tables = self.tables
                    for i, r in enumerate(rows):
                        tables = tables.at[r, self.cursors[r]].set(stack[i])
                    self.tables = tables
                kw = (keys, weights)
                n_classes = 1
            else:
                classes = tiering.fill_classes(self.ring.fill, rows,
                                               self.ring.queue.shape[1])
                n_classes = len(classes)
                flat = self.tables.reshape((t * b,) + self.tables.shape[2:])
                for cols, rows_g in classes:
                    with obs.span("flush.gather", rows=int(rows_g.size),
                                  cols=cols):
                        keys, weights = self.ring.class_slice(rows_g, cols)
                    flat_rows = rows_g * b + self.cursors[rows_g]
                    with obs.span("flush.update"):
                        flat = ops.update_rows(
                            flat, self.spec, keys, rng, flat_rows,
                            weights=weights, uniform_rows=(t, rows_g),
                            donate=True)
                    if len(classes) == 1:
                        kw = (keys, weights)
                self.tables = flat.reshape((t, b) + flat.shape[1:])
            if self.tracker is not None:
                if kw is None:
                    # multi-class epoch: one batch-max re-gather for the
                    # refresh (stale padding is weight-0, so candidacy is
                    # identical to per-class gathers)
                    with obs.span("flush.gather", rows=int(rows.size)):
                        kw = self.ring.live_slice(rows)
                with obs.span("flush.reselect"):
                    self._refresh_topk(rows, *kw)
            self.ring.reset()
            if obs.recording():
                ep.set_metadata(classes=n_classes)
        self._note_flush(pending)
        return pending

    def _flush_tiered(self, pending: int, reason: str) -> int:
        """Tiered window flush epoch: per fill class, hot tenants land in
        their ACTIVE buckets through the same flat row-mapped dispatch an
        all-resident plane issues (flat row `slot*B + cursor`, uniforms
        over the full-tenant grid) and cold tenants spill their active
        bucket from the host queue mirror through `ops.tier_spill` — then
        ONE cross-tier tracker refresh, the recency stamp, and the
        rebalance swap."""
        t_ = self.tier
        rng = self.rng.next()
        total = len(self.names)
        b = self.wspec.buckets
        active = np.flatnonzero(t_.hfill).astype(np.int32)
        classes = tiering.fill_classes(t_.hfill, active, t_.capw)
        with obs.span("flush_epoch", cpu=True, plane=self.label,
                      rows=int(active.size), events=pending,
                      classes=len(classes), reason=reason):
            for cols, rows_g in classes:
                slot_g = t_.slot[rows_g]
                hot_g = rows_g[slot_g >= 0]
                cold_g = rows_g[slot_g < 0]
                if hot_g.size:
                    slots = t_.slot[hot_g].astype(np.int32)
                    with obs.span("flush.gather", rows=int(hot_g.size),
                                  cols=cols):
                        keys, weights = ops.flush_rows_inputs(
                            self.ring.queue,
                            t_.hfill[hot_g].astype(np.int32),
                            jnp.asarray(slots), cols)
                    h = self.tables.shape[0]
                    flat = self.tables.reshape((h * b,)
                                               + self.tables.shape[2:])
                    flat_rows = slots * b + self.cursors[hot_g]
                    with obs.span("flush.update"):
                        flat = ops.update_rows(
                            flat, self.spec, keys, rng, flat_rows,
                            weights=weights, uniform_rows=(total, hot_g),
                            donate=True)
                    self.tables = flat.reshape((h, b) + flat.shape[1:])
                if cold_g.size:
                    with obs.span("tier_spill", plane=self.label,
                                  rows=int(cold_g.size)):
                        self._tier_spill_window(cold_g, cols, rng, total)
            if self.tracker is not None:
                with obs.span("flush.reselect"):
                    self._refresh_topk_tiered(active)
            self.ring.reset()
            t_.note_flush(active)
            self._tier_rebalance()
        self._note_flush(pending)
        return pending

    def _tier_spill_window(self, rows_g: np.ndarray, cols: int, rng,
                           total: int) -> None:
        """Spill one fill class of cold windowed tenants: their ACTIVE
        bucket slices batch through the XLA reference engine with the
        same full-grid uniforms the hot dispatch consumes, landing back
        in the host leaves bit-identical to a resident flush."""
        t_ = self.tier
        keys = jnp.asarray(t_.hqueue[rows_g, :cols])
        weights = jnp.asarray(
            (np.arange(cols) < t_.hfill[rows_g, None]).astype(np.float32))
        stack = jnp.asarray(t_.cold[rows_g, self.cursors[rows_g]])
        with jax.transfer_guard_device_to_host("allow"):
            new = ops.tier_spill(stack, self.spec, keys, rng, weights,
                                 (total, rows_g))
            t_.cold[rows_g, self.cursors[rows_g]] = np.asarray(new)
        self._m_spills.inc(int(rows_g.size))
        self._m_spill_bytes.inc(2 * int(rows_g.size)
                                * self.spec.memory_bytes)

    def _refresh_topk_tiered(self, active: np.ndarray) -> None:
        """Cross-tier stacked heap refresh: hot tenants score through the
        row-mapped stacked window query on the device leaf; cold tenants
        upload their leaves and run the SAME query family (the window
        reduce's "sum" rounding differs between engine families at 1 ulp,
        so tier parity requires one engine for both).  Per-row results
        match the resident service's single refresh because the stacked
        refresh is row-independent and both gathers run at the same
        batch-max width."""
        t_ = self.tier
        hot_a = active[t_.slot[active] >= 0]
        cold_a = active[t_.slot[active] < 0]
        cols = min(t_.capw,
                   ops.CHUNK * -(-int(t_.hfill[active].max()) // ops.CHUNK))
        for rows_a, hot in ((hot_a, True), (cold_a, False)):
            if rows_a.size == 0:
                continue
            rows_d = jnp.asarray(rows_a)
            wts = w.window_weights_stacked(self.cursors[rows_a],
                                           self.wspec.buckets)
            if hot:
                slots = t_.slot[rows_a].astype(np.int32)
                keys, weights = ops.flush_rows_inputs(
                    self.ring.queue, t_.hfill[rows_a].astype(np.int32),
                    jnp.asarray(slots), cols)
                qfn = (lambda ck, s=slots: ops.window_query_stacked(
                    self.tables, self.spec, ck, wts, rows=s))
            else:
                keys = jnp.asarray(t_.hqueue[rows_a, :cols])
                weights = jnp.asarray(
                    (np.arange(cols)
                     < t_.hfill[rows_a, None]).astype(np.float32))
                stack = jnp.asarray(t_.cold[rows_a])
                qfn = (lambda ck, st=stack: ops.window_query_stacked(
                    st, self.spec, ck, wts))
            new = topk.refresh_stacked(self._tracker_rows(rows_d), keys,
                                       weights > 0, qfn)
            self._scatter_tracker(rows_d, new)

    def _refresh_topk(self, rows, keys, weights) -> None:
        """Stacked heap refresh for the flushed window tenants: candidates
        are scored through the row-mapped stacked multi-ring window query
        against the native leaf, so expired buckets pull candidates down
        and fresh mass pushes them up in the same re-selection — ONE query
        launch regardless of how many tenants flushed, each ring carrying
        its own weight row (`window_weights_stacked` over the cursor
        mirror, one evaluation for all rings).
        """
        rows_d = jnp.asarray(rows)
        wts = w.window_weights_stacked(self.cursors[rows], self.wspec.buckets)
        new = topk.refresh_stacked(
            self._tracker_rows(rows_d), keys, weights > 0,
            lambda ck: ops.window_query_stacked(self.tables, self.spec, ck,
                                                wts, rows=rows))
        self._scatter_tracker(rows_d, new)

    def topk_row(self, row: int, n_buckets: Optional[int] = None,
                 mode: str = "sum", gamma: Optional[float] = None,
                 engine: str = "auto"):
        """(keys, estimates, filled) of one tenant's heap.

        Window estimates move without any flush (watermark rotation,
        expiry, query-time decay), so the read path re-scores the standing
        candidates against the current ring — forwarding n_buckets / mode
        / gamma through the stacked query's weight row — and persists the
        re-ordered heap before answering.
        """
        rows = np.asarray([row], np.int32)
        wts = w.window_weights_stacked(self.cursors[rows],
                                       self.wspec.buckets,
                                       n_buckets=n_buckets, gamma=gamma)
        rows_d = jnp.asarray(rows)
        if self.tier is not None and int(self.tier.slot[row]) < 0:
            # cold tenant: score the uploaded host leaf with the same
            # stacked query family (tier parity, see _refresh_topk_tiered)
            stack = jnp.asarray(self.tier.cold[rows])
            qfn = (lambda ck: ops.window_query_stacked(
                stack, self.spec, ck, wts, mode=mode, engine=engine))
        else:
            qrows = (rows if self.tier is None
                     else self.tier.slot[rows].astype(np.int32))
            qfn = (lambda ck: ops.window_query_stacked(
                self.tables, self.spec, ck, wts, mode=mode, engine=engine,
                rows=qrows))
        new = topk.refresh_stacked(
            self._tracker_rows(rows_d), jnp.zeros((1, 0), jnp.uint32), None,
            qfn)
        self._scatter_tracker(rows_d, new)
        tk = self.tracker
        return (np.asarray(tk.keys[row]), np.asarray(tk.estimates[row]),
                np.asarray(tk.filled[row]))

    def query_row(self, row: int, keys: jnp.ndarray, **kw) -> jnp.ndarray:
        """Window estimate for one tenant (fused in-kernel bucket reduce;
        cold tenants query through the same reduce on their uploaded
        leaf — `win_view` handles the tier)."""
        return w.window_query(self.win_view(row), keys, **kw)

    def query_rows(self, keys: jnp.ndarray) -> jnp.ndarray:
        """(T, N) window estimates, tenant-ordered: ONE stacked launch.

        keys: (N,) probes shared by every tenant (broadcast — free, no
        copy) or (T, N) per-tenant probes.  Each tenant's default read
        resolves into its own row of ONE `window_weights_stacked`
        evaluation (its cursor off the host mirror, the full-ring
        n_buckets / sum-mode defaults `query_row` serves), so `query_all`
        over W windowed tenants costs ONE `window_query_stacked` dispatch
        instead of W per-ring `window_query` launches — and row r stays
        bit-identical to `query_row(r, keys)` by the stacked kernel's
        per-ring contract.  Tiered planes answer hot tenants through the
        stacked query on the slot-ordered device leaf and cold tenants
        through the SAME query family on their uploaded host leaves
        (one engine family, as in `_refresh_topk_tiered`), reassembled in
        tenant order.
        """
        t = len(self.names)
        b = self.wspec.buckets
        keys = jnp.asarray(keys)
        per_tenant = keys.ndim == 2

        def probes_of(rows: np.ndarray) -> jnp.ndarray:
            if per_tenant:
                return keys[jnp.asarray(rows)]
            return jnp.broadcast_to(keys[None], (len(rows),) + keys.shape)

        if self.tier is None:
            all_rows = np.arange(t, dtype=np.int32)
            wts = w.window_weights_stacked(self.cursors, b)
            return ops.window_query_stacked(self.tables, self.spec,
                                            probes_of(all_rows), wts)
        t_ = self.tier
        out = np.zeros((t, keys.shape[-1]), np.float32)
        st = t_.slot_tenant
        cold = np.flatnonzero(t_.slot < 0).astype(np.int32)
        with jax.transfer_guard_device_to_host("allow"):
            if st.size:
                wts = w.window_weights_stacked(self.cursors[st], b)
                out[st] = np.asarray(ops.window_query_stacked(
                    self.tables, self.spec, probes_of(st), wts))
            if cold.size:
                wts = w.window_weights_stacked(self.cursors[cold], b)
                out[cold] = np.asarray(ops.window_query_stacked(
                    jnp.asarray(t_.cold[cold]), self.spec,
                    probes_of(cold), wts))
        return jnp.asarray(out)

    def table_row(self, row: int) -> jnp.ndarray:
        """One tenant's ACTIVE bucket table across tiers."""
        cur = self.cursors[row]
        if self.tier is None:
            return self.tables[row, cur]
        slot = int(self.tier.slot[row])
        if slot >= 0:
            return self.tables[slot, cur]
        return jnp.asarray(self.tier.cold[row, cur])


class RoutedPlane(_TelemetryMixin):
    """Tenants sharing one SketchSpec, their tables sharded by key over a
    1-D mesh (`CountService(mesh=...)`): the key-routed deployment, for
    tables too large for one chip.

    Shard s of the mesh axis holds the (T, d, w) stack of the keys it owns
    (`sharded.route_of(key) == s`), so `spec.width` is one shard's width
    and the plane counts over n * w columns per row.  The queue ring is
    sharded by column: every append spreads each tenant's batch over the
    shards so their fills differ by at most one, and each chip holds its
    share of the pending events.  A flush epoch is ONE jitted `shard_map`
    program, `_routed_epoch`, with the stack and the heaps donated: every
    shard dedups its share, routes each key to its owner with its weight
    (`all_to_all`), lands the conservative update and candidate re-score
    in place on the stack it owns, and re-selects its heaps.  Reads route
    each probe to its owner and gather the answers back; `topk` merges the
    shards' heaps (disjoint key sets).  Every enqueued event is visible to
    the next read, as on a plain plane.

    The exchange gives every owner as many slots per row as the row has
    entries, so it cannot overflow; the registry counts what it routes
    (`routed_keys`, events) and drops (`routed_dropped`, 0 by that sizing)
    and the load of the busiest shard over the mean
    (`routed_shard_keys_max`).  The device's counts are read back without
    blocking, at the next epoch or read.  Windows, tiers, packed storage,
    admission and snapshots are not supported on a routed plane.
    """

    tier = None

    def __init__(self, spec: SketchSpec, queue_capacity: int, mesh,
                 seed: int = 0, track_top: Optional[int] = None,
                 metrics: Optional[obs.MetricsRegistry] = None,
                 label: str = "p0"):
        if spec.packed:
            raise ValueError("a key-routed plane stores one cell per lane "
                             "(packed=False): its epoch updates the stack "
                             "in place")
        self.spec = spec
        self.mesh = mesh
        self.shards = int(mesh.size)
        axis = mesh.axis_names[0]
        by_shard = PartitionSpec(axis)
        self._sharded = NamedSharding(mesh, by_shard)
        self._replicated = NamedSharding(mesh, PartitionSpec())
        self.share = -(-int(queue_capacity) // self.shards)
        self.names: list[str] = []
        self.track_top = track_top
        self.rng = _RngLane(seed)
        self._lanes = np.asarray(sharded.shard_seeds(seed, self.shards),
                                 np.uint32)
        self.fill = np.zeros((self.shards, 0), np.int64)
        self._pending_stats: list = []
        self._init_telemetry(metrics, label)
        self._m_routed = self.metrics.counter("routed_keys", plane=label)
        self._m_dropped = self.metrics.counter("routed_dropped", plane=label)
        self._g_skew = self.metrics.gauge("routed_shard_keys_max",
                                          plane=label)

        def shard_map(body, in_specs, out_specs):
            return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs)

        def _routed_epoch(tables, heaps, queue, fill, rows, rng, *, cols):
            body = functools.partial(sharded.routed_epoch, spec=spec,
                                     cols=cols, axis_name=axis)
            return shard_map(body, (by_shard,) * 4 + (PartitionSpec(),
                                                      by_shard),
                             (by_shard,) * 3)(tables, heaps, queue, fill,
                                              rows, rng)

        def _routed_append(queue, keys, meta, *, aligned):
            def body(q, k, m):
                return ops.queue_append_local(q[0], k[0], m[0],
                                              aligned=aligned)[None]
            return shard_map(body, (by_shard,) * 3, by_shard)(queue, keys,
                                                              meta)

        def _routed_query(tables, rows, probes):
            body = functools.partial(sharded.routed_query_rows, spec=spec,
                                     axis_name=axis)
            return shard_map(body, (by_shard, PartitionSpec(),
                                    PartitionSpec()),
                             PartitionSpec())(tables, rows, probes)

        def _routed_grow(tables, heaps, queue):
            # one more tenant row in every shard's stack, heaps and ring
            def grow(a, fill_value=0):
                pad = jnp.full(a.shape[:1] + (1,) + a.shape[2:], fill_value,
                               a.dtype)
                return jnp.concatenate([a, pad], axis=1)
            return (grow(tables),
                    topk.TopK(keys=grow(heaps.keys),
                              estimates=grow(heaps.estimates, -jnp.inf),
                              filled=grow(heaps.filled)),
                    grow(queue))

        # built per plane, so a plane's programs are traced with the code
        # that is there when it is made
        self._epoch = jax.jit(_routed_epoch, static_argnames=("cols",),
                              donate_argnames=("tables", "heaps"))
        self._append = jax.jit(_routed_append, static_argnames=("aligned",),
                               donate_argnames=("queue",))
        self._query = jax.jit(_routed_query)
        self._grow = jax.jit(_routed_grow, out_shardings=self._sharded)
        n, k = self.shards, int(track_top or 0)
        self.tables, self.heaps, self.queue = jax.jit(
            lambda: (jnp.zeros((n, 0, spec.depth, spec.width),
                               spec.storage_dtype),
                     topk.TopK(keys=jnp.zeros((n, 0, k), jnp.uint32),
                               estimates=jnp.zeros((n, 0, k), jnp.float32),
                               filled=jnp.zeros((n, 0, k), bool)),
                     jnp.zeros((n, 0, ops.ring_width(self.share)),
                               jnp.uint32)),
            out_shardings=self._sharded)()

    @property
    def tracker(self) -> Optional[topk.TopK]:
        """The shards' (n, T, K) heaps, each of the keys its shard owns
        (None without tracking)."""
        return None if self.track_top is None else self.heaps

    @property
    def queue_capacity(self) -> int:
        return self.shards * self.share

    def add(self, name: str) -> int:
        self.names.append(name)
        self.tables, self.heaps, self.queue = self._grow(
            self.tables, self.heaps, self.queue)
        self.fill = np.concatenate(
            [self.fill, np.zeros((self.shards, 1), np.int64)], axis=1)
        self._g_tenants.set(len(self.names))
        return len(self.names) - 1

    def pending(self) -> int:
        return int(self.fill.sum())

    def queue_free(self, row: int) -> int:
        return self.queue_capacity - int(self.fill[:, row].sum())

    def queue_append_rows(self, rows, batches) -> None:
        """Append tenant microbatches in ONE launch: each batch is cut in
        contiguous pieces, one per shard, that leave the row's shard fills
        within one of each other (the shards fill evenly)."""
        with obs.span("queue_append", plane=self.label,
                      rows=len(rows)) as sp:
            if obs.recording():
                sp.set_metadata(events=sum(int(b.size) for b in batches))
            n = self.shards
            fill = self.fill[:, rows]                         # (n, R)
            total = fill.sum(axis=0) + [b.size for b in batches]
            take = total // n + (np.arange(n)[:, None] < total % n) - fill
            width = ops.CHUNK * -(-int(take.max()) // ops.CHUNK)
            keys = np.zeros((n, len(rows), width), np.uint32)
            for i, b in enumerate(batches):
                ends = np.cumsum(take[:, i])
                for s in range(n):
                    keys[s, i, :take[s, i]] = b[ends[s] - take[s, i]:ends[s]]
            meta = np.stack([np.broadcast_to(np.asarray(rows), fill.shape),
                             fill, take], axis=1).astype(np.int32)
            self.queue = self._append(
                self.queue, jax.device_put(keys, self._sharded),
                jax.device_put(meta, self._sharded),
                aligned=not fill.any())
            self.fill[:, rows] += take

    def _settle(self, wait: bool = True) -> None:
        """Fold the device's counts of the epochs landed so far into the
        registry (copied to the host without blocking at dispatch).  With
        `wait` a read here waits for an epoch still running; without it
        (at the next flush, so the chip is never left idle while the host
        waits) such an epoch's counts stay pending."""
        running = []
        for stats in self._pending_stats:
            if not wait and not stats.is_ready():
                running.append(stats)
                continue
            got = np.asarray(stats)                           # (n, 2)
            self._m_dropped.inc(int(got[:, 1].sum()))
            recv = got[:, 0]
            if recv.sum():
                self._g_skew.set(float(recv.max() / recv.mean()))
        self._pending_stats = running

    def flush(self, dense: bool = False, reason: str = "explicit") -> int:
        """Land every tenant's pending events: ONE `_routed_epoch`
        program over the mesh (see the class docstring).  `reason` labels
        the epoch's `cms.flush_epoch` span, which carries the mesh's
        `shards` and the events it routes (`routed_keys`)."""
        if dense:
            raise ValueError("a key-routed plane has no dense flush")
        pending = self.pending()
        if pending == 0:
            return 0
        self._settle(wait=False)
        active = np.flatnonzero(self.fill.sum(axis=0)).astype(np.int32)
        with obs.span("flush_epoch", cpu=True, plane=self.label,
                      rows=int(active.size), events=pending, reason=reason,
                      shards=self.shards, routed_keys=pending):
            with obs.span("route", plane=self.label, shards=self.shards):
                fill = self.fill[:, active]
                cols = min(self.queue.shape[2],
                           ops.CHUNK * -(-int(fill.max()) // ops.CHUNK))
                epoch = self.rng.next()[1]
                lanes = np.stack([self._lanes,
                                  np.full(self.shards, epoch, np.uint32)],
                                 axis=1)
                fill = jax.device_put(fill.astype(np.int32), self._sharded)
                lanes = jax.device_put(lanes, self._sharded)
                rows = jax.device_put(active, self._replicated)
            ops.note_dispatch("routed_epoch")
            self.tables, self.heaps, stats = self._epoch(
                self.tables, self.heaps, self.queue, fill, rows, lanes,
                cols=cols)
            stats.copy_to_host_async()
            self._pending_stats.append(stats)
            self.fill[:] = 0
        self._m_routed.inc(pending)
        self._note_flush(pending)
        return pending

    def query_rows(self, keys: jnp.ndarray) -> jnp.ndarray:
        """(T, N) estimates, tenant-ordered (keys (N,) for every tenant, or
        (T, N)): one routed read over the mesh."""
        keys = np.asarray(keys)
        t = len(self.names)
        if keys.ndim == 1:
            keys = np.broadcast_to(keys, (t, keys.size))
        return self._read(np.arange(t, dtype=np.int32), keys)

    def query_row(self, row: int, keys: jnp.ndarray) -> jnp.ndarray:
        """Estimates for one tenant: one routed read over the mesh."""
        return self._read(np.asarray([row], np.int32),
                          np.asarray(keys)[None])[0]

    def _read(self, rows: np.ndarray, probes: np.ndarray) -> jnp.ndarray:
        self._settle()
        ops.note_dispatch("routed_query")
        return self._query(self.tables,
                           jax.device_put(rows, self._replicated),
                           jax.device_put(probes.astype(np.uint32),
                                          self._replicated))

    def topk_row(self, row: int):
        """(keys, estimates, filled) of one tenant's heap, estimate-sorted:
        the shards' heaps merged.  Each shard's heap holds keys it alone
        owns, scored by the flush that last touched the row, so the merge
        is a plain top-k of the union and its estimates are the query
        answers."""
        self._settle()
        tk = self.heaps
        keys = np.asarray(tk.keys)[:, row].ravel()
        est = np.asarray(tk.estimates)[:, row].ravel()
        filled = np.asarray(tk.filled)[:, row].ravel()
        est = np.where(filled, est, -np.inf)
        top = np.argsort(-est, kind="stable")[:self.track_top]
        return keys[top], est[top], filled[top]

    def table_row(self, row: int) -> jnp.ndarray:
        raise ValueError("a key-routed tenant's table is split over the "
                         "mesh's shards: read it with query")


class CountService:
    """Registry of named sketches bucketed into fused-ingest planes."""

    def __init__(self, spec: Optional[SketchSpec] = None,
                 tenants: Sequence[str] = (), queue_capacity: int = 4096,
                 seed: int = 0, track_top: Optional[int] = None,
                 metrics: Optional[obs.MetricsRegistry] = None,
                 probe: Optional[obs.AccuracyProbe] = None,
                 tier: Optional[TierSpec] = None, mesh=None):
        if queue_capacity < 1:
            raise ValueError("queue_capacity must be positive")
        if track_top is not None and track_top < 1:
            raise ValueError("track_top must be positive")
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError("a key-routed service takes a 1-D mesh: "
                                 "its one axis shards the keys")
            if tier is not None:
                raise ValueError("tiered planes under a mesh are not "
                                 "supported")
        self.mesh = mesh
        self.default_spec = spec
        self.queue_capacity = int(queue_capacity)
        self.seed = int(seed)
        self.track_top = None if track_top is None else int(track_top)
        self.tier = tier
        self._planes: dict[SketchSpec, TenantPlane] = {}
        self._wplanes: dict[w.WindowSpec, WindowPlane] = {}
        self._where: dict[str, tuple[object, int]] = {}
        self._order: list[str] = []
        self._admission: dict[str, adm.AdmissionSpec] = {}
        # telemetry plane: one registry threaded through every plane; the
        # accuracy probe (opt-in) shadows enqueued keys with exact
        # host-side counts (see repro.obs)
        self.metrics = metrics if metrics is not None else obs.MetricsRegistry()
        self.probe = probe
        self._m_events = self.metrics.counter("events")
        self._m_flushes = self.metrics.counter("flushes")
        self._audit_depth = 0
        for name in tenants:
            self.add_tenant(name)

    # ---- registry ----

    @property
    def stats(self) -> dict:
        """Legacy {events, flushes} view, now served by the metrics
        registry (same numbers, one source of truth)."""
        return {"events": int(self._m_events.value),
                "flushes": int(self._m_flushes.value)}

    @stats.setter
    def stats(self, d: dict) -> None:
        self._m_events.value = int(d.get("events", 0))
        self._m_flushes.value = int(d.get("flushes", 0))

    @contextlib.contextmanager
    def _audited(self):
        """Scope one public call's kernel dispatches into the registry's
        per-op `dispatch{op=...}` counters (re-entrant calls — a query's
        internal flush — fold into the outermost scope, so nothing double
        counts)."""
        if self._audit_depth:
            yield
            return
        self._audit_depth += 1
        try:
            with ops.audit_scope() as tally:
                yield
        finally:
            self._audit_depth -= 1
            for op, n in tally.items():
                self.metrics.counter("dispatch", op=op).inc(n)

    @property
    def spec(self) -> Optional[SketchSpec]:
        """The default SketchSpec (tenants registered without an explicit
        spec use it) — kept for source compatibility with the single-spec
        service."""
        return self.default_spec

    @property
    def tenants(self) -> list[str]:
        return list(self._order)

    @property
    def planes(self) -> list[object]:
        """All planes, sketch planes first (inspection/benchmark hook)."""
        return list(self._planes.values()) + list(self._wplanes.values())

    def add_tenant(self, name: str, spec: Optional[SketchSpec] = None,
                   window: Optional[w.WindowSpec] = None,
                   admission: Optional[adm.AdmissionSpec] = None) -> int:
        """Register a tenant; returns its row in its plane's stacked table.

        spec: sketch geometry (defaults to the service-level spec).
        window: register a watermark-windowed tenant instead (ring-backed
        `WindowedSketch`; `enqueue(..., ts=...)` drives rotation).
        admission: arm the tracker-fed admission plane for this tenant —
        `svc.admit(name, ids)` maps raw ids to embedding rows, admitting
        exactly the tracked candidates whose estimates clear
        `admission.threshold`.  The tracker feeds the decisions, so they
        refresh with every flush epoch for free; requires the service to
        be constructed with `track_top=K`.  Growing a plane reshapes its
        stacked arrays, so that plane's next flush recompiles the fused
        kernel (amortized: tenant churn is rare next to ingest).
        """
        if name in self._where:
            raise ValueError(f"tenant {name!r} already registered")
        if admission is not None and self.track_top is None:
            raise ValueError("tracker-fed admission needs the heavy-hitter "
                             "plane: construct the service with track_top=K")
        if self.mesh is not None and (window is not None
                                      or admission is not None):
            raise ValueError("a key-routed service (mesh=...) serves plain "
                             "tenants: windows and admission are not "
                             "supported under a mesh")
        if window is not None:
            if spec is not None and spec != window.sketch:
                raise ValueError("pass the sketch spec inside WindowSpec "
                                 "for windowed tenants")
            plane = self._wplanes.get(window)
            if plane is None:
                plane = self._wplanes.setdefault(
                    window, WindowPlane(window, self.queue_capacity,
                                        self.seed,
                                        track_top=self.track_top,
                                        metrics=self.metrics,
                                        label=f"w{len(self._wplanes)}",
                                        tier=self.tier))
        else:
            spec = spec or self.default_spec
            if spec is None:
                raise ValueError("no spec: pass one (or a WindowSpec), or "
                                 "construct the service with a default")
            plane = self._planes.get(spec)
            if plane is None and self.mesh is not None:
                plane = self._planes.setdefault(
                    spec, RoutedPlane(spec, self.queue_capacity, self.mesh,
                                      self.seed, track_top=self.track_top,
                                      metrics=self.metrics,
                                      label=f"p{len(self._planes)}"))
            elif plane is None:
                plane = self._planes.setdefault(
                    spec, TenantPlane(spec, self.queue_capacity, self.seed,
                                      track_top=self.track_top,
                                      metrics=self.metrics,
                                      label=f"p{len(self._planes)}",
                                      tier=self.tier))
        row = plane.add(name)
        self._where[name] = (plane, row)
        self._order.append(name)
        if admission is not None:
            self._admission[name] = admission
        return row

    def admission_of(self, name: str) -> Optional[adm.AdmissionSpec]:
        """The tenant's admission policy (None when admission is off)."""
        self._lookup(name)
        return self._admission.get(name)

    def _lookup(self, name: str) -> tuple[object, int]:
        if name not in self._where:
            raise KeyError(f"unknown tenant {name!r}; have {self.tenants}")
        return self._where[name]

    def spec_of(self, name: str) -> SketchSpec:
        plane, _ = self._lookup(name)
        return plane.spec

    def epoch_of(self, name: str) -> Optional[int]:
        """Watermark interval index of a windowed tenant (None until the
        first timestamped enqueue)."""
        plane, row = self._lookup(name)
        if not isinstance(plane, WindowPlane):
            raise ValueError(f"tenant {name!r} is not windowed")
        return plane.epochs[row]

    def sketch_of(self, name: str) -> Sketch:
        """Flushed view of one tenant's sketch (shares the table slice).

        For windowed tenants this is the ACTIVE bucket's sketch."""
        plane, row = self._lookup(name)
        self._flush_plane(plane, "read")
        # host cursor/tier mirrors: the tenant's (active-bucket) table is
        # a static slice of its tier's array, no dynamic_index dispatch
        return Sketch(table=plane.table_row(row), spec=plane.spec)

    # ---- ingest ----

    def enqueue(self, name: str, keys, ts=None) -> None:
        """Buffer events for a tenant in its plane's device ring.

        Auto-flushes on queue pressure — scoped to the OWNING plane only
        (another plane's ring never pays this tenant's pressure epoch).
        `ts` (event time) is required semantics for windowed tenants: it
        advances the tenant's watermark (`window_advance_to`) before the
        events are buffered, flushing the plane first when the batch
        crosses into a new interval.
        """
        plane, row = self._lookup(name)
        keys = _as_keys(keys)
        with self._audited(), obs.span("enqueue", events=int(keys.size)):
            if ts is not None:
                if not isinstance(plane, WindowPlane):
                    raise ValueError(f"tenant {name!r} is not windowed; "
                                     "register with a WindowSpec to use ts")
                plane.advance(row, ts,
                              lambda: self._flush_plane(plane, "watermark"))
            if self.probe is not None:
                self.probe.observe(name, keys)
            self._m_events.inc(int(keys.size))
            cap = plane.queue_capacity
            while keys.size:
                free = plane.queue_free(row)
                if free == 0:
                    self._flush_plane(plane, "pressure")
                    free = cap
                take = min(free, keys.size)
                plane.queue_append_rows([row], [keys[:take]])
                keys = keys[take:]
            plane.note_append()

    def enqueue_many(self, events: dict, ts=None) -> None:
        """Buffer several tenants' microbatches with ONE scatter-append
        launch per plane (the batched regime `bench_ingest` measures).

        `ts` carries the same contract as `enqueue`: it advances every
        windowed tenant's watermark and raises for plain tenants (instead
        of silently dropping the event-time semantics).  Falls back to
        per-tenant `enqueue` for any batch that does not fit its tenant's
        free queue space in one piece — that overflow path's pressure
        flush is scoped to the owning plane, like `enqueue`'s.
        """
        by_plane: dict[int, tuple[object, list, list]] = {}
        overflow: list[tuple[str, np.ndarray]] = []

        def n_events() -> int:
            return sum(int(np.size(k)) for k in events.values())

        with self._audited(), obs.span("enqueue_many", cpu=True,
                                       tenants=len(events)) as sp:
            if ts is not None:
                # batch the watermark advances per plane: every boundary
                # crossing in this call rotates in ONE masked dispatch
                # (`WindowPlane.advance_many`) instead of one per tenant
                adv: dict[int, tuple[object, list]] = {}
                for name in events:
                    plane, row = self._lookup(name)
                    if not isinstance(plane, WindowPlane):
                        raise ValueError(f"tenant {name!r} is not windowed; "
                                         "register with a WindowSpec to use "
                                         "ts")
                    _, items = adv.setdefault(id(plane), (plane, []))
                    items.append((row, ts))
                for plane, items in adv.values():
                    plane.advance_many(
                        items,
                        lambda p=plane: self._flush_plane(p, "watermark"))
            with obs.span("stage") as st:
                for name, keys in events.items():
                    plane, row = self._lookup(name)
                    keys = _as_keys(keys)
                    if keys.size == 0:
                        continue
                    if keys.size > plane.queue_free(row):
                        overflow.append((name, keys))
                        continue
                    _, rows, batches = by_plane.setdefault(id(plane),
                                                           (plane, [], []))
                    rows.append(row)
                    batches.append(keys)
                    if self.probe is not None:
                        self.probe.observe(name, keys)
                    self._m_events.inc(int(keys.size))
                if obs.recording():
                    st.set_metadata(events=n_events())
            for plane, rows, batches in by_plane.values():
                plane.queue_append_rows(rows, batches)
                plane.note_append()
            for name, keys in overflow:
                self.enqueue(name, keys)
            if obs.recording():
                sp.set_metadata(events=n_events(), overflow=len(overflow))

    def flush(self) -> int:
        """Land every DIRTY plane's pending events (one fused launch per
        dirty plane; clean planes are skipped outright — no dispatch, no
        PRNG draw).

        Returns the number of events ingested; the per-plane launch shape
        is CHUNK-quantized via the fill trim (see `_DeviceRing.live_slice`).
        Each plane draws from its own PRNG lane (seeded with the service
        seed), so per-plane state evolves exactly as in a dedicated
        single-spec service.
        """
        return self._flush_dirty("explicit")

    def _flush_dirty(self, reason: str) -> int:
        """Land every dirty plane (`flush`, and `query_all`'s read-your-
        writes flush); `reason` labels the epochs' spans."""
        dirty = self.dirty_planes
        with self._audited(), obs.span("flush", planes=len(dirty)):
            total = sum(plane.flush(reason=reason) for plane in dirty)
        if total:
            self._m_flushes.inc()
        return total

    def _flush_plane(self, plane, reason: str) -> int:
        """Scoped flush epoch: land ONE plane's pending events.

        The serve-path epoch scheduler — read ops (`query`/`topk`/`admit`/
        `sketch_of`) and `enqueue`'s queue-pressure fallback flush only
        the plane they touch, so a read never pays another plane's epoch
        and a clean plane costs zero dispatches (and consumes no PRNG
        draw, which is what keeps the scoped service bit-identical to an
        always-full-flush one: a skipped clean flush is indistinguishable
        from a landed empty one).  Read-your-writes still holds per
        tenant because every tenant's pending events live in its own
        plane's ring.  `reason` (pressure / read / watermark) labels the
        epoch's span.
        """
        with self._audited():
            total = plane.flush(reason=reason) if plane.pending() else 0
        if total:
            self._m_flushes.inc()
        return total

    @property
    def dirty_planes(self) -> list:
        """Planes with buffered events awaiting a flush epoch (the fill
        mirror is the dirty signal — host-side, no device read-back)."""
        return [p for p in self.planes if p.pending()]

    def tier_occupancy(self) -> dict[str, dict[str, int]]:
        """Per-plane tier occupancy {plane_label: {"hot": n, "cold": m}} —
        the serving-surface view of the tier gauges (empty when the
        service was constructed without a TierSpec)."""
        return {p.label: {"hot": p.tier.hot_count,
                          "cold": p.tier.cold_count}
                for p in self.planes if p.tier is not None}

    # ---- serving ----

    def query(self, name: str, keys, **window_kw) -> jnp.ndarray:
        """Estimated counts for one tenant (flushes the tenant's OWN plane
        first — read-your-writes without paying other planes' epochs; a
        clean plane costs zero update dispatches).

        Plain tenants: one `ops.query_row` dispatch over the plane's
        stack at the tenant's row (on TPU one jitted program; off-TPU
        within VMEM the Pallas query).  Windowed tenants: the fused window
        reduction over the ring (`window_kw` forwards n_buckets / mode /
        gamma / engine)."""
        plane, row = self._lookup(name)
        if window_kw and not isinstance(plane, WindowPlane):
            raise ValueError(f"tenant {name!r} is not windowed; window "
                             f"args {sorted(window_kw)} do not apply")
        with self._audited(), obs.span("query", tenant=name) as sp:
            self._flush_plane(plane, "read")
            with obs.span("query.upload") as up:
                probes = jnp.asarray(_as_keys(keys))
                if obs.recording():
                    up.set_metadata(probes=int(probes.size))
            with obs.span("query.dispatch"):
                out = plane.query_row(row, probes, **window_kw)
            if obs.recording():
                sp.set_metadata(probes=int(probes.size))
            return out

    def query_all(self, keys) -> dict[str, jnp.ndarray]:
        """Estimated counts for EVERY tenant: one fused launch per plane —
        windowed planes included (a plane with W windowed tenants answers
        in ONE row-stacked `window_query_stacked` dispatch, not W
        per-ring launches; see `WindowPlane.query_rows`).

        keys: (N,) probes shared by all tenants, or (T, N) per-tenant
        probes (row order = registry order, `self.tenants`).  Returns
        {tenant: float32 (N,) estimates}, bit-consistent with calling
        `query` per tenant.  Flushes every dirty plane first (this read
        touches them all): read-your-writes.
        """
        with self._audited(), \
                obs.span("query_all", tenants=len(self._order)):
            self._flush_dirty("read")
            keys = np.asarray(keys)
            per_tenant = keys.ndim == 2
            if per_tenant and keys.shape[0] != len(self._order):
                raise ValueError(f"per-tenant probes need {len(self._order)} "
                                 f"rows, got {keys.shape[0]}")
            keys = _as_keys(keys).reshape(keys.shape)
            out: dict[str, jnp.ndarray] = {}
            row_of = {name: i for i, name in enumerate(self._order)}
            for plane in self.planes:
                if per_tenant:
                    probes = jnp.asarray(
                        np.stack([keys[row_of[n]] for n in plane.names]))
                else:
                    probes = jnp.asarray(keys)
                est = plane.query_rows(probes)
                for i, n in enumerate(plane.names):
                    out[n] = est[i]
            return out

    def topk(self, name: str, k: Optional[int] = None, **window_kw):
        """Current top-k heavy hitters of one tenant: (keys, estimates).

        Served from the tenant's device-resident tracker (refreshed by
        every flush with the just-flushed keys; flushes the tenant's own
        plane first here, so the answer is read-your-writes).  Returns up
        to `k` (default: the
        tracker width `track_top`) keys sorted by descending estimate —
        fewer if the tenant has seen fewer distinct keys — and the
        estimates agree exactly with `query`/`query_all` on those keys.
        Windowed tenants re-score their candidates against the current
        ring first (rotation/expiry/decay reorder the heap) and forward
        `window_kw` (n_buckets / mode / gamma) to that scoring query.
        """
        plane, row = self._lookup(name)
        if plane.tracker is None:
            raise ValueError("heavy-hitter tracking is off: construct the "
                             "service with track_top=K")
        k = self.track_top if k is None else int(k)
        if not 1 <= k <= self.track_top:
            raise ValueError(f"k must be in [1, {self.track_top}], got {k}")
        if window_kw and not isinstance(plane, WindowPlane):
            raise ValueError(f"tenant {name!r} is not windowed; "
                             f"window args {sorted(window_kw)} do not apply")
        with self._audited(), obs.span("topk", tenant=name):
            self._flush_plane(plane, "read")
            keys, est, filled = plane.topk_row(row, **window_kw)
        sel = filled[:k]
        return keys[:k][sel], est[:k][sel]

    def admit(self, name: str, ids, **window_kw):
        """Map raw ids -> embedding rows under the tenant's tracker-fed
        admission policy: (rows, admitted_mask), aligned with ids.

        Flushes the tenant's own plane first, so the decisions reflect
        the current flush epoch's tracker refresh — hot keys acquire
        private rows automatically the
        moment the heavy-hitter plane sees them clear the threshold.  For
        plain tenants the decision needs no sketch launch
        (`admission.admit_tracked` is O(K) candidate compares per id
        against the standing heap).  Windowed tenants first re-score
        their candidates against the current ring (one stacked
        window-query launch, as in `topk`) and forward `window_kw`
        (n_buckets / mode / gamma), so admission can be time-scoped: an
        id whose traffic expired out of the window loses its private row
        on the next decision.
        """
        plane, row = self._lookup(name)
        aspec = self._admission.get(name)
        if aspec is None:
            raise ValueError(f"tenant {name!r} has no admission policy: "
                             "register with add_tenant(admission="
                             "AdmissionSpec(...))")
        if window_kw and not isinstance(plane, WindowPlane):
            raise ValueError(f"tenant {name!r} is not windowed; "
                             f"window args {sorted(window_kw)} do not apply")
        with self._audited(), obs.span("admit", tenant=name):
            self._flush_plane(plane, "read")
            if isinstance(plane, WindowPlane):
                # re-score the heap against the current ring (rotation/
                # expiry/decay) and persist it — then decide from the
                # fresh tracker
                plane.topk_row(row, **window_kw)
            # tracker leaves sliced on device (no host round trip); ids
            # validate host-side (np) and upload ONCE inside admit_tracked
            tk = plane.tracker
            return adm.admit_tracked(tk.keys[row], tk.estimates[row],
                                     tk.filled[row], _as_keys(ids), aspec)

    # ---- persistence ----

    @staticmethod
    def _plane_meta(p, base: dict) -> dict:
        # v8: tiered planes snapshot their membership + policy signals in
        # the manifest (the cold store itself is a leaf) so restore
        # re-tiers deterministically
        if p.tier is not None:
            base["tier"] = p.tier.meta()
        return base

    def _meta(self) -> dict:
        meta = {
            # v8: tier membership (manifest) + cold stores (leaf tree)
            # for tiered services; untiered manifests are shape-identical
            # to v7.  v7 made the window leaf the plane's native
            # (T, B, d, w) array + host cursor/epoch mirrors — leaf
            # SHAPES unchanged from v6 (which stacked per-tenant rings
            # into the same layout at snapshot time), so v6-and-earlier
            # checkpoints restore into the native plane with no
            # conversion.  v6 added the packed-storage flag (pre-v6
            # manifests restore as packed=False).
            "version": 8,
            "queue_capacity": self.queue_capacity,
            "seed": self.seed,
            "track_top": self.track_top,
            "tenant_order": self.tenants,
            "stats": dict(self.stats),
            # v5: the whole metrics-registry snapshot (counters, gauges
            # with high-water marks, histograms) — restore reloads it so
            # telemetry survives a restart; "stats" stays alongside for
            # pre-v5 readers
            "metrics": self.metrics.snapshot(),
            # v4: per-tenant tracker-fed admission policies (decisions
            # themselves live in the tracker leaves, refreshed per epoch)
            "admission": {name: dataclasses.asdict(spec)
                          for name, spec in self._admission.items()},
            "planes": [self._plane_meta(p, {"spec": _spec_meta(p.spec),
                                            "tenants": list(p.names),
                                            "rng_draws": p.rng.draws})
                       for p in self._planes.values()],
            "windows": [self._plane_meta(p, {"sketch": _spec_meta(p.spec),
                                             "buckets": p.wspec.buckets,
                                             "interval": p.wspec.interval,
                                             "tenants": list(p.names),
                                             "rng_draws": p.rng.draws})
                        for p in self._wplanes.values()],
        }
        if self.tier is not None:
            meta["tier"] = {"max_hot_tenants": self.tier.max_hot_tenants,
                            "policy": self.tier.policy}
        if self.default_spec is not None:
            meta["spec"] = _spec_meta(self.default_spec)  # v1 reader compat
            meta["tenants"] = self.tenants
        return meta

    @staticmethod
    def _tracker_leaves(plane) -> dict:
        return {"keys": plane.tracker.keys,
                "estimates": plane.tracker.estimates,
                "filled": plane.tracker.filled}

    def _tree(self, with_topk: Optional[bool] = None) -> dict:
        """Checkpoint leaf tree.  with_topk: include the (T, K) tracker
        leaves (defaults to whether tracking is on; restore passes the
        manifest's answer so v2 checkpoints map onto a tracker-less
        target)."""
        if with_topk is None:
            with_topk = self.track_top is not None
        planes = []
        for p in self._planes.values():
            # v8 tiered leaves: "tables" is the (H, d, w) hot stack,
            # "cold_tables" the (T, d, w) cold store, and "queue"/"fill"
            # snapshot the TENANT-indexed host mirror (authoritative for
            # ring contents; the slot-indexed device ring is its gather,
            # rebuilt on restore)
            if p.tier is not None:
                leaf = {"tables": p.tables,
                        "cold_tables": jnp.asarray(p.tier.cold),
                        "queue": jnp.asarray(p.tier.hqueue),
                        "fill": jnp.asarray(p.tier.hfill)}
            else:
                leaf = {"tables": p.tables,
                        "queue": p.ring.queue,
                        "fill": jnp.asarray(p.ring.fill)}
            if with_topk:
                leaf["topk"] = self._tracker_leaves(p)
            planes.append(leaf)
        windows = []
        for p in self._wplanes.values():
            # v7: the native leaf goes straight into the checkpoint —
            # no per-tenant restack; cursor/epoch come from the host
            # mirrors (same (T,) shapes v6 produced by stacking)
            leaf = {"cursor": jnp.asarray(p.cursors, jnp.int32),
                    "epoch": jnp.asarray([
                        -1 if e is None else int(e)
                        for e in p.epochs], jnp.int32)}
            if p.tier is not None:
                leaf.update({"tables": p.tables,
                             "cold_tables": jnp.asarray(p.tier.cold),
                             "queue": jnp.asarray(p.tier.hqueue),
                             "fill": jnp.asarray(p.tier.hfill)})
            else:
                leaf.update({"tables": p.tables,
                             "queue": p.ring.queue,
                             "fill": jnp.asarray(p.ring.fill)})
            if with_topk:
                leaf["topk"] = self._tracker_leaves(p)
            windows.append(leaf)
        return {"planes": planes, "windows": windows}

    def snapshot(self, root: str, step: int) -> str:
        """Atomic checkpoint of every plane (pending ring events included)."""
        if self.mesh is not None:
            raise ValueError("snapshot and restore of a key-routed service "
                             "are not supported")
        return checkpoint.save(root, step, self._tree(),
                               metadata=self._meta())

    @classmethod
    def restore(cls, root: str, step: Optional[int] = None,
                track_top: Optional[int] = None,
                packed: Optional[bool] = None) -> "CountService":
        """Rebuild a service (registry + planes + rings) from a snapshot.

        Accepts the v7 manifest (native (T, B, d, w) window leaf — same
        leaf shapes v6 wrote, so v6-and-earlier window planes restore
        into the native layout with no conversion), v6 (packed-storage
        flag), v5 (metrics
        snapshot), v4 (admission plane), v3 (multi-plane + tracker state),
        the v2 multi-plane layout, and the original v1 single-plane layout
        (whose host queue is replayed into the device ring).  Pre-v5
        checkpoints restore with COLD metrics (only the legacy
        events/flushes stats carry over); pre-v6 specs restore as
        packed=False.  `packed=True/False` converts every plane's storage
        layout on load (repack-on-load): tables restore in their saved
        layout, then unpack/repack cell-exactly, so an unpacked v5
        snapshot comes back as a packed service (or vice versa) with
        bit-identical estimates.  Checkpoints written with tracking on
        restore their trackers; `track_top` re-arms tracking:

          * pre-v3 / tracker-less snapshot — COLD (T, track_top) heaps
            that refill from post-restore traffic (the tables carry no
            candidate list to rebuild from);
          * snapshot taken at a DIFFERENT track_top — the heaps are
            resized in place (`topk.resize_stacked`): shrinking keeps
            each row's best `track_top` candidates, growing preserves
            the standing candidates and cold-masks the new slots.
        """
        meta, step = checkpoint.load_metadata(root, step)
        if meta.get("version", 1) < 2:
            svc = cls._restore_v1(root, step, meta, track_top)
            if packed is not None:
                svc._convert_packing(packed)
            return svc
        default = (_spec_from_meta(meta["spec"]) if "spec" in meta else None)
        saved_k = meta.get("track_top")
        # v8: reconstruct the TierSpec first so planes grow slot-indexed
        # device stacks; the snapshotted membership is re-applied below
        tier = (TierSpec(**meta["tier"]) if "tier" in meta else None)
        svc = cls(default, queue_capacity=meta["queue_capacity"],
                  seed=meta.get("seed", 0),
                  track_top=saved_k if saved_k is not None else track_top,
                  tier=tier)
        admission_of = {name: adm.AdmissionSpec(**spec)
                        for name, spec in meta.get("admission", {}).items()}
        plane_of: dict[str, dict] = {}
        for pm in meta["planes"]:
            for name in pm["tenants"]:
                plane_of[name] = {"spec": _spec_from_meta(pm["spec"])}
        for wm in meta["windows"]:
            wspec = w.WindowSpec(sketch=_spec_from_meta(wm["sketch"]),
                                 buckets=wm["buckets"],
                                 interval=wm["interval"])
            for name in wm["tenants"]:
                plane_of[name] = {"window": wspec}
        for name in meta["tenant_order"]:
            svc.add_tenant(name, admission=admission_of.get(name),
                           **plane_of[name])
        has_topk = saved_k is not None
        tree, _ = checkpoint.restore(root, svc._tree(with_topk=has_topk),
                                     step=step)
        for p, pm, leaves in zip(svc._planes.values(), meta["planes"],
                                 tree["planes"]):
            cls._restore_plane_leaves(p, pm, leaves)
            if has_topk:
                p.tracker = topk.TopK(**leaves["topk"])
        for p, wm, leaves in zip(svc._wplanes.values(), meta["windows"],
                                 tree["windows"]):
            # v7 saves the native leaf; v6-and-earlier saved identical
            # shapes (stacked per-tenant rings), so both land here as-is
            cls._restore_plane_leaves(p, wm, leaves)
            p.cursors = np.asarray(leaves["cursor"], np.int32)
            for i in range(len(p.names)):
                epoch = int(leaves["epoch"][i])
                p.epochs[i] = None if epoch < 0 else epoch
            if has_topk:
                p.tracker = topk.TopK(**leaves["topk"])
        svc.stats = dict(meta.get("stats", svc.stats))
        # v5 carries the full registry snapshot; pre-v5 checkpoints restore
        # with cold metrics (only the stats counters above carry over)
        if "metrics" in meta:
            svc.metrics.load(meta["metrics"])
        if (track_top is not None and saved_k is not None
                and track_top != saved_k):
            svc._resize_trackers(track_top)
        if packed is not None:
            svc._convert_packing(packed)
        return svc

    @staticmethod
    def _restore_plane_leaves(p, pm: dict, leaves: dict) -> None:
        """Apply one plane's checkpoint leaves + rng lane.  Tiered planes
        re-apply the snapshotted membership first (deterministic
        re-tiering), land the host mirrors, and rebuild the slot-indexed
        device ring as the mirror's gather."""
        p.rng.draws = int(pm.get("rng_draws", 0))
        if p.tier is None:
            p.tables = leaves["tables"]
            p.ring.queue = leaves["queue"]
            p.ring.fill = np.asarray(leaves["fill"], np.int64)
            return
        t = p.tier
        tm = pm["tier"]
        t.load_membership(tm["slot_tenant"], tm["last_active"],
                          tm["hits"], tm["epoch"])
        with jax.transfer_guard_device_to_host("allow"):
            # np.array (not asarray): device leaves read back as read-only
            # views, and the host tier mutates these in place
            t.cold = np.array(leaves["cold_tables"]).astype(
                t.dtype, copy=False)
            t.hqueue = np.array(leaves["queue"], np.uint32)
            t.hfill = np.array(leaves["fill"], np.int64)
        p.tables = leaves["tables"]
        st = t.slot_tenant
        p.ring.queue = jnp.asarray(t.hqueue[st])
        p.ring.fill = t.hfill[st].copy()
        p._tier_gauges()

    def _convert_packing(self, packed: bool) -> None:
        """Switch every plane's table storage layout in place
        (repack-on-load): unpack each table to its cell states under the
        current spec, re-store them under the converted spec.  Cell
        VALUES are preserved exactly, so estimates are bit-identical
        across the conversion; packing requires each spec's width to
        divide by cells_per_lane (`SketchSpec` validates).  Registry
        keys, the default spec, and the windowed sketches' embedded
        specs all follow the new layout."""
        if self.default_spec is not None:
            self.default_spec = dataclasses.replace(self.default_spec,
                                                    packed=packed)
        planes: dict[SketchSpec, TenantPlane] = {}
        for spec, p in self._planes.items():
            new = dataclasses.replace(spec, packed=packed)
            if new != spec:
                p.tables = sk.storage_table(sk.logical_table(p.tables, spec),
                                            new)
                p.spec = new
                if p.tier is not None:
                    self._repack_cold(p.tier, spec, new,
                                      (new.depth, new.storage_width))
            planes[new] = p
        self._planes = planes
        wplanes: dict[w.WindowSpec, WindowPlane] = {}
        for wspec, p in self._wplanes.items():
            new_sk = dataclasses.replace(wspec.sketch, packed=packed)
            new_w = (wspec if new_sk == wspec.sketch
                     else dataclasses.replace(wspec, sketch=new_sk))
            if new_w != wspec:
                # one whole-leaf repack: logical/storage_table act on the
                # trailing (d, w) axes, so the (T, B, d, w) leaf converts
                # in a single fused computation
                p.tables = sk.storage_table(
                    sk.logical_table(p.tables, wspec.sketch), new_sk)
                p.wspec = new_w
                if p.tier is not None:
                    self._repack_cold(p.tier, wspec.sketch, new_sk,
                                      (new_w.buckets, new_sk.depth,
                                       new_sk.storage_width))
            wplanes[new_w] = p
        self._wplanes = wplanes

    @staticmethod
    def _repack_cold(t, old_spec: SketchSpec, new_spec: SketchSpec,
                     row_shape: tuple) -> None:
        """Repack a plane's cold store alongside its hot stack (same
        cell-exact logical/storage round trip, one fused computation
        through the device)."""
        with jax.transfer_guard_device_to_host("allow"):
            # np.array: the read-back is read-only, the cold store mutates
            t.cold = np.array(sk.storage_table(
                sk.logical_table(jnp.asarray(t.cold), old_spec), new_spec))
        t.row_shape = tuple(row_shape)
        t.dtype = np.dtype(new_spec.storage_dtype)

    def _resize_trackers(self, k: int) -> None:
        """Re-arm every plane's heap stack at width k (restore with a
        different track_top than was snapshotted)."""
        self.track_top = int(k)
        for plane in self.planes:
            plane.track_top = self.track_top
            if plane.tracker is not None:
                plane.tracker = topk.resize_stacked(plane.tracker,
                                                    self.track_top)

    @classmethod
    def _restore_v1(cls, root: str, step: int, meta: dict,
                    track_top: Optional[int] = None) -> "CountService":
        """Restore a pre-plane (single-spec, host-queue) checkpoint: load
        the stacked tables directly and replay the persisted host queue
        into the device ring.  Trackers (if re-armed) start cold."""
        spec = _spec_from_meta(meta["spec"])
        svc = cls(spec, tenants=meta["tenants"],
                  queue_capacity=meta["queue_capacity"],
                  track_top=track_top)
        plane = next(iter(svc._planes.values()))
        target = {"tables": plane.tables,
                  "queue": jax.ShapeDtypeStruct(
                      (len(meta["tenants"]), meta["queue_capacity"]),
                      jnp.uint32),
                  "fill": jax.ShapeDtypeStruct((len(meta["tenants"]),),
                                               jnp.int64)}
        tree, _ = checkpoint.restore(root, target, step=step)
        plane.tables = tree["tables"]
        queue = np.asarray(tree["queue"], np.uint32)
        fill = np.asarray(tree["fill"], np.int64)
        for t in range(queue.shape[0]):
            if fill[t]:
                plane.ring.append([t], [queue[t, :fill[t]]])
        # the v1 split-chain rng leaf has no counter-lane equivalent; the
        # restored plane restarts its lane (forward determinism only)
        svc.stats = dict(meta.get("stats", svc.stats))
        return svc
