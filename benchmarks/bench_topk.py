"""Heavy-hitter plane benchmarks: active-row flush + single-launch epoch.

Three questions about the flush pipeline refactor:

  1. ACTIVE-ROW FLUSH — under hot-tenant skew (one tenant of T bursting,
     the regime bench_ingest's queue-plane rows also probe), the dense
     flush sweeps every tenant's VMEM-resident table through the fused
     update grid (T, chunk) while the active-row flush grids over
     (R, chunk) = (1, chunk) via the SMEM row map.  Both paths are timed
     interleaved on identically-fed services and the final tables are
     asserted bit-identical — the speedup is pure grid shrinkage, not a
     semantics change.  The >= 2x acceptance bar at T >= 16 lives here.
  2. TRACKER REFRESH — what does track_top=K add to a flush?  The tracker
     path re-queries the just-flushed keys + standing candidates and
     re-selects the (T, K) heaps on device; its cost is reported as the
     tracked/untracked cycle ratio plus the absolute refresh_stacked
     launch time.
  3. SINGLE-LAUNCH EPOCH — the fused update+score flush
     (ops.update_score_rows, ONE dispatch) vs the PR 4 two-launch
     pipeline (active-row update launch, then a fused query refresh
     launch).  Tables AND heaps are asserted bit-identical; the results
     JSON additionally records `launch_audit` — per-op dispatch counts
     captured under `ops.audit_scope()` during one flush epoch — so the
     single-launch claim is machine-checked by check_regression.py, not
     prose.

    PYTHONPATH=src python -m benchmarks.bench_topk [--quick]
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.bench_ingest import _paired_cycles
from repro.core import CMLS16, SketchSpec
from repro.core import topk
from repro.core.counters import pack_table
from repro.kernels import ops
from repro.stream import CountService, WindowSpec

METHODOLOGY = {
    "flush_hot1": "capacity 2 kernel-CHUNKs; each cycle enqueues ONE hot "
                  "tenant of T a capacity-filling microbatch then flushes "
                  "with the REAL fused update landing.  active = the "
                  "service's active-row path (ops.update_rows, grid "
                  "(1, chunk), SMEM row map); dense = plane.flush("
                  "dense=True), the whole-plane (T, chunk) grid.  timer = "
                  "2 warmup cycles then 7 interleaved active/dense pairs; "
                  "speedup = median per-pair ratio; the two services' "
                  "tables are asserted bit-identical afterwards (shared "
                  "uniforms grid, skipped rows were weight-0 no-ops).",
    "tracker": "same hot1 cycle with track_top=64 vs untracked: the "
               "overhead ratio prices the per-flush heap refresh "
               "(candidate re-query + top-K re-select on device).  "
               "refresh_T* rows time one refresh_stacked launch directly "
               "(K=64 standing candidates + one CHUNK batch per row, "
               "scored through the fused multi-tenant query).",
    "epoch": "same hot1 cycle on TRACKED services (track_top=64): fused = "
             "the default flush (ops.update_score_rows lands the update "
             "and re-scores the candidate union in ONE dispatch), pair = "
             "the PR 4 pipeline (ops.update_rows launch, then the "
             "two-launch _refresh_topk query).  Interleaved pairs, median "
             "ratio; tables AND tracker heaps asserted bit-identical "
             "afterwards.",
    "launch_audit": "per-op dispatch counts (ops.audit_scope) captured "
                    "over ONE flush epoch per scenario: the tracked "
                    "tenant-plane flush must be exactly one "
                    "update_score_rows dispatch — for PACKED storage too "
                    "(tracked_flush_epoch_packed): packing changes the "
                    "cell layout inside the launch, never the launch "
                    "count — and the windowed plane's flush epoch exactly "
                    "one row-mapped update (update_rows on the native "
                    "(T*B, d, w) reshape) plus one window_query_stacked "
                    "tracker refresh regardless of flushed-tenant count.  "
                    "window_rotation_T3 audits a watermark advance of ALL "
                    "three tenants with empty queues: one masked "
                    "window_advance_rows dispatch, not one rotation per "
                    "tenant.  check_regression.py fails the suite if the "
                    "audit regresses.",
    "window_epoch_native": "windowed flush on the native (T, B, d, w) "
                           "leaf vs the legacy restack pipeline, every "
                           "tenant pending (so both paths process the "
                           "same R=T rows and the delta is purely data "
                           "movement).  native = plane.flush(): the leaf "
                           "reshapes FREE to (T*B, d, w) and the "
                           "row-mapped kernel lands each batch at flat "
                           "row tenant*B+cursor, leaf donated and in/out "
                           "aliased — zero bytes restacked.  restack = "
                           "plane.flush(dense=True): gathers the active "
                           "buckets into a fresh (T, d, w) stack, runs "
                           "the dense launch, scatters each bucket back "
                           "— 2*T*d*w_storage*itemsize bytes copied per "
                           "epoch (gather + scatter-back), reported as "
                           "restack_bytes in the derived column.  "
                           "Interleaved pairs, median ratio; leafs AND "
                           "tracker heaps asserted bit-identical "
                           "afterwards.",
    "packed_format": "topk_packed rows: the tracked single-launch epoch "
                     "on packed vs unpacked storage (same seeds, "
                     "interleaved pairs, median ratio); afterwards the "
                     "packed tables are asserted lane-identical to "
                     "pack_table(unpacked) and the heaps bit-identical.  "
                     "topk_structure rows are not timings: they record "
                     "how many tenant tables fit one VMEM block "
                     "(VMEM_TABLE_LIMIT / table_bytes_streamed, using "
                     "the 32-bit-lane streaming model from cell_format) "
                     "and the bytes one T-tenant dense epoch sweeps — "
                     "the capacity headroom packing buys even where "
                     "interpret mode hides the bandwidth win.",
}


def _hot_batch(cap, seed):
    return (np.random.default_rng(seed).zipf(1.3, cap) % 50_000
            ).astype(np.uint32)


def _flush_point(spec, t, cap):
    names = [f"tn{i}" for i in range(t)]
    svc_a = CountService(spec, tenants=names, queue_capacity=cap, seed=0)
    svc_d = CountService(spec, tenants=names, queue_capacity=cap, seed=0)
    batch = _hot_batch(cap, seed=t)

    def active_cycle():
        svc_a.enqueue_many({names[0]: batch})
        svc_a.planes[0].flush()
        jax.block_until_ready(svc_a.planes[0].tables)

    def dense_cycle():
        svc_d.enqueue_many({names[0]: batch})
        svc_d.planes[0].flush(dense=True)
        jax.block_until_ready(svc_d.planes[0].tables)

    ta, td, ratio = _paired_cycles(active_cycle, dense_cycle, warmup=2,
                                   reps=7)
    assert (np.asarray(svc_a.planes[0].tables)
            == np.asarray(svc_d.planes[0].tables)).all(), \
        "active-row and dense flushes landed different tables"
    return ta, td, ratio


def _tracker_point(spec, t, cap, k=64):
    names = [f"tn{i}" for i in range(t)]
    plain = CountService(spec, tenants=names, queue_capacity=cap, seed=0)
    tracked = CountService(spec, tenants=names, queue_capacity=cap, seed=0,
                           track_top=k)
    batch = _hot_batch(cap, seed=t + 101)

    def plain_cycle():
        plain.enqueue_many({names[0]: batch})
        plain.planes[0].flush()
        jax.block_until_ready(plain.planes[0].tables)

    def tracked_cycle():
        tracked.enqueue_many({names[0]: batch})
        tracked.planes[0].flush()
        jax.block_until_ready((tracked.planes[0].tables,
                               tracked.planes[0].tracker.keys))

    tp, tt, _ = _paired_cycles(plain_cycle, tracked_cycle, warmup=2, reps=7)
    # direct refresh launch: K standing candidates + one CHUNK batch per row
    tracker = topk.init_stacked(t, k)
    tables = plain.planes[0].tables
    keys = jnp.asarray(np.stack([_hot_batch(ops.CHUNK, seed=i)
                                 for i in range(t)]))

    def refresh():
        return topk.refresh_stacked(
            tracker, keys, None,
            lambda ck: ops.query_many(tables, spec, ck))

    t_ref, _ = common.timer(refresh, warmup=1, iters=3)
    return tp, tt, t_ref


def _pair_flush(plane):
    """The PR 4 two-launch pipeline, reconstructed: active-row update
    launch, then the separate fused-query tracker refresh (the path the
    single-launch epoch replaced; `_refresh_topk` is retained for the
    dense baseline, which is exactly the second launch)."""
    pending = plane.pending()
    if pending == 0:
        return 0
    rng = plane.rng.next()
    active = np.flatnonzero(plane.ring.fill).astype(np.int32)
    keys, weights = plane.ring.live_slice(active)
    plane.tables = ops.update_rows(plane.tables, plane.spec, keys, rng,
                                   active, weights=weights)
    plane._refresh_topk(active, keys, weights)
    plane.ring.reset()
    return pending


def _epoch_point(spec, t, cap, k=64):
    """Fused single-launch epoch vs the two-launch pipeline, hot1 regime."""
    names = [f"tn{i}" for i in range(t)]
    svc_f = CountService(spec, tenants=names, queue_capacity=cap, seed=0,
                         track_top=k)
    svc_p = CountService(spec, tenants=names, queue_capacity=cap, seed=0,
                         track_top=k)
    batch = _hot_batch(cap, seed=t + 77)

    def fused_cycle():
        svc_f.enqueue_many({names[0]: batch})
        svc_f.planes[0].flush()
        jax.block_until_ready((svc_f.planes[0].tables,
                               svc_f.planes[0].tracker.keys))

    def pair_cycle():
        svc_p.enqueue_many({names[0]: batch})
        _pair_flush(svc_p.planes[0])
        jax.block_until_ready((svc_p.planes[0].tables,
                               svc_p.planes[0].tracker.keys))

    tf, tp, ratio = _paired_cycles(fused_cycle, pair_cycle, warmup=2, reps=7)
    pf, pp = svc_f.planes[0], svc_p.planes[0]
    assert (np.asarray(pf.tables) == np.asarray(pp.tables)).all(), \
        "fused epoch and two-launch pipeline landed different tables"
    assert (np.asarray(pf.tracker.keys) == np.asarray(pp.tracker.keys)).all() \
        and (np.asarray(pf.tracker.estimates)
             == np.asarray(pp.tracker.estimates)).all(), \
        "fused epoch and two-launch pipeline landed different heaps"
    return tf, tp, ratio


def _packed_epoch_point(spec_u, spec_p, t, cap, k=64):
    """Tracked single-launch epoch, packed vs unpacked storage, hot1."""
    names = [f"tn{i}" for i in range(t)]
    unp = CountService(spec_u, tenants=names, queue_capacity=cap, seed=0,
                       track_top=k)
    pk = CountService(spec_p, tenants=names, queue_capacity=cap, seed=0,
                      track_top=k)
    batch = _hot_batch(cap, seed=t + 55)

    def packed_cycle():
        pk.enqueue_many({names[0]: batch})
        pk.planes[0].flush()
        jax.block_until_ready((pk.planes[0].tables, pk.planes[0].tracker.keys))

    def unpacked_cycle():
        unp.enqueue_many({names[0]: batch})
        unp.planes[0].flush()
        jax.block_until_ready((unp.planes[0].tables,
                               unp.planes[0].tracker.keys))

    tp, tu, ratio = _paired_cycles(packed_cycle, unpacked_cycle, warmup=2,
                                   reps=7)
    pf, uf = pk.planes[0], unp.planes[0]
    assert (np.asarray(pf.tables)
            == np.asarray(pack_table(uf.tables, spec_u.counter.bits))).all(), \
        "packed and unpacked epochs landed different cell states"
    assert (np.asarray(pf.tracker.keys) == np.asarray(uf.tracker.keys)).all() \
        and (np.asarray(pf.tracker.estimates)
             == np.asarray(uf.tracker.estimates)).all(), \
        "packed and unpacked epochs landed different heaps"
    return tp, tu, ratio


def _window_epoch_point(spec, t, cap, buckets=4, k=8):
    """Native zero-copy windowed flush vs the legacy restack pipeline,
    every tenant pending (same R rows both sides — the delta is pure
    data movement)."""
    wspec = WindowSpec(sketch=spec, buckets=buckets, interval=60.0)
    names = [f"tn{i}" for i in range(t)]
    nat = CountService(queue_capacity=cap, seed=0, track_top=k)
    rst = CountService(queue_capacity=cap, seed=0, track_top=k)
    for svc in (nat, rst):
        for n in names:
            svc.add_tenant(n, window=wspec)
    batches = {n: _hot_batch(cap // t, seed=7 + i)
               for i, n in enumerate(names)}

    def native_cycle():
        nat.enqueue_many(batches, ts=10.0)
        nat.planes[0].flush()
        jax.block_until_ready(nat.planes[0].tables)

    def restack_cycle():
        rst.enqueue_many(batches, ts=10.0)
        rst.planes[0].flush(dense=True)
        jax.block_until_ready(rst.planes[0].tables)

    tn, tr, ratio = _paired_cycles(native_cycle, restack_cycle, warmup=2,
                                   reps=7)
    pn, pr = nat.planes[0], rst.planes[0]
    assert (np.asarray(pn.tables) == np.asarray(pr.tables)).all(), \
        "native and restack window flushes landed different leafs"
    assert (np.asarray(pn.tracker.keys) == np.asarray(pr.tracker.keys)).all() \
        and (np.asarray(pn.tracker.estimates)
             == np.asarray(pr.tracker.estimates)).all(), \
        "native and restack window flushes landed different heaps"
    restack_bytes = (2 * t * spec.depth * spec.storage_width
                     * pn.tables.dtype.itemsize)
    return tn, tr, ratio, restack_bytes


def _structure_rows(spec_u, spec_p, t):
    """Capacity headroom from packing, derived from the storage shapes
    (no timing): tenants per VMEM block and bytes per dense flush epoch."""
    rows = []
    for tag, spec in (("unpacked", spec_u), ("packed", spec_p)):
        swept = common.format_methodology(spec)["table_bytes_streamed"]
        rows.append({
            "name": f"topk_structure/{tag}",
            "us_per_call": "",
            "derived": (f"tenants_per_vmem_block="
                        f"{ops.VMEM_TABLE_LIMIT // swept} "
                        f"epoch_bytes_T{t}={swept * t}"),
        })
    return rows


def _launch_audit(spec, cap, k=8):
    """Per-op dispatch counts over one flush epoch per scenario.

    Each scenario runs under its own `ops.audit_scope()` — a scoped tally
    that sees exactly the dispatches of its `with` block, so concurrent
    suites (or the service's own metrics registry) can't leak counts into
    the audit the way the old global reset/read pair could."""
    audit = {}
    names = ["a", "b", "c"]
    svc = CountService(spec, tenants=names, queue_capacity=cap, track_top=k)
    svc.enqueue_many({"a": _hot_batch(256, 1), "b": _hot_batch(256, 2)})
    with ops.audit_scope() as tally:
        svc.flush()
    audit["tracked_flush_epoch"] = dict(tally)
    psvc = CountService(dataclasses.replace(spec, packed=True),
                        tenants=names, queue_capacity=cap, track_top=k)
    psvc.enqueue_many({"a": _hot_batch(256, 1), "b": _hot_batch(256, 2)})
    with ops.audit_scope() as tally:
        psvc.flush()
    audit["tracked_flush_epoch_packed"] = dict(tally)
    svc.enqueue_many({"a": _hot_batch(256, 3)})
    with ops.audit_scope() as tally:
        for plane in svc.planes:
            plane.flush(dense=True)
    audit["dense_two_launch"] = dict(tally)
    wspec = WindowSpec(sketch=spec, buckets=4, interval=60.0)
    wsvc = CountService(queue_capacity=cap, track_top=k)
    for n in names:
        wsvc.add_tenant(n, window=wspec)
    for flushed in (1, 3):
        for i, n in enumerate(names[:flushed]):
            wsvc.enqueue(n, _hot_batch(256, 10 + i), ts=10.0)
        with ops.audit_scope() as tally:
            wsvc.flush()
        audit[f"window_flush_T{flushed}"] = dict(tally)
    # all three tenants cross a watermark boundary with empty queues:
    # the whole plane must rotate in ONE masked dispatch
    wplane = wsvc.planes[0]
    with ops.audit_scope() as tally:
        wplane.advance_many([(i, 70.0) for i in range(len(names))],
                            wsvc.flush)
    audit["window_rotation_T3"] = dict(tally)
    return audit


def _rows(quick: bool):
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    cap = 2 * ops.CHUNK
    points = [8, 16] if quick else [8, 16, 32]
    rows = []
    for t in points:
        ta, td, ratio = _flush_point(spec, t, cap)
        rows += [
            {"name": f"topk_flush_hot1/active_T{t}",
             "us_per_call": round(ta * 1e6),
             "derived": f"{round(cap / ta / 1e6, 1)} Mkeys/s"},
            {"name": f"topk_flush_hot1/dense_T{t}",
             "us_per_call": round(td * 1e6),
             "derived": f"speedup_x{ratio:.2f}"},
        ]
    for t in points:
        tf, tp, ratio = _epoch_point(spec, t, cap)
        rows += [
            {"name": f"topk_epoch/fused_T{t}",
             "us_per_call": round(tf * 1e6),
             "derived": "1 launch: update+re-score"},
            {"name": f"topk_epoch/two_launch_T{t}",
             "us_per_call": round(tp * 1e6),
             "derived": f"speedup_x{ratio:.2f}"},
        ]
    for t in points[:1] if quick else points[:2]:
        tp, tt, t_ref = _tracker_point(spec, t, cap)
        rows += [
            {"name": f"topk_tracker/flush_tracked_T{t}",
             "us_per_call": round(tt * 1e6),
             "derived": f"overhead_x{tt / tp:.2f}"},
            {"name": f"topk_tracker/refresh_T{t}",
             "us_per_call": round(t_ref * 1e6),
             "derived": f"K=64+{ops.CHUNK} cands"},
        ]
    pspec = dataclasses.replace(spec, packed=True)
    for t in points[:1] if quick else points[:2]:
        tp, tu, ratio = _packed_epoch_point(spec, pspec, t, cap)
        rows += [
            {"name": f"topk_packed/packed_T{t}",
             "us_per_call": round(tp * 1e6),
             "derived": f"{round(cap / tp / 1e6, 1)} Mkeys/s"},
            {"name": f"topk_packed/unpacked_T{t}",
             "us_per_call": round(tu * 1e6),
             "derived": f"packed_speedup_x{ratio:.2f}"},
        ]
    for t in points[:1] if quick else points[:2]:
        tn, tr, ratio, restack_bytes = _window_epoch_point(spec, t, cap)
        rows += [
            {"name": f"window_epoch_native/native_T{t}",
             "us_per_call": round(tn * 1e6),
             "derived": "0 restack bytes (donated leaf)"},
            {"name": f"window_epoch_native/restack_T{t}",
             "us_per_call": round(tr * 1e6),
             "derived": f"speedup_x{ratio:.2f} "
                        f"restack_bytes={restack_bytes}"},
        ]
    rows += _structure_rows(spec, pspec, t=points[-1])
    return rows


def run(quick: bool = False) -> list[dict]:
    rows = _rows(quick)
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    audit = _launch_audit(spec, 2 * ops.CHUNK)
    os.makedirs("results", exist_ok=True)
    methodology = dict(METHODOLOGY, **common.mode_methodology())
    methodology["cell_format"] = {
        "unpacked": common.format_methodology(spec),
        "packed": common.format_methodology(
            dataclasses.replace(spec, packed=True)),
    }
    with open("results/bench_topk.json", "w") as f:
        json.dump({"methodology": methodology, "rows": rows,
                   "launch_audit": audit}, f, indent=1)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    common.emit(run(quick=args.quick))
