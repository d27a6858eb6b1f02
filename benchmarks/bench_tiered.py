"""Tiered-plane benchmarks: host-resident cold tier vs all-resident planes.

The subsystem under test is `TierSpec(max_hot_tenants=N)` on a
`CountService`: only the N most recently active tenants per plane keep a
row in the device-resident (T, d, w) table, the rest live in a host-side
NumPy cold store fed by batched XLA-reference spills (`ops.tier_spill`,
same dedup + parity-uniforms grid as the fused device flush, so every
tenant's table stays bit-identical to an all-resident service).  Three
questions, plus a machine-checked launch audit:

  1. CAPACITY — how many tenants does one device-table byte budget now
     serve?  A tiered service at max_hot_tenants=8 ingests T in
     {16, 64, 128} all-active tenants; the row prices a full
     everyone-active epoch (the spill-heavy worst case) and the derived
     column records the device/host byte split
     (`tiering.tier_memory_bytes`) and the T/8 capacity multiple — the
     10-100x tenant-per-chip claim as a measured number.
  2. HOT PATH — the acceptance ratio: traffic confined to the hot
     working set (the 8 device-resident tenants, per-event tenant
     popularity Zipf 1.1 among them) must ingest within ~10% of an
     all-resident service, because the tiered flush issues the IDENTICAL
     single fused update+score dispatch.  Interleaved pairs, median
     per-pair ratio; afterwards query_all AND topk are asserted
     bit-identical between the two services.
  3. CHURN — a rotating working set (the active group shifts by half its
     width every epoch) forces demote->promote swaps; the row prices a
     churn epoch and the derived column records the promotion/demotion/
     spill-byte traffic the rotation forced (deterministic: fixed seed).

The ingest cycles run under `jax.transfer_guard_device_to_host
("disallow")` — the tiering layer's sanctioned cold-tier copies run
under their own scoped allowance, so the guard proves the hot path
proper never reads the ring back.  The results JSON records a
`launch_audit` section (per-op dispatch counts under
`ops.audit_scope()`) that check_regression.py gates: a hot-only tiered
flush epoch is still exactly ONE `update_score_rows` dispatch (packed
storage too), a mixed epoch adds exactly one batched `tier_spill`, and a
swap epoch adds exactly one demotion gather + one promotion scatter.

    PYTHONPATH=src python -m benchmarks.bench_tiered [--quick]
"""
from __future__ import annotations

import dataclasses
import json
import os
import statistics
import time

import jax
import numpy as np

from benchmarks import common
from benchmarks.bench_ingest import _paired_cycles
from repro.core import CMLS16, SketchSpec
from repro.kernels import ops
from repro.stream import CountService, TierSpec, tier_memory_bytes

METHODOLOGY = {
    "capacity": "one tiered CountService (max_hot_tenants=8, LRU) per "
                "point, T in the sweep all enqueueing 1 kernel-CHUNK per "
                "cycle — every epoch updates 8 hot rows through the fused "
                "dispatch and spills T-8 cold rows through ONE batched "
                "ops.tier_spill, the everyone-active worst case.  "
                "us_per_call = median epoch over 5 cycles after 2 "
                "warmups; derived = device/host byte split "
                "(tiering.tier_memory_bytes) and the T/8 tenants-per-"
                "device-byte multiple.",
    "hot_path": "the acceptance ratio: a tiered (max_hot_tenants=8) and "
                "an all-resident service, both track_top=8, ingest the "
                "IDENTICAL stream confined to the 8 device-resident "
                "tenants (per-event tenant popularity Zipf 1.1 over the "
                "hot set, 8 CHUNKs of keys per cycle) out of T total "
                "tenants.  Both flushes group active rows by fill class "
                "and issue the same single fused update_score_rows epoch, "
                "so the ratio prices pure tiering overhead (the host "
                "queue mirror + slot indirection).  Interleaved pairs, "
                "median per-pair ratio (tiered/resident, <= ~1.1 "
                "accepted); afterwards query_all over every tenant and "
                "topk over a hot tenant are asserted bit-identical "
                "between the services.",
    "churn": "rotating working set: T tenants, max_hot_tenants=8, each "
             "epoch the 8-tenant active group shifts by 4 (half-overlap) "
             "so every epoch demotes up to 4 idle hot tenants and "
             "promotes the newly active cold ones (one gather->host copy "
             "+ one host->device scatter per epoch, amortized over the "
             "ring).  us_per_call = median epoch over 12 rotations; "
             "derived = total promotions/demotions/spill-bytes the "
             "rotation forced (fixed seed, deterministic).",
    "launch_audit": "per-op dispatch counts (ops.audit_scope) captured "
                    "over ONE tiered flush epoch per scenario: hot-only "
                    "traffic must flush in exactly one update_score_rows "
                    "dispatch (unpacked AND packed storage — the cold "
                    "tier never changes the hot launch count); traffic "
                    "touching cold tenants adds exactly one batched "
                    "tier_spill; an epoch whose recency plan swaps "
                    "membership adds exactly one tier_demote gather + "
                    "one tier_promote scatter.  Gated by "
                    "check_regression.py.",
}

HOT = 8  # max_hot_tenants for every scenario: the acceptance geometry


def _median_cycle(cycle, warmup=2, reps=5):
    for _ in range(warmup):
        cycle()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        cycle()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def _capacity_point(spec, t, cap):
    names = [f"tn{i:03d}" for i in range(t)]
    tspec = TierSpec(max_hot_tenants=HOT)
    svc = CountService(spec, tenants=names, queue_capacity=cap, seed=0,
                       tier=tspec)
    rng = np.random.default_rng(t)
    batches = (rng.zipf(1.3, (t, ops.CHUNK)) % 50_000).astype(np.uint32)
    events = {n: batches[i] for i, n in enumerate(names)}

    def cycle():
        svc.enqueue_many(events)
        svc.flush()
        jax.block_until_ready(svc.planes[0].tables)

    with jax.transfer_guard_device_to_host("disallow"):
        te = _median_cycle(cycle)
    return te, tier_memory_bytes(spec, tspec, t)


def _hot_ratio_point(spec, t, cap):
    """Tiered vs all-resident on hot-working-set traffic: same stream,
    same grouped flush geometry, so the tiered service issues the
    identical fused dispatches and the ratio isolates tiering overhead."""
    names = [f"tn{i:03d}" for i in range(t)]
    tiered = CountService(spec, tenants=names, queue_capacity=cap, seed=0,
                          track_top=HOT, tier=TierSpec(max_hot_tenants=HOT))
    resident = CountService(spec, tenants=names, queue_capacity=cap, seed=0,
                            track_top=HOT)
    rng = np.random.default_rng(17)
    # per-event tenant popularity: Zipf 1.1 over the device-resident
    # working set (the first HOT tenants added hold the hot slots)
    owner = (rng.zipf(1.1, HOT * ops.CHUNK) - 1) % HOT
    keys = (rng.zipf(1.3, owner.size) % 50_000).astype(np.uint32)
    events = {names[i]: keys[owner == i] for i in range(HOT)
              if (owner == i).any()}

    def tiered_cycle():
        tiered.enqueue_many(events)
        tiered.flush()
        jax.block_until_ready(tiered.planes[0].tables)

    def resident_cycle():
        resident.enqueue_many(events)
        resident.flush()
        jax.block_until_ready(resident.planes[0].tables)

    with jax.transfer_guard_device_to_host("disallow"):
        tt, tr, ratio = _paired_cycles(tiered_cycle, resident_cycle)
    # identical stream + identical grouped dispatches => every tenant
    # (hot AND never-touched cold) answers bit-identically to the
    # all-resident service, trackers included
    probes = np.stack([np.arange(16, dtype=np.uint32)] * t)
    a, b = tiered.query_all(probes), resident.query_all(probes)
    for n in names:
        assert (np.asarray(a[n]) == np.asarray(b[n])).all(), \
            f"tiered and resident services answer {n} differently"
    ka, va = tiered.topk(names[0], 5)
    kb, vb = resident.topk(names[0], 5)
    assert (np.asarray(ka) == np.asarray(kb)).all() and \
        (np.asarray(va) == np.asarray(vb)).all(), \
        "tiered and resident trackers diverged on a hot tenant"
    return tt, tr, ratio


def _churn_point(spec, t, cap, epochs=12):
    names = [f"tn{i:03d}" for i in range(t)]
    svc = CountService(spec, tenants=names, queue_capacity=cap, seed=0,
                       track_top=HOT, tier=TierSpec(max_hot_tenants=HOT))
    label = svc.planes[0].label
    rng = np.random.default_rng(23)
    batches = (rng.zipf(1.3, (HOT, ops.CHUNK)) % 50_000).astype(np.uint32)
    ts = []
    with jax.transfer_guard_device_to_host("disallow"):
        for e in range(epochs):
            start = (e * (HOT // 2)) % t  # half-overlap rotation
            events = {names[(start + i) % t]: batches[i]
                      for i in range(HOT)}
            t0 = time.perf_counter()
            svc.enqueue_many(events)
            svc.flush()
            jax.block_until_ready(svc.planes[0].tables)
            ts.append(time.perf_counter() - t0)
    promos = int(svc.metrics.counter("tier_promotions", plane=label).value)
    demos = int(svc.metrics.counter("tier_demotions", plane=label).value)
    sbytes = int(svc.metrics.counter("tier_spill_bytes", plane=label).value)
    # drop the first two epochs: compilation + the tier warm-up transient
    return statistics.median(ts[2:]), promos, demos, sbytes


def _launch_audit(spec, cap):
    """Per-op dispatch counts over one tiered flush epoch per scenario.

    max_hot_tenants=2 over 6 tenants; equal batch sizes keep every epoch
    in ONE fill class so the scenario isolates the tier split, not the
    per-row trim.  The swap scenario leaves one standing hot tenant idle
    for an epoch while a cold tenant goes active, so the LRU plan demotes
    and promotes exactly one row inside the flush."""
    audit = {}
    rng = np.random.default_rng(3)

    def batch():
        return (rng.zipf(1.3, 512) % 50_000).astype(np.uint32)

    for suffix, s in (("", spec),
                      ("_packed", dataclasses.replace(spec, packed=True))):
        names = [f"tn{i}" for i in range(6)]
        svc = CountService(s, tenants=names, queue_capacity=cap, seed=0,
                           track_top=4, tier=TierSpec(max_hot_tenants=2))
        # hot-only epoch: both device-resident tenants, nobody cold
        svc.enqueue_many({names[0]: batch(), names[1]: batch()})
        with ops.audit_scope() as tally:
            svc.flush()
        audit[f"tiered_flush_hot_only{suffix}"] = dict(sorted(tally.items()))
        if suffix:
            continue
        # mixed epoch: the hot pair stays active (no LRU victims), one
        # cold tenant rides the batched spill
        svc.enqueue_many({names[0]: batch(), names[1]: batch(),
                          names[2]: batch()})
        with ops.audit_scope() as tally:
            svc.flush()
        audit["tiered_flush_mixed"] = dict(sorted(tally.items()))
        # swap epoch: tn1 idles while cold tn3 goes active -> the plan
        # demotes tn1 and promotes tn3 inside the same flush
        svc.enqueue_many({names[0]: batch(), names[3]: batch()})
        with ops.audit_scope() as tally:
            svc.flush()
        audit["tiered_swap_epoch"] = dict(sorted(tally.items()))
    return audit


def _rows(quick: bool):
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    cap = 8 * ops.CHUNK
    capacity = [16, 64] if quick else [16, 64, 128]
    hot_ratio = [64] if quick else [64, 128]
    churn = [32] if quick else [32, 64]
    rows = []
    for t in capacity:
        te, mem = _capacity_point(spec, t, cap)
        rows.append(
            {"name": f"tiered_capacity/T{t}_hot{HOT}",
             "us_per_call": round(te * 1e6),
             "derived": f"{t // HOT}x_tenants_per_device_byte "
                        f"hot={mem['hot'] // 1024}KiB "
                        f"cold={mem['cold'] // 1024}KiB"})
    for t in hot_ratio:
        tt, tr, ratio = _hot_ratio_point(spec, t, cap)
        rows += [
            {"name": f"tiered_hot/tiered_T{t}",
             "us_per_call": round(tt * 1e6),
             "derived": f"{round(HOT * ops.CHUNK / tt / 1e6, 1)} Mkeys/s"},
            {"name": f"tiered_hot/resident_T{t}",
             "us_per_call": round(tr * 1e6),
             "derived": f"hot_path_ratio_x{ratio:.2f}"},
        ]
    for t in churn:
        te, promos, demos, sbytes = _churn_point(spec, t, cap)
        rows.append(
            {"name": f"tiered_churn/T{t}_hot{HOT}",
             "us_per_call": round(te * 1e6),
             "derived": f"promotions={promos} demotions={demos} "
                        f"spill_bytes={sbytes}"})
    return rows


def run(quick: bool = False) -> list[dict]:
    rows = _rows(quick)
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    audit = _launch_audit(spec, 2 * ops.CHUNK)
    os.makedirs("results", exist_ok=True)
    methodology = dict(METHODOLOGY, **common.mode_methodology())
    with open("results/bench_tiered.json", "w") as f:
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
