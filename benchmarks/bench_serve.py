"""Serve-path load harness: production-shaped traffic with latency SLOs.

The other suites time kernels and planes in isolation; this one drives a
`CountService` the way production does — mixed-skew multi-tenant streams
through `enqueue_many`, reads through `query_all`/`topk`/`admit`
interleaved with the ingest — and reports what an operator watches:
sustained QPS per scenario and p50/p99 op latency.  Four scenarios, per
the workload-sweep evaluation practice the serve path is built for
(skew changes both error and cost under conservative updates, so a
single uniform trace proves nothing):

  1. ZIPF MIX — half the tenants draw keys from Zipf 1.05 (heavy tail,
     near-uniform: the collision-heavy worst case), half from Zipf 1.3
     (skewed: the conservative-update best case); every cycle ingests
     all tenants and serves query_all + topk + admit.
  2. FLASH CROWD — a steady baseline phase, then one tenant's traffic
     spikes 10x into a few hot keys while every other tenant keeps its
     base rate; reads continue through the spike.  QPS is reported for
     both phases, latency over the whole run.
  3. CHURN — a tiered service (max_hot_tenants=4 over 16 tenants) under
     a rotating working set: the 4-tenant active group shifts by half
     its width every cycle, forcing demote/promote swaps between the
     device and host tiers while query_all keeps serving every tenant.
  4. WATERMARK SKEW — windowed tenants (8-bucket watermark rings) fed
     event-time batches whose timestamps advance at per-tenant rates,
     with late-but-in-interval events riding every cycle and occasional
     multi-interval jumps forcing rotations mid-serve.

Latency is timed per op here: `perf_counter` around the call plus its
own `block_until_ready` on what the op produced, so p50/p99 cover the
device work each op claims, not just its dispatch time.  Warmup cycles
(compilation) are excluded by clearing the op times before the timed
loop.

The results JSON carries a `launch_audit` section (per-op dispatch
counts under `ops.audit_scope()`) that check_regression.py gates — the
serve-path epoch-scheduler claims as machine-checked facts:

  * `query_all` over a plane with W windowed tenants is ONE row-stacked
    `window_query_stacked` dispatch (was W per-ring launches);
  * a read on a clean service issues ZERO update dispatches (its plane
    skips the flush epoch outright — no PRNG draw, no launch);
  * a read scopes its flush to the OWNING plane: another plane's dirty
    ring stays buffered (no cross-plane epoch on the read path).

    PYTHONPATH=src python -m benchmarks.bench_serve [--quick]
"""
from __future__ import annotations

import collections
import json
import os
import statistics
import time

import jax
import numpy as np

from benchmarks import common
from repro.core import CMLS16, CMS32, SketchSpec
from repro.core.admission import AdmissionSpec
from repro.kernels import ops
from repro.stream import CountService, TierSpec, WindowSpec

METHODOLOGY = {
    "latency": "per-op wall time: perf_counter around the call plus its "
               "own block_until_ready on what the op produced — device "
               "work included, async-dispatch enqueue time alone never "
               "reported.  p50/p99 are exact percentiles over the timed "
               "cycles' op times (warmup/compilation cycles excluded by "
               "clearing them); the *_p50/*_p99 rows put both under the "
               "calibration-normalized regression gate.",
    "qps": "sustained events/second over the timed serve loop, ingest "
           "AND reads included (the operator's number: what the service "
           "absorbs while also answering queries).  us_per_call = median "
           "full serve cycle.",
    "zipf_mix": "8 plain tenants on one plane, half drawing keys from "
                "Zipf 1.05 (heavy-tailed, collision-heavy) and half from "
                "Zipf 1.3 (skewed), 512 keys each per cycle; every cycle "
                "runs enqueue_many + query_all + topk + admit (tracker-"
                "fed admission tenant rides the same plane).",
    "flash_crowd": "8 tenants at a 256-key base rate; after the base "
                   "phase one tenant spikes 10x into 32 hot keys while "
                   "the others hold their rate, reads continuing.  QPS "
                   "reported separately for base and spike phases.",
    "churn": "tiered service (TierSpec(max_hot_tenants=4), LRU) over 16 "
             "tenants; the 4-tenant active group rotates by 2 every "
             "cycle, so each cycle demotes idle hot tenants and promotes "
             "newly active cold ones while query_all serves all 16.  "
             "derived = the swap traffic the rotation forced.",
    "watermark_skew": "4 windowed tenants (8 x 60s watermark buckets) "
                      "fed event-time batches: timestamps advance at "
                      "per-tenant rates, every cycle also lands late-"
                      "but-in-interval events (same-interval timestamps "
                      "behind the max seen), and every third cycle one "
                      "tenant jumps 2+ intervals, rotating mid-serve; "
                      "query_all + topk serve each cycle.",
    "launch_audit": "per-op dispatch counts (ops.audit_scope) for the "
                    "epoch-scheduler claims: windowed query_all = ONE "
                    "window_query_stacked dispatch for W tenants; a "
                    "clean-service read = ZERO update dispatches; a read "
                    "with ANOTHER plane dirty still flushes nothing "
                    "(scoped epochs); a read with its OWN plane dirty "
                    "pays exactly that plane's epoch.  Gated by "
                    "check_regression.py.",
}

PROBE_N = 64  # probes per query_all/query call in every scenario


class _OpTimes:
    """Per-op wall times in us: `time(op, fn, ...)` runs `fn`, then blocks
    on what it produced (or on `sync()`, for an op that returns nothing),
    so each time covers the device work the op issued."""

    def __init__(self):
        self.durs = collections.defaultdict(list)

    def time(self, op: str, fn, *args, sync=None, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out if sync is None else sync())
        self.durs[op].append((time.perf_counter() - t0) * 1e6)
        return out

    def clear(self) -> None:
        self.durs.clear()


def _rings(svc) -> list:
    """Every plane's device ring: what an enqueue leaves in flight."""
    return [p.ring.queue for p in svc.planes]


def _pct_rows(times: _OpTimes, scenario: str, ops_wanted) -> list[dict]:
    """p50/p99 rows per op from its timed calls."""
    rows = []
    for op in ops_wanted:
        durs = times.durs.get(op)
        if not durs:
            continue
        p50, p99 = np.percentile(durs, 50), np.percentile(durs, 99)
        rows += [
            {"name": f"serve_{scenario}/{op}_p50",
             "us_per_call": round(float(p50)),
             "derived": f"n={len(durs)} calls"},
            {"name": f"serve_{scenario}/{op}_p99",
             "us_per_call": round(float(p99)),
             "derived": f"max={round(float(max(durs)))}us"},
        ]
    return rows


def _qps_row(scenario: str, cycle_times, events_per_cycle: int,
             suffix: str = "", extra: str = "") -> dict:
    med = statistics.median(cycle_times)
    qps = events_per_cycle / med
    tag = f"serve_{scenario}/qps{suffix}"
    derived = f"{qps / 1e6:.3f} Mevents/s sustained"
    if extra:
        derived += f" {extra}"
    return {"name": tag, "us_per_call": round(med * 1e6),
            "derived": derived}


def _scenario_zipf_mix(quick: bool) -> list[dict]:
    spec = SketchSpec(width=2048, depth=2, counter=CMLS16)
    names = [f"mix{i}" for i in range(8)]
    times = _OpTimes()
    svc = CountService(spec, tenants=names, queue_capacity=8192, seed=0,
                       track_top=8)
    svc.add_tenant("adm", admission=AdmissionSpec(
        threshold=32.0, n_fallback=512, table_rows=1 << 14))
    rng = np.random.default_rng(11)
    probes = np.arange(PROBE_N, dtype=np.uint32)

    def events():
        ev = {}
        for i, n in enumerate(names):
            a = 1.05 if i % 2 == 0 else 1.3  # half heavy-tail, half skewed
            ev[n] = (rng.zipf(a, 512) % 50_000).astype(np.uint32)
        ev["adm"] = (rng.zipf(1.3, 512) % 50_000).astype(np.uint32)
        return ev

    def cycle():
        times.time("enqueue_many", svc.enqueue_many, events(),
                   sync=lambda: _rings(svc))
        times.time("query_all", svc.query_all, probes)
        times.time("topk", svc.topk, names[1], 4)
        times.time("admit", svc.admit, "adm", probes[:16])

    warmup, reps = (1, 3) if quick else (2, 8)
    for _ in range(warmup):
        cycle()
    times.clear()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        cycle()
        ts.append(time.perf_counter() - t0)
    rows = [_qps_row("zipf_mix", ts, 512 * 9)]
    rows += _pct_rows(times, "zipf_mix",
                      ("enqueue_many", "query_all", "topk", "admit"))
    return rows


def _scenario_flash_crowd(quick: bool) -> list[dict]:
    spec = SketchSpec(width=2048, depth=2, counter=CMLS16)
    names = [f"fc{i}" for i in range(8)]
    times = _OpTimes()
    svc = CountService(spec, tenants=names, queue_capacity=16384, seed=0,
                       track_top=8)
    rng = np.random.default_rng(13)
    probes = np.arange(PROBE_N, dtype=np.uint32)
    base_n, spike_n = 256, 2560  # the 10x spike

    def cycle(spike: bool):
        ev = {n: (rng.zipf(1.2, base_n) % 50_000).astype(np.uint32)
              for n in names}
        if spike:
            # the crowd converges on a handful of ids (the viral object)
            ev[names[0]] = (rng.integers(0, 32, spike_n)
                            .astype(np.uint32))
        times.time("enqueue_many", svc.enqueue_many, ev,
                   sync=lambda: _rings(svc))
        times.time("query_all", svc.query_all, probes)
        times.time("topk", svc.topk, names[0], 4)

    warmup, reps = (1, 3) if quick else (2, 6)
    for _ in range(warmup):
        cycle(False)
        cycle(True)  # compile the spike shapes too: timed cycles only
    times.clear()
    base_ts, spike_ts = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        cycle(False)
        base_ts.append(time.perf_counter() - t0)
    for _ in range(reps):
        t0 = time.perf_counter()
        cycle(True)
        spike_ts.append(time.perf_counter() - t0)
    rows = [
        _qps_row("flash_crowd", base_ts, base_n * 8, suffix="_base"),
        _qps_row("flash_crowd", spike_ts, base_n * 7 + spike_n,
                 suffix="_spike", extra="(10x one-tenant spike)"),
    ]
    rows += _pct_rows(times, "flash_crowd", ("enqueue_many", "query_all"))
    return rows


def _scenario_churn(quick: bool) -> list[dict]:
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    t, hot = 16, 4
    names = [f"ch{i:02d}" for i in range(t)]
    times = _OpTimes()
    svc = CountService(spec, tenants=names, queue_capacity=4096, seed=0,
                       tier=TierSpec(max_hot_tenants=hot))
    label = svc.planes[0].label
    rng = np.random.default_rng(17)
    probes = np.arange(PROBE_N, dtype=np.uint32)

    def cycle(e: int):
        start = (e * (hot // 2)) % t  # half-overlap rotation
        ev = {names[(start + i) % t]:
              (rng.zipf(1.3, 512) % 50_000).astype(np.uint32)
              for i in range(hot)}
        times.time("enqueue_many", svc.enqueue_many, ev,
                   sync=lambda: _rings(svc))
        times.time("query_all", svc.query_all, probes)

    warmup, reps = (2, 4) if quick else (2, 10)
    for e in range(warmup):
        cycle(e)
    times.clear()
    ts = []
    for e in range(reps):
        t0 = time.perf_counter()
        cycle(warmup + e)
        ts.append(time.perf_counter() - t0)
    promos = int(svc.metrics.counter("tier_promotions", plane=label).value)
    demos = int(svc.metrics.counter("tier_demotions", plane=label).value)
    rows = [_qps_row("churn", ts, 512 * hot,
                     extra=f"promotions={promos} demotions={demos}")]
    rows += _pct_rows(times, "churn", ("enqueue_many", "query_all"))
    return rows


def _scenario_watermark_skew(quick: bool) -> list[dict]:
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    wspec = WindowSpec(sketch=spec, buckets=8, interval=60.0)
    names = [f"wm{i}" for i in range(4)]
    times = _OpTimes()
    svc = CountService(queue_capacity=8192, seed=0, track_top=8)
    for n in names:
        svc.add_tenant(n, window=wspec)
    rng = np.random.default_rng(19)
    probes = np.arange(PROBE_N, dtype=np.uint32)
    # per-tenant event-time rates: tenant i's clock advances ~ (i+1)/2
    # intervals per cycle, so watermarks drift apart and rotations land
    # on different cycles per tenant
    clocks = np.zeros(4)

    def cycle(e: int):
        rates = (np.arange(4) + 1) * 30.0
        clocks[:] += rates * rng.uniform(0.8, 1.2, 4)
        if e % 3 == 2:
            clocks[e % 4] += 2.5 * wspec.interval  # skew jump: 2+ intervals
        for i, n in enumerate(names):
            # the batch's own timestamp: LATE relative to the tenant's max
            # seen time but inside the current interval (admissible
            # lateness — behind-watermark events raise instead)
            late = clocks[i] - (clocks[i] % wspec.interval) * rng.uniform()
            times.time("enqueue_many", svc.enqueue_many,
                       {n: (rng.zipf(1.2, 512) % 50_000).astype(np.uint32)},
                       ts=float(late), sync=lambda: _rings(svc))
        times.time("query_all", svc.query_all, probes)
        times.time("topk", svc.topk, names[0], 4)

    warmup, reps = (1, 3) if quick else (2, 8)
    for e in range(warmup):
        cycle(e)
    times.clear()
    ts = []
    for e in range(reps):
        t0 = time.perf_counter()
        cycle(warmup + e)
        ts.append(time.perf_counter() - t0)
    rows = [_qps_row("watermark_skew", ts, 512 * 4)]
    rows += _pct_rows(times, "watermark_skew",
                      ("enqueue_many", "query_all", "topk"))
    return rows


def _launch_audit() -> dict:
    """Per-op dispatch counts for the epoch-scheduler claims."""
    audit = {}
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    rng = np.random.default_rng(7)
    probes = np.arange(16, dtype=np.uint32)

    def batch():
        return (rng.zipf(1.3, 512) % 50_000).astype(np.uint32)

    # W=4 windowed tenants, flushed: query_all = ONE stacked dispatch
    wspec = WindowSpec(sketch=spec, buckets=4, interval=60.0)
    svc = CountService(queue_capacity=2048, seed=0)
    for i in range(4):
        svc.add_tenant(f"w{i}", window=wspec)
    svc.enqueue_many({f"w{i}": batch() for i in range(4)}, ts=0.0)
    svc.flush()
    with ops.audit_scope() as tally:
        svc.query_all(probes)
    audit["windowed_query_all_W4"] = dict(sorted(tally.items()))

    # clean-service read: the query launch and NOTHING else (no update
    # dispatch, no PRNG draw — the plane skips its epoch outright)
    svc2 = CountService(spec, tenants=["a", "b"], queue_capacity=2048,
                        seed=0)
    svc2.enqueue("a", batch())
    svc2.flush()
    with ops.audit_scope() as tally:
        svc2.query("a", probes)
    audit["clean_read"] = dict(sorted(tally.items()))

    # scoped epochs: tenant "m"'s plane is dirty, tenant "a"'s is clean —
    # reading "a" must leave "m"'s ring buffered (no cross-plane flush)
    svc3 = CountService(spec, tenants=["a"], queue_capacity=2048, seed=0)
    svc3.add_tenant("m", spec=SketchSpec(width=512, depth=2, counter=CMS32))
    svc3.enqueue("a", batch())
    svc3.flush()
    svc3.enqueue("m", batch())
    with ops.audit_scope() as tally:
        svc3.query("a", probes)
    audit["scoped_read_other_plane_dirty"] = dict(sorted(tally.items()))
    # ... while reading a tenant whose OWN plane is dirty pays exactly
    # that plane's epoch (one fused update) plus the query launch
    svc3.enqueue("a", batch())
    with ops.audit_scope() as tally:
        svc3.query("a", probes)
    audit["scoped_read_own_plane_dirty"] = dict(sorted(tally.items()))
    return audit


def run(quick: bool = False) -> list[dict]:
    rows = []
    rows += _scenario_zipf_mix(quick)
    rows += _scenario_flash_crowd(quick)
    rows += _scenario_churn(quick)
    rows += _scenario_watermark_skew(quick)
    audit = _launch_audit()
    os.makedirs("results", exist_ok=True)
    methodology = dict(METHODOLOGY, **common.mode_methodology())
    with open("results/bench_serve.json", "w") as f:
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
