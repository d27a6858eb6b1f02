"""Counting-plane serving driver: spec-bucketed planes + device-ring ingest.

    PYTHONPATH=src python -m repro.launch.serve_counts \
        --tenants 8 --batches 50 --batch 4096

Stands up a `CountService` whose tenants span TWO sketch specs (a wide
CMLS16 plane and a narrow CMS32 metrics plane) plus a watermark-windowed
tenant, pushes a Zipfian event stream through the device-resident ingest
rings (`enqueue_many`: one scatter-append launch per plane per microbatch;
every flush is ONE fused update+re-score epoch per plane — track_top is
on, so the heavy-hitter heaps refresh inside the update launch), serves
ALL tenants' hot-key queries with one fused query launch per plane, reads
the trending board off the tracker, maps ids through the tracker-fed
admission plane, and round-trips the whole multi-plane registry through a
checkpoint.  The ingest loop runs under
`jax.transfer_guard_device_to_host("disallow")` — the queue buffers
provably never cross back to the host.  `--tier-hot N` turns on tiered
hot/cold storage (`TierSpec(max_hot_tenants=N)`): only the N most active
tenants per plane stay device-resident, the rest serve from the host cold
store, and the driver prints each plane's tier occupancy and
promotion/demotion/spill counters (the tiering layer's host copies run
under their own scoped transfer-guard allowance, so the disallow pin
still holds for the ingest path proper).

The whole run is observed through `repro.obs`: per-plane ring/watermark
gauges and dispatch tallies come off the service's metrics registry
(never `svc.stats`), and a sampled exact shadow probe scores serving
accuracy by frequency decile.  Scrape the run with:

    PYTHONPATH=src python -m repro.launch.serve_counts \
        --metrics-out /tmp/serve.prom --trace-out /tmp/serve_trace

`serve.prom` is Prometheus text exposition (point a scraper at it or
diff it in CI).  `serve_trace/` receives a `jax.profiler` trace of the
serve phases: the service's `cms.*` host spans (enqueue, flush epoch and
its gather/update/reselect, query, ...) on the same clock as the device
ops they issue; open it in TensorBoard's profile plugin or Perfetto.

`main` returns a `ServeRun` (the service it built, the CMS32 metrics
tenant's raw event stream for exact-count checks, per-phase wall times
and the probe's ARE deciles) so `chip_smoke.py` can drive this path and
check it.  A snapshot round-trip whose answers differ raises.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import tempfile
import time

import numpy as np

import jax

from repro import obs
from repro.core import CMLS16, CMS32, SketchSpec
from repro.core.admission import AdmissionSpec
from repro.launch.cache import enable_compile_cache
from repro.stream import CountService, TierSpec, WindowPlane, WindowSpec


@dataclasses.dataclass
class ServeRun:
    """What one `main` run built and measured."""
    svc: CountService
    tenants: list            # the wide plane's tenant names
    metrics_events: np.ndarray   # every key enqueued to "metrics_qps"
    phases: dict             # phase -> wall seconds (device work included)
    ares: dict               # tenant -> ARE by frequency decile


def main(argv=None) -> ServeRun:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--batches", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--queue-cap", type=int, default=8192)
    ap.add_argument("--width", type=int, default=4096)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None,
                    help="write Prometheus text exposition here on exit")
    ap.add_argument("--trace-out", default=None,
                    help="record a jax.profiler trace of the serve phases "
                         "into this directory")
    ap.add_argument("--probe-rate", type=float, default=0.05,
                    help="hash-sample rate of the exact accuracy shadow")
    ap.add_argument("--tier-hot", type=int, default=None,
                    help="turn on tiered hot/cold storage: keep at most "
                         "this many tenants per plane device-resident "
                         "(TierSpec(max_hot_tenants=...), LRU victims)")
    args = ap.parse_args(argv)

    # --trace-out: the serve phases run inside one jax.profiler session
    tracing = (jax.profiler.trace(args.trace_out) if args.trace_out
               else contextlib.nullcontext())
    with tracing:
        spec = SketchSpec(width=args.width, depth=args.depth, counter=CMLS16)
        metrics_spec = SketchSpec(width=1024, depth=2, counter=CMS32)
        names = [f"tenant_{t:02d}" for t in range(args.tenants)]
        registry = obs.MetricsRegistry()
        slo_probe = obs.AccuracyProbe(rate=args.probe_rate)
        tier = (None if args.tier_hot is None
                else TierSpec(max_hot_tenants=args.tier_hot))
        svc = CountService(spec, tenants=names, queue_capacity=args.queue_cap,
                           seed=args.seed, track_top=16, metrics=registry,
                           probe=slo_probe, tier=tier)
        # heterogeneous plane: two CMS32 metrics tenants ride the same service
        svc.add_tenant("metrics_qps", spec=metrics_spec)
        svc.add_tenant("metrics_err", spec=metrics_spec)
        # watermark-windowed tenant: 60s buckets, rotation driven by event time
        wspec = WindowSpec(sketch=spec, buckets=8, interval=60.0)
        svc.add_tenant("trending", window=wspec)
        # tracker-fed admission tenant: hot ids earn private embedding rows
        aspec = AdmissionSpec(threshold=64.0, n_fallback=1024,
                              table_rows=1 << 16)
        svc.add_tenant("emb_ids", admission=aspec)
        rng = np.random.default_rng(args.seed)
        phases = {}
        metrics_events = []

        t0 = time.perf_counter()
        ts = 0.0
        with jax.transfer_guard_device_to_host("disallow"):
            for _ in range(args.batches):
                events = {}
                for t, name in enumerate(names):
                    # each tenant counts its own key universe (offset by id)
                    keys = (rng.zipf(1.3, args.batch) % 10_000) + t * 1_000_000
                    events[name] = keys.astype(np.uint32)
                events["metrics_qps"] = (rng.zipf(1.3, 256) % 500).astype(
                    np.uint32)
                metrics_events.append(events["metrics_qps"])
                events["emb_ids"] = (
                    rng.zipf(1.3, args.batch) % 10_000).astype(np.uint32)
                svc.enqueue_many(events)
                ts += float(rng.exponential(25.0))
                svc.enqueue("trending",
                            (rng.zipf(1.3, args.batch) % 10_000).astype(
                                np.uint32), ts=ts)
            svc.flush()
        dt = time.perf_counter() - t0
        phases["ingest"] = dt
        total = int(svc.metrics.counter("events").value)
        flushes = int(svc.metrics.counter("flushes").value)
        print(f"[serve_counts] ingested {total} events for "
              f"{len(svc.tenants)} tenants across {len(svc.planes)} planes "
              f"in {dt:.2f}s ({total/dt/1e6:.2f} M events/s, "
              f"{flushes} flushes, device rings donated "
              f"end-to-end — no host read-back)")

        # per-plane health straight off the registry: ring occupancy high-water
        # (how close each plane came to auto-flush pressure) and event-time
        # watermark lag for the windowed tenants
        for plane in svc.planes:
            fill = svc.metrics.gauge("ring_fill", plane=plane.label)
            cap = len(plane.names) * svc.queue_capacity
            n_ev = svc.metrics.counter("plane_events", plane=plane.label)
            line = (f"[serve_counts] plane {plane.label}: "
                    f"{int(n_ev.value)}"
                    f" events, ring high-water {int(fill.high_water)}/{cap}")
            if isinstance(plane, WindowPlane):
                lags = [int(svc.metrics.gauge("watermark_lag",
                                              plane=plane.label,
                                              tenant=n).value)
                        for n in plane.names]
                line += f", watermark lag {lags} intervals"
            print(line)

        # tier occupancy + swap traffic (tiering on): the hot/cold split per
        # plane and how many promotions/demotions/spills the stream forced
        for label, occ in svc.tier_occupancy().items():
            promos = int(svc.metrics.counter("tier_promotions",
                                             plane=label).value)
            demos = int(svc.metrics.counter("tier_demotions",
                                            plane=label).value)
            spills = int(svc.metrics.counter("tier_spill_events",
                                             plane=label).value)
            sbytes = int(svc.metrics.counter("tier_spill_bytes",
                                             plane=label).value)
            print(f"[serve_counts] tier {label}: {occ['hot']} hot / "
                  f"{occ['cold']} cold tenants, {promos} promotions, "
                  f"{demos} demotions, {spills} spills ({sbytes} bytes)")

        # every tenant's hot keys answered by one fused query launch per plane
        probes = np.stack(
            [np.arange(8, dtype=np.uint32) + t * 1_000_000
             for t in range(args.tenants)]
            # metrics x2 + trending + emb
            + [np.arange(8, dtype=np.uint32)] * 4)
        t0 = time.perf_counter()
        counts = jax.block_until_ready(svc.query_all(probes))
        dt_q = time.perf_counter() - t0
        phases["query_all"] = dt_q
        for name in names[:2] + ["metrics_qps"]:
            print(f"[serve_counts] {name} hot-key counts: "
                  f"{[round(float(x), 1) for x in np.asarray(counts[name])]}")
        # one fused launch per plane — windowed planes included: every
        # windowed tenant rides ONE row-stacked window query, not one
        # bucket-fused launch each
        launches = len(svc.planes)
        print(f"[serve_counts] served {len(svc.tenants)} tenants x "
              f"{probes.shape[1]} probes in {launches} fused launches "
              f"({dt_q*1e3:.1f} ms)")

        # heavy hitters straight off the tracker: refreshed by the same fused
        # launch that landed each flush, estimates exactly the query answers
        t0 = time.perf_counter()
        hot, est = svc.topk(names[0], 5)
        phases["topk"] = time.perf_counter() - t0
        print(f"[serve_counts] {names[0]} top-5 heavy hitters (tracker-fed): "
              f"{[(int(k), round(float(v))) for k, v in zip(hot, est)]}")

        # tracker-fed admission: hot ids map to private rows, cold ids share
        # the fallback space; decisions refreshed by every flush epoch
        ids = np.arange(32, dtype=np.uint32)
        t0 = time.perf_counter()
        rows, admitted = svc.admit("emb_ids", ids)
        n_adm = int(np.asarray(admitted).sum())
        phases["admit"] = time.perf_counter() - t0
        print(f"[serve_counts] admission plane: {n_adm}/{len(ids)} probe ids "
              f"admitted to private rows (threshold {aspec.threshold}, "
              f"{aspec.table_rows} private + {aspec.n_fallback} shared rows)")

        # the time-aware tenant: watermark epoch + lazy decay at query time
        t0 = time.perf_counter()
        est_w = np.asarray(svc.query("trending", np.arange(8), n_buckets=5))
        est_d = np.asarray(svc.query("trending", np.arange(8), gamma=0.8))
        phases["window_query"] = time.perf_counter() - t0
        print(f"[serve_counts] trending (last 5 of 8 x 60s buckets, watermark "
              f"epoch {svc.epoch_of('trending')}): "
              f"{[round(float(x)) for x in est_w]}")
        print(f"[serve_counts] trending lazy-decayed (gamma=0.8/interval):    "
              f"{[round(float(x)) for x in est_d]}")

        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory() as d:
            svc.snapshot(d, step=1)
            svc2 = CountService.restore(d)
            probe = np.arange(16, dtype=np.uint32)
            same = all(
                bool((np.asarray(svc.query(n, probe))
                      == np.asarray(svc2.query(n, probe))).all())
                for n in svc.tenants)
            print(f"[serve_counts] snapshot/restore roundtrip: queries match="
                  f"{same}, tenants={len(svc2.tenants)}, planes="
                  f"{len(svc2.planes)}, stats={svc2.stats}")
            del svc2
        if not same:
            raise RuntimeError("snapshot/restore round-trip changed query "
                               "answers")
        phases["snapshot_roundtrip"] = time.perf_counter() - t0

        # accuracy SLO probe: the exact shadow slice scored by frequency decile
        # (decile 0 = coldest keys; the paper's ARE-by-decile evaluation as a
        # live metric).  record() also lands the deciles in the registry.
        t0 = time.perf_counter()
        ares = slo_probe.record(svc)
        phases["accuracy_probe"] = time.perf_counter() - t0
        for tenant in sorted(ares)[:3]:
            print(f"[serve_counts] {tenant} ARE by decile (cold->hot, "
                  f"{len(slo_probe.counts[tenant])} shadowed keys): "
                  f"{[round(v, 3) for v in ares[tenant]]}")

        disp = {k: v for k, v in svc.metrics.snapshot()["counters"].items()
                if k.startswith("dispatch")}
        print(f"[serve_counts] dispatch tallies: {disp}")

        if args.metrics_out:
            obs.write_prometheus(args.metrics_out, svc.metrics)
            print(f"[serve_counts] wrote Prometheus exposition -> "
                  f"{args.metrics_out}")
        print("[serve_counts] phase wall times (s): "
              + ", ".join(f"{k} {v:.3f}" for k, v in phases.items()))
        if args.trace_out:
            print(f"[serve_counts] jax.profiler trace (cms.* host spans "
                  f"beside the device ops) -> {args.trace_out}")
        return ServeRun(svc=svc, tenants=names,
                        metrics_events=np.concatenate(metrics_events)
                        if metrics_events else np.zeros(0, np.uint32),
                        phases=phases, ares=ares)


if __name__ == "__main__":
    main()
