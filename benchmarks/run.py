"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]
    PYTHONPATH=src python -m benchmarks.run --suites bench_ingest,bench_topk

Prints ``name,us_per_call,derived`` CSV (required format) and mirrors the
rows into results/benchmarks.json.  --suites selects a comma-separated
subset by module name (``bench_ingest``) or display name
(``ingest_plane``) — what CI's bench-smoke job and local pre-commit runs
use to target the regression-gated suites instead of paying for all of
them.  The kernels' mode follows the platform (interpret mode off-TPU),
and every suite records the platform, device kind and device count in
its JSON methodology block.

Every invocation is observed through `repro.obs`:

  * each suite runs under `ops.audit_scope()`, so the results JSON
    carries a `metrics` section — per-suite dispatch tallies — alongside
    the timed rows;
  * a fixed-seed SLO probe workload (a CountService with a full-rate
    exact shadow counter) runs after the suites and scores serving
    accuracy by frequency decile; the deciles land in
    results/accuracy.json for `check_regression.py` to diff against the
    committed envelope in benchmarks/baselines/accuracy.json;
  * a per-cell-format probe (packed cms32/log16/log8 at one constant
    byte budget, same fixed-seed stream) adds fmt_* pseudo-tenants to
    that envelope, gating the packed formats' accuracy per decile;
  * the registry exports as results/metrics.prom (Prometheus text
    exposition) — an artifact CI's bench-smoke job uploads.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks import (bench_are_counts, bench_batched_divergence,
                        bench_damped_update, bench_ingest, bench_pmi,
                        bench_query, bench_serve, bench_throughput,
                        bench_tiered, bench_topk, bench_window)
from benchmarks.common import emit, mode_methodology
from repro import obs
from repro.launch.cache import enable_compile_cache
from repro.kernels import ops

SUITES = [
    ("fig1_are_counts", bench_are_counts.run),
    ("fig2_fig3_pmi", bench_pmi.run),
    ("throughput", bench_throughput.run),
    ("batched_divergence", bench_batched_divergence.run),
    ("paper_next_steps", bench_damped_update.run),
    ("streaming_window", bench_window.run),
    ("query_plane", bench_query.run),
    ("ingest_plane", bench_ingest.run),
    ("topk_plane", bench_topk.run),
    ("tiered_plane", bench_tiered.run),
    ("serve_path", bench_serve.run),
]

SLO_SEED = 0
SLO_TENANT = "slo"
# Byte budget for the per-format accuracy probe (packed storage, exact
# from_memory sizing) — small enough to stress collisions so the decile
# envelope actually separates the formats.
FMT_BUDGET = 65_536


def _aliases(name: str, fn) -> set[str]:
    """A suite answers to its display name and its module name."""
    return {name, fn.__module__.split(".")[-1]}


def _select(args) -> list:
    wanted = set()
    if args.suite:
        wanted.add(args.suite)
    if args.suites:
        wanted.update(s.strip() for s in args.suites.split(",") if s.strip())
    if not wanted:
        return SUITES
    known = set().union(*(_aliases(n, f) for n, f in SUITES))
    unknown = wanted - known
    if unknown:
        raise SystemExit(f"unknown suite(s) {sorted(unknown)}; "
                         f"known: {sorted(known)}")
    return [(n, f) for n, f in SUITES if _aliases(n, f) & wanted]


def slo_probe_run(registry: obs.MetricsRegistry) -> dict[str, list[float]]:
    """Fixed-seed accuracy probe workload: a CountService fed a Zipfian
    stream with every key shadowed exactly (rate=1.0), scored by
    frequency decile.  Deterministic given SLO_SEED — both the stream and
    the sketch's row hashes — and deliberately NOT scaled by --quick, so
    every run (CI quick mode, local full mode, the baseline refresh)
    scores the identical workload and the committed envelope is a tight
    per-decile bound, not a statistical one."""
    from repro.core import CMLS16, SketchSpec
    from repro.stream import CountService

    spec = SketchSpec(width=2048, depth=2, counter=CMLS16)
    probe = obs.AccuracyProbe(rate=1.0, capacity=8192)
    svc = CountService(spec, tenants=(SLO_TENANT,), queue_capacity=4096,
                       seed=SLO_SEED, metrics=registry, probe=probe)
    rng = np.random.default_rng(SLO_SEED)
    for _ in range(8):
        keys = (rng.zipf(1.2, 2048) % 20_000).astype(np.uint32)
        svc.enqueue(SLO_TENANT, keys)
    svc.flush()
    return probe.record(svc)


def format_probe_run(registry: obs.MetricsRegistry
                     ) -> dict[str, list[float]]:
    """Per-cell-format accuracy probe: one packed CountService per format
    (cms32 / log16 / log8) at the same FMT_BUDGET table bytes, fed the
    identical fixed-seed Zipfian stream as the SLO probe.  The resulting
    pseudo-tenants (fmt_cms32, ...) land in results/accuracy.json next to
    the SLO tenant, so check_regression's per-decile envelope gates the
    packed formats' serving accuracy — including the constant-memory
    ordering the paper's Figure 1 claims (log16 no worse than cms32 at
    equal bytes on a skewed stream)."""
    from repro.core import CMLS8, CMLS16, CMS32, SketchSpec
    from repro.stream import CountService

    out: dict[str, list[float]] = {}
    for fmt, counter in (("cms32", CMS32), ("log16", CMLS16),
                         ("log8", CMLS8)):
        spec = SketchSpec.from_memory(FMT_BUDGET, depth=2, counter=counter,
                                      packed=True)
        probe = obs.AccuracyProbe(rate=1.0, capacity=8192)
        tenant = f"fmt_{fmt}"
        svc = CountService(spec, tenants=(tenant,), queue_capacity=4096,
                           seed=SLO_SEED, metrics=registry, probe=probe)
        rng = np.random.default_rng(SLO_SEED)
        for _ in range(8):
            keys = (rng.zipf(1.2, 2048) % 20_000).astype(np.uint32)
            svc.enqueue(tenant, keys)
        svc.flush()
        out.update(probe.record(svc))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced corpus + budget grid (CI-speed)")
    ap.add_argument("--suite", default=None,
                    help="run one suite by name")
    ap.add_argument("--suites", default=None,
                    help="comma-separated subset, by module or display "
                         "name (e.g. bench_ingest,bench_topk)")
    args = ap.parse_args()
    enable_compile_cache()

    registry = obs.MetricsRegistry()

    print("name,us_per_call,derived")
    all_rows = []
    dispatch: dict[str, dict[str, int]] = {}
    for name, fn in _select(args):
        t0 = time.time()
        with ops.audit_scope() as tally:
            rows = fn(quick=args.quick)
        dispatch[name] = dict(sorted(tally.items()))
        for op, n in tally.items():
            registry.counter("dispatch", suite=name, op=op).inc(n)
        emit(rows)
        all_rows += rows
        print(f"suite/{name},{round((time.time() - t0) * 1e6)},elapsed",
              flush=True)

    with ops.audit_scope() as tally:
        accuracy = slo_probe_run(registry)
    dispatch["slo_probe"] = dict(sorted(tally.items()))

    with ops.audit_scope() as tally:
        accuracy.update(format_probe_run(registry))
    dispatch["format_probe"] = dict(sorted(tally.items()))

    metrics = {
        "dispatch": dispatch,
        "accuracy_are_deciles": accuracy,
    }
    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.json", "w") as f:
        json.dump({"rows": all_rows, "metrics": metrics}, f, indent=1)
    with open("results/accuracy.json", "w") as f:
        json.dump({"methodology": dict(mode_methodology(), seed=SLO_SEED,
                                       format_probe_budget=FMT_BUDGET),
                   "are_by_decile": accuracy}, f, indent=1)
    obs.write_prometheus("results/metrics.prom", registry)
    for tenant, deciles in accuracy.items():
        print(f"accuracy/{tenant},,are_deciles="
              f"{'|'.join(f'{v:.4f}' for v in deciles)}")


if __name__ == "__main__":
    main()
