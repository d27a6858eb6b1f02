"""Query-plane benchmarks: fused multi-tenant query + in-kernel window reduce.

Two questions, mirroring the read path's two claims (the duals of
bench_window's ingest claims):

  1. TENANT FUSION — does one `fused_query_pallas` launch gridded
     (tenant, key-chunk) beat a Python loop of per-tenant `query_pallas`
     launches?  Same tables, same probes, same interpret-mode backend;
     outputs are asserted bit-identical before timing is reported.  The
     acceptance bar is >= 2x at T >= 8 (launch amortization, exactly the
     win the fused ingest kernel demonstrated).

  2. WINDOW REDUCTION — does the (key-chunk, bucket) kernel with the
     weighted sum reduction done in-kernel beat the vmapped jnp path
     (B per-bucket queries + host-side weighted reduce)?  Decay weights
     gamma^age ride along in both paths, so this also prices lazy decay.

    PYTHONPATH=src python -m benchmarks.bench_query [--quick]
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.common import timer
from repro.core import CMLS16, SketchSpec
from repro.core import sketch as sk
from repro.kernels import ops
from repro.kernels.sketch import (fused_query_pallas, query_pallas,
                                  window_query_pallas)

METHODOLOGY = {
    "tenant_fusion": "T pre-built (d, w) tables stacked (T, d, w), one "
                     "shared probe set of N keys per tenant; fused = one "
                     "fused_query_pallas launch gridded (tenant, chunk); "
                     "loop = Python loop of T query_pallas launches; "
                     "interpret-mode Pallas on CPU, timer = 1 warmup + 3 "
                     "iters, block_until_ready.  Outputs asserted "
                     "bit-identical before timing.  N = 1024 keys (one "
                     "kernel chunk) models the serving regime where "
                     "per-launch overhead dominates; the larger-batch "
                     "point (T=8, N=2048) records how the advantage "
                     "shrinks as compute amortizes dispatch.",
    "window_reduce": "bucket ring of B (d, w) tables, N probe keys, "
                     "gamma^age decay weights; kernel = one "
                     "window_query_pallas launch gridded (chunk, bucket) "
                     "with the weighted sum in-kernel; jnp = vmapped "
                     "per-bucket query + weighted reduce (the "
                     "pre-refactor path), jitted end-to-end so the "
                     "comparison is compiled-vs-kernel, not tracing "
                     "overhead.  Same timer discipline; outputs match "
                     "within float tolerance.",
}


def _tables(spec, t, seed):
    rng = np.random.default_rng(seed)
    tabs = []
    for i in range(t):
        keys = jnp.asarray((rng.zipf(1.3, 4000) % 3000).astype(np.uint32))
        tabs.append(sk.update_batched(sk.init(spec), keys,
                                      jax.random.PRNGKey(seed + i)).table)
    return jnp.stack(tabs)


def _fusion_rows(quick: bool):
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    seeds = ops._seeds_tuple(spec)
    rows = []
    points = [(2, 1024), (8, 1024)] if quick else \
        [(2, 1024), (8, 1024), (16, 1024), (8, 2048)]
    for t, n in points:
        tables = _tables(spec, t, seed=t)
        probe = jnp.asarray((np.random.default_rng(n).zipf(1.3, n) % 3000)
                            .astype(np.uint32))
        probes = jnp.broadcast_to(probe[None], (t, n))

        def fused(tb, k):
            return fused_query_pallas(tb, k, seeds=seeds, width=spec.width,
                                      counter=spec.counter,
                                      interpret=not ops.on_tpu())

        def loop(tb, k):
            return jnp.stack([
                query_pallas(tb[i], k[i], seeds=seeds, width=spec.width,
                             counter=spec.counter,
                             interpret=not ops.on_tpu())
                for i in range(t)])

        t_fused, out_f = timer(fused, tables, probes)
        t_loop, out_l = timer(loop, tables, probes)
        assert (np.asarray(out_f) == np.asarray(out_l)).all(), \
            "fused and per-tenant query loop disagree"
        rows += [
            {"name": f"query/fused_T{t}_N{n}",
             "us_per_call": round(t_fused * 1e6),
             "derived": f"{t * n} probes"},
            {"name": f"query/loop_T{t}_N{n}",
             "us_per_call": round(t_loop * 1e6),
             "derived": f"speedup_x{t_loop / t_fused:.2f}"},
        ]
    return rows


def _window_rows(quick: bool):
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    seeds = ops._seeds_tuple(spec)
    rows = []
    points = [(4, 1024)] if quick else [(4, 1024), (8, 2048)]
    for b, n in points:
        tables = _tables(spec, b, seed=100 + b)
        probe = jnp.asarray((np.random.default_rng(b).zipf(1.3, n) % 3000)
                            .astype(np.uint32))
        weights = jnp.float32(0.9) ** jnp.arange(b, dtype=jnp.float32)

        def kernel(tb, k, w):
            return window_query_pallas(tb, k, w, seeds=seeds,
                                       width=spec.width, counter=spec.counter,
                                       mode="sum",
                                       interpret=not ops.on_tpu())

        @jax.jit
        def jnp_path(tb, k, w):
            return ops.window_query_tables(tb, spec, k, w, mode="sum",
                                           engine="jnp")

        t_k, out_k = timer(kernel, tables, probe, weights)
        t_j, out_j = timer(jnp_path, tables, probe, weights)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j),
                                   rtol=1e-5, atol=1e-5)
        rows += [
            {"name": f"window_query/kernel_B{b}_N{n}",
             "us_per_call": round(t_k * 1e6),
             "derived": f"{b} buckets in-kernel"},
            {"name": f"window_query/jnp_B{b}_N{n}",
             "us_per_call": round(t_j * 1e6),
             "derived": f"speedup_x{t_j / t_k:.2f}"},
        ]
    return rows


def run(quick: bool = False) -> list[dict]:
    rows = _fusion_rows(quick) + _window_rows(quick)
    os.makedirs("results", exist_ok=True)
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    methodology = dict(METHODOLOGY, **common.mode_methodology())
    methodology["cell_format"] = {
        "unpacked": common.format_methodology(spec),
        "packed": common.format_methodology(
            dataclasses.replace(spec, packed=True)),
    }
    with open("results/bench_query.json", "w") as f:
        json.dump({"methodology": methodology, "rows": rows}, f, indent=1)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    from benchmarks.common import emit
    emit(run(quick=args.quick))
