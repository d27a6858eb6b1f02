"""Streaming plane benchmarks: windowed accuracy + fused-ingest throughput.

Two questions, mirroring the subsystem's two claims:

  1. ACCURACY — are sliding-window estimates from the bucket ring as good
     as a single CML sketch built from ONLY the window's events (the
     brute-force recount)?  We stream R rotation intervals of a Zipfian
     corpus, query the last W buckets, and compare ARE against exact
     recounts of those W intervals, alongside the recount-sketch ARE as
     the envelope.

  2. THROUGHPUT — does the fused (tenant, key-chunk) kernel beat a Python
     loop of per-tenant `update_pallas` launches?  Same pre-deduplicated
     inputs, same interpret-mode backend, timed with warmup; the win is
     launch amortization, which is exactly what production multi-tenant
     ingest pays for.  Methodology fields ride along in the JSON mirror
     (results/bench_window.json).

    PYTHONPATH=src python -m benchmarks.bench_window [--quick]
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.common import timer
from repro.core import CMLS16, SketchSpec
from repro.core import sketch as sk
from repro.core.hashing import make_row_seeds
from repro.kernels import ops
from repro.kernels.sketch import fused_update_pallas, update_pallas
from repro.stream import WindowSpec, window_init, window_query, window_rotate, \
    window_update

METHODOLOGY = {
    "accuracy": "R rotation intervals of zipf(1.3) events; window = last W "
                "buckets queried in sum mode; ARE over keys with true "
                "count >= 1 vs exact recount of the W intervals; envelope = "
                "ARE of a fresh single sketch (same spec) fed only those "
                "events.",
    "throughput": "identical pre-deduplicated (T, N) inputs; fused = one "
                  "fused_update_pallas launch gridded (tenant, chunk); loop "
                  "= Python loop of T single-tenant update_pallas launches; "
                  "interpret-mode Pallas on CPU, timer = 1 warmup + 3 iters, "
                  "block_until_ready.  Per-tenant microbatch N = 1024 keys "
                  "(one kernel chunk): the multi-tenant serving regime the "
                  "fusion targets, where per-launch overhead dominates and "
                  "launch amortization is the win.  A larger-batch point "
                  "(T=8, N=2048) records how the advantage shrinks as "
                  "per-launch compute amortizes dispatch instead.",
}


def _zipf(rng, n, vocab):
    return (rng.zipf(1.3, n) % vocab).astype(np.uint32)


def _accuracy_rows(quick: bool):
    rng = np.random.default_rng(0)
    spec = SketchSpec(width=2048 if quick else 8192, depth=4, counter=CMLS16)
    buckets, window = 8, 5
    per_rot = 2000 if quick else 20_000
    vocab = 1200 if quick else 8000
    win = window_init(WindowSpec(sketch=spec, buckets=buckets))
    upd = jax.jit(window_update)
    rot = jax.jit(window_rotate)
    key = jax.random.PRNGKey(0)
    rotations = []
    for r in range(12):
        ev = _zipf(rng, per_rot, vocab)
        rotations.append(ev)
        key, k = jax.random.split(key)
        win = upd(win, jnp.asarray(ev), k)
        if r < 11:
            win = rot(win)

    window_events = np.concatenate(rotations[-window:])
    uniq, true = np.unique(window_events, return_counts=True)
    est = np.asarray(window_query(win, jnp.asarray(uniq), n_buckets=window))
    are_window = float(np.mean(np.abs(est - true) / true))

    # envelope: one sketch fed exactly the window's events
    key, k = jax.random.split(key)
    ref = sk.update_batched(sk.init(spec), jnp.asarray(window_events), k)
    est_ref = np.asarray(sk.query(ref, jnp.asarray(uniq)))
    are_ref = float(np.mean(np.abs(est_ref - true) / true))

    # staleness: events that only exist in expired buckets must not count
    old = np.setdiff1d(np.concatenate(rotations[:3]), window_events)
    leak = 0.0
    if old.size:
        leak = float(np.max(np.asarray(window_query(
            win, jnp.asarray(old.astype(np.uint32)), n_buckets=window))))
    return [
        {"name": "window/are_sliding_window", "derived": round(are_window, 5)},
        {"name": "window/are_recount_envelope", "derived": round(are_ref, 5)},
        {"name": "window/expired_leak_max", "derived": round(leak, 3)},
    ]


def _throughput_rows(quick: bool):
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    seeds = tuple(int(x) for x in make_row_seeds(spec.seed, spec.depth))
    rows = []
    points = [(2, 1024), (8, 1024)] if quick else \
        [(2, 1024), (8, 1024), (16, 1024), (8, 2048)]
    for t, n in points:
        rng = np.random.default_rng(t)
        keys = jnp.asarray(np.stack([_zipf(rng, n, 4000) for _ in range(t)]))
        sorted_keys, mult = jax.vmap(sk.dedup_weighted)(
            keys, jnp.ones(keys.shape, jnp.float32))
        unif = jax.random.uniform(jax.random.PRNGKey(t), sorted_keys.shape)
        tables = jnp.zeros((t, spec.depth, spec.width), spec.counter.dtype)

        def fused(tb, k, m, u):
            return fused_update_pallas(tb, k, m, u, seeds=seeds,
                                       width=spec.width, counter=spec.counter,
                                       interpret=not ops.on_tpu())

        def loop(tb, k, m, u):
            return jnp.stack([
                update_pallas(tb[i], k[i], m[i], u[i], seeds=seeds,
                              width=spec.width, counter=spec.counter,
                              interpret=not ops.on_tpu())
                for i in range(t)])

        t_fused, out_f = timer(fused, tables, sorted_keys, mult, unif)
        t_loop, out_l = timer(loop, tables, sorted_keys, mult, unif)
        assert (np.asarray(out_f) == np.asarray(out_l)).all(), \
            "fused and per-tenant loop disagree"
        speedup = t_loop / t_fused
        rows += [
            {"name": f"ingest/fused_T{t}_N{n}",
             "us_per_call": round(t_fused * 1e6),
             "derived": f"{t * n} keys"},
            {"name": f"ingest/loop_T{t}_N{n}",
             "us_per_call": round(t_loop * 1e6),
             "derived": f"speedup_x{speedup:.2f}"},
        ]
    return rows


def run(quick: bool = False) -> list[dict]:
    rows = _accuracy_rows(quick) + _throughput_rows(quick)
    os.makedirs("results", exist_ok=True)
    methodology = dict(METHODOLOGY, **common.mode_methodology())
    with open("results/bench_window.json", "w") as f:
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
