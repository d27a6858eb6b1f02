"""Smoke run of the counting service's serve path on a TPU.

    python chip_smoke.py              # one chip: CountService serve path
    python chip_smoke.py --chips 4    # four chips: the key-routed plane only

One chip: drives `repro.launch.serve_counts.main` at a deployment state
size — 17 tenants x (4 x 2^22) CMLS16 tables (32 MiB each) in the wide
plane, an 8-bucket window leaf of the same tables (256 MiB) and the
1024 x 2 CMS32 metrics plane, about 0.8 GiB resident — then checks that
no "auto" dispatch reached a Pallas kernel, that `topk` estimates equal
`query` answers for the same keys (plain and windowed tenants), and that
no CMS32 estimate falls below its key's exact count on the same seeded
stream.  The snapshot round-trip inside `main` raises if it changes an
answer.

Four chips: counting through `CountService`'s key-routed plane
(`CountService(mesh=...)`) on a 4-device mesh, per-shard CMS32 tables at
w=2^22, d=4: `enqueue_many`, queue-pressure and explicit flush epochs
(each one `_routed_epoch` program: local dedup, `all_to_all` to each
key's owner, in-place update), `query` and `topk`, checked against exact
counts, against one device's plain plane over the same stream, and
against the routing counters (every event routed, none dropped).

Earlier lines report the engine each op took, compile seconds, the wall
time of each phase, `peak_bytes_in_use` and ARE by decile.  The last line
is one JSON object, `{"ok": ..., "device": {"platform", "kind",
"count"}}`.  The exit code is 0 only when every check passed on a TPU.
Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SERVE_ARGS = ["--tenants", "16", "--width", "4194304", "--depth", "4",
              "--batch", "4096", "--batches", "20"]
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# four-chip phase: per-shard CMS32 tables, STEPS enqueues of BATCH keys per
# shard drawn from a Zipf stream over KEY_SPACE ids, top-K heavy hitters
ROUTED_WIDTH = 1 << 22
ROUTED_DEPTH = 4
STEPS = 4
BATCH = 65_536
KEY_SPACE = 1 << 20
TOP_K = 16
SEED = 0


class CompileClock:
    """Sums the backend compile time JAX reports through its monitoring
    events (a program served from the persistent cache reports none)."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.programs += 1


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def serve_phase() -> None:
    """The one-chip serve path through `serve_counts.main`."""
    from repro.kernels import ops
    from repro.launch import serve_counts

    scope = ops.audit_scope()
    with scope:
        run = serve_counts.main(SERVE_ARGS)
    engines = {f"{op}/{eng}": n for (op, eng), n in sorted(scope.engines.items())}
    log(f"engine per op (dispatches): {engines}")
    kernel_ops = sorted(k for k in engines if k.endswith("/kernel"))
    check(not kernel_ops, f"auto dispatches reached Pallas kernels: {kernel_ops}")

    svc = run.svc
    t0 = time.perf_counter()
    for name in (run.tenants[0], "trending"):
        keys, est = svc.topk(name)
        check(len(keys) > 0, f"{name}: empty top-k")
        answers = np.asarray(svc.query(name, keys))
        check(np.array_equal(np.asarray(est), answers),
              f"{name}: top-k estimates {np.asarray(est).tolist()} differ "
              f"from query answers {answers.tolist()}")
        log(f"{name}: top-{len(keys)} estimates equal query answers")
    uniq, exact = np.unique(run.metrics_events, return_counts=True)
    est = np.asarray(svc.query("metrics_qps", uniq))
    below = int((est < exact).sum())
    check(below == 0, f"metrics_qps: {below} CMS32 estimates below the "
                      f"exact count")
    log(f"metrics_qps: {uniq.size} keys, every CMS32 estimate >= exact "
        f"count (max over-count {float((est - exact).max())})")
    phases = dict(run.phases, checks=time.perf_counter() - t0)
    log(f"phase wall seconds: {json.dumps(phases)}")
    for tenant in sorted(run.ares):
        log(f"ARE by decile {tenant}: {json.dumps(run.ares[tenant])}")


def routed_phase(n_chips: int) -> None:
    """Key-routed counting through `CountService`'s routed plane over
    `n_chips` devices, against exact counts and against one device's plain
    plane over the same stream."""
    import jax
    from jax.sharding import AxisType, Mesh

    from repro.core import CMS32, SketchSpec
    from repro.stream import CountService

    n = len(jax.devices())
    check(n == n_chips, f"need {n_chips} devices, JAX sees {n}")
    mesh = Mesh(np.asarray(jax.devices()), ("keys",),
                axis_types=(AxisType.Auto,))
    spec = SketchSpec(width=ROUTED_WIDTH, depth=ROUTED_DEPTH, counter=CMS32)

    rng = np.random.default_rng(SEED)
    stream = (rng.zipf(1.2, (STEPS, n * BATCH)) % KEY_SPACE).astype(np.uint32)
    uniq, exact = np.unique(stream, return_counts=True)
    order = np.argsort(-exact, kind="stable")
    check(exact[order[TOP_K - 1]] > exact[order[TOP_K]],
          "exact top-k has a tie at its boundary; pick another seed")
    true_top = set(uniq[order[:TOP_K]].tolist())
    phases = {}

    def count(svc) -> None:
        svc.add_tenant("keys", spec=spec)
        for step in range(STEPS):
            svc.enqueue_many({"keys": stream[step]})
        svc.flush()
        jax.block_until_ready(svc.planes[0].tables)

    t0 = time.perf_counter()
    routed = CountService(queue_capacity=2 * n * BATCH, seed=SEED,
                          track_top=TOP_K, mesh=mesh)
    count(routed)
    phases["routed_ingest"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    est = np.asarray(routed.query("keys", uniq))
    phases["routed_query"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    top_keys, top_est = routed.topk("keys")
    phases["routed_topk"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    one = CountService(queue_capacity=2 * n * BATCH, seed=SEED,
                       track_top=TOP_K)
    count(one)
    single_est = np.asarray(one.query("keys", uniq))
    phases["single_device_reference"] = time.perf_counter() - t0

    m = routed.metrics
    routed_keys = m.counter("routed_keys", plane="p0").value
    dropped = m.counter("routed_dropped", plane="p0").value
    skew = m.gauge("routed_shard_keys_max", plane="p0").value
    over_routed = est - exact
    over_single = single_est - exact
    log(f"{STEPS} enqueues of {n * BATCH} keys over {n} shards, "
        f"{uniq.size} distinct; routed over-count sum "
        f"{float(over_routed.sum())} max {float(over_routed.max())}, "
        f"single-device sum {float(over_single.sum())} max "
        f"{float(over_single.max())}")
    log(f"ARE routed {float(np.mean(np.abs(over_routed) / exact))}, "
        f"single-device {float(np.mean(np.abs(over_single) / exact))}; "
        f"routed_keys {routed_keys}, routed_dropped {dropped}, busiest "
        f"shard over the mean {skew}")
    check(routed_keys == stream.size, "routed_keys differs from the events")
    check(dropped == 0, f"the exchange dropped {dropped} events")
    check((over_routed >= 0).all(), "a routed estimate is below its exact "
                                    "count")
    check((over_single >= 0).all(), "a single-device estimate is below its "
                                    "exact count")
    check(over_routed.sum() <= over_single.sum(),
          "routed tables over-count more than one device's table")
    check(set(top_keys.tolist()) == true_top,
          "routed top-k differs from the exact heavy hitters")
    single_top = set(uniq[np.argsort(-single_est, kind="stable")[:TOP_K]]
                     .tolist())
    check(single_top == true_top,
          "single-device top-k differs from the exact heavy hitters")
    pos = {k: i for i, k in enumerate(uniq.tolist())}
    check(all(e == est[pos[k]] for k, e in zip(top_keys.tolist(),
                                                top_est.tolist())),
          "routed top-k estimates differ from the query answers")
    log(f"routed top-{TOP_K} equals the exact heavy hitters: "
        f"{sorted(true_top)}")
    log(f"phase wall seconds: {json.dumps(phases)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the key-routed plane's phase")
    args = ap.parse_args(argv)
    result = {"ok": False}
    try:
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.launch.cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        import jax

        devs = jax.devices()
        result["device"] = {"platform": devs[0].platform,
                            "kind": devs[0].device_kind, "count": len(devs)}
        check(devs[0].platform == "tpu",
              f"no TPU: JAX's default platform is {devs[0].platform}")
        log(f"devices: {result['device']}, compile cache {cache_dir}")
        clock = CompileClock()
        jax.monitoring.register_event_duration_secs_listener(clock)
        t0 = time.perf_counter()
        if args.chips == 1:
            serve_phase()
        else:
            routed_phase(args.chips)
        log(f"wall {time.perf_counter() - t0} s, backend compile "
            f"{clock.seconds} s over {clock.programs} programs")
        for d in devs:
            stats = d.memory_stats() or {}
            log(f"{d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}, "
                f"bytes_limit {stats.get('bytes_limit')}")
        result["ok"] = True
    except Exception as e:  # the boundary: report any failure, exit non-zero
        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
