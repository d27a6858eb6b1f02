"""Device-resident ingest plane: scatter-append kernel, spec-bucketed
planes, watermark plumbing, and the v2 snapshot schema."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import CMLS8, CMLS16, CMS32, SketchSpec
from repro.core import sketch as sk
from repro.kernels import ops
from repro.stream import (CountService, WindowSpec, window_advance_steps,
                          window_advance_to, window_init, window_query,
                          window_rotate, window_update)
from repro.train import checkpoint


def _zipf(n, vocab, seed=0):
    return (np.random.default_rng(seed).zipf(1.3, n) % vocab).astype(np.uint32)


# --------------------------------------------------------------------------
# queue_append kernel vs a host reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["kernel", "xla"])
def test_queue_append_matches_host_reference(engine):
    """Random ragged multi-row appends accumulate exactly like host slices,
    on both the Pallas kernel and its XLA reference engine (exercising the
    dense whole-plane path and the row-indirected path)."""
    rng = np.random.default_rng(7)
    t, cap = 5, 4096
    queue = ops.queue_init(t, cap)
    ref = np.zeros((t, ops.ring_width(cap)), np.uint32)
    fill = np.zeros(t, np.int64)
    for it in range(25):
        r = t if it % 3 == 0 else int(rng.integers(1, t + 1))
        rows = np.arange(t) if r == t else rng.choice(t, r, replace=False)
        batches = []
        for row in rows:
            n = int(rng.integers(1, 1200))
            if fill[row] + n > cap:
                fill[row] = 0  # host mimic of a flush reset
            k = rng.integers(1, 2**32, n, dtype=np.uint32)
            ref[row, fill[row]:fill[row] + n] = k
            batches.append(k)
        n_pad = ops.CHUNK * -(-max(b.size for b in batches) // ops.CHUNK)
        keys = np.zeros((r, n_pad), np.uint32)
        for i, b in enumerate(batches):
            keys[i, :b.size] = b
        queue = ops.queue_append(queue, jnp.asarray(keys),
                                 rows.astype(np.int32),
                                 fill[rows].astype(np.int32),
                                 np.asarray([b.size for b in batches],
                                            np.int32), engine=engine)
        for row, b in zip(rows, batches):
            fill[row] += b.size
    got = np.asarray(queue)
    for row in range(t):
        np.testing.assert_array_equal(got[row, :fill[row]],
                                      ref[row, :fill[row]])


def test_queue_append_kernel_and_xla_engines_bit_identical():
    """The Pallas scatter-append and its XLA reference agree on the WHOLE
    ring (stale slots included), for both the dense and row paths."""
    rng = np.random.default_rng(3)
    t, cap = 4, 2048
    qk = ops.queue_init(t, cap)
    qx = ops.queue_init(t, cap)
    fill = np.zeros(t, np.int64)
    for it in range(8):
        if it % 2 == 0:
            rows = np.arange(t)  # dense path
        else:
            rows = rng.choice(t, 2, replace=False)
        n = int(rng.integers(1, cap // 2))
        keys = rng.integers(1, 2**32, (len(rows), n), dtype=np.uint32)
        for row in rows:
            if fill[row] + n > cap:
                fill[row] = 0
        f = fill[rows].astype(np.int32)
        c = np.full(len(rows), n, np.int32)
        qk = ops.queue_append(qk, jnp.asarray(keys), rows.astype(np.int32),
                              f, c, engine="kernel")
        qx = ops.queue_append(qx, jnp.asarray(keys), rows.astype(np.int32),
                              f, c, engine="xla")
        for row in rows:
            fill[row] += n
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qx))


@pytest.mark.parametrize("engine", ["kernel", "xla"])
def test_queue_append_preserves_other_rows_and_prefix(engine):
    """The aliased ring only changes the appended span of the target row."""
    queue = ops.queue_init(3, 1024)
    queue = ops.queue_append(queue, jnp.full((1, ops.CHUNK), 7, jnp.uint32),
                             [1], [0], [100], engine=engine)
    before = np.asarray(queue).copy()
    queue = ops.queue_append(queue, jnp.full((1, ops.CHUNK), 9, jnp.uint32),
                             [1], [100], [50], engine=engine)
    after = np.asarray(queue)
    assert (after[1, 100:150] == 9).all()
    np.testing.assert_array_equal(after[0], before[0])
    np.testing.assert_array_equal(after[2], before[2])
    np.testing.assert_array_equal(after[1, :100], before[1, :100])
    np.testing.assert_array_equal(after[1, 150:], before[1, 150:])


def test_enqueue_flush_never_reads_ring_back():
    """enqueue -> flush with device->host transfers disallowed: the ring is
    device-resident end-to-end (the acceptance check bench_ingest also
    enforces)."""
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    svc = CountService(spec, tenants=("a", "b"), queue_capacity=2048)
    svc.flush()  # warm up compilation outside the guard
    with jax.transfer_guard_device_to_host("disallow"):
        svc.enqueue("a", _zipf(1500, 300, seed=1))
        svc.enqueue("b", _zipf(700, 300, seed=2))
        svc.flush()
    assert float(svc.query("a", [0])[0]) >= 0  # queries still work after


# --------------------------------------------------------------------------
# spec-bucketed planes: heterogeneous tenants in one service
# --------------------------------------------------------------------------

SPEC_A = SketchSpec(width=2048, depth=3, counter=CMLS16)
SPEC_B = SketchSpec(width=512, depth=2, counter=CMS32)


def _hetero_service(cap=1024, seed=0):
    svc = CountService(SPEC_A, tenants=("ads", "search"), queue_capacity=cap,
                       seed=seed)
    svc.add_tenant("metrics", spec=SPEC_B)
    svc.add_tenant("audit", spec=SPEC_B)
    return svc


def _single_spec_pair(cap=1024, seed=0):
    sa = CountService(SPEC_A, tenants=("ads", "search"), queue_capacity=cap,
                      seed=seed)
    sb = CountService(SPEC_B, tenants=("metrics", "audit"),
                      queue_capacity=cap, seed=seed)
    return sa, sb


STREAMS = {"ads": _zipf(3000, 300, seed=1),
           "search": _zipf(1200, 300, seed=2) + 10_000,
           "metrics": _zipf(2000, 200, seed=3),
           "audit": _zipf(800, 200, seed=4) + 5_000}


def test_hetero_service_bit_consistent_with_single_spec_services():
    """Two specs in ONE service == two single-spec services, bit for bit.

    Each plane flushes with its own fused launch and its own RNG lane, so
    the stacked updates must land exactly as in a dedicated service."""
    svc = _hetero_service()
    sa, sb = _single_spec_pair()
    for name, keys in STREAMS.items():
        for i in range(0, len(keys), 700):
            svc.enqueue(name, keys[i:i + 700])
            (sa if name in ("ads", "search") else sb).enqueue(
                name, keys[i:i + 700])
    probe = np.arange(256, dtype=np.uint32)
    got = svc.query_all(probe)
    assert set(got) == set(STREAMS)
    for name in ("ads", "search"):
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(sa.query(name, probe)))
    for name in ("metrics", "audit"):
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(sb.query(name, probe)))
    # query == query_all rows (per-plane fused launch vs T=1 launch)
    for name in STREAMS:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(svc.query(name, probe)))


def test_hetero_service_per_tenant_probe_rows():
    svc = _hetero_service()
    for name, keys in STREAMS.items():
        svc.enqueue(name, keys)
    probes = np.stack([np.arange(64, dtype=np.uint32) + 100 * i
                       for i in range(len(svc.tenants))])
    per = svc.query_all(probes)
    for i, name in enumerate(svc.tenants):
        np.testing.assert_array_equal(np.asarray(per[name]),
                                      np.asarray(svc.query(name, probes[i])))
    with pytest.raises(ValueError):
        svc.query_all(np.zeros((2, 8), np.uint32))


def test_hetero_service_snapshot_restore_roundtrip(tmp_path):
    svc = _hetero_service()
    for name, keys in STREAMS.items():
        svc.enqueue(name, keys)
    q_before = {n: np.asarray(svc.query(n, np.arange(64))) for n in STREAMS}
    svc.enqueue("metrics", np.full(37, 123_456, np.uint32))  # queued residue
    events, flushes = svc.stats["events"], svc.stats["flushes"]
    svc.snapshot(str(tmp_path), step=3)

    svc2 = CountService.restore(str(tmp_path))
    assert svc2.tenants == svc.tenants
    assert svc2.spec == SPEC_A
    assert svc2.spec_of("audit") == SPEC_B
    # satellite: stats survive the round-trip (events/flushes not reset)
    assert svc2.stats == {"events": events, "flushes": flushes}
    for name in STREAMS:
        np.testing.assert_array_equal(q_before[name],
                                      np.asarray(svc2.query(name,
                                                            np.arange(64))))
    assert float(svc2.query("metrics", [123_456])[0]) >= 18


def test_restore_v1_single_plane_checkpoint(tmp_path):
    """The pre-plane manifest layout (v1: host queue, single spec) still
    restores: tables load directly, the persisted host queue replays into
    the device ring."""
    spec = SPEC_A
    tables = jnp.stack([sk.update_batched(sk.init(spec),
                                          jnp.asarray(_zipf(500, 100, seed=t)),
                                          jax.random.PRNGKey(t)).table
                        for t in range(2)])
    queue = np.zeros((2, 256), np.uint32)
    queue[1, :40] = 777
    fill = np.array([0, 40], np.int64)
    c = spec.counter
    meta = {"tenants": ["x", "y"], "queue_capacity": 256,
            "spec": {"width": spec.width, "depth": spec.depth,
                     "seed": spec.seed,
                     "counter": {"kind": c.kind, "base": c.base,
                                 "bits": c.bits}}}
    tree = {"tables": tables, "queue": jnp.asarray(queue),
            "fill": jnp.asarray(fill), "rng": jax.random.PRNGKey(5)}
    checkpoint.save(str(tmp_path), 11, tree, metadata=meta)

    svc = CountService.restore(str(tmp_path))
    assert svc.tenants == ["x", "y"]
    before = np.asarray(ops.query(sk.Sketch(table=tables[0], spec=spec),
                                  jnp.arange(50, dtype=jnp.uint32)))
    np.testing.assert_array_equal(before,
                                  np.asarray(svc.query("x", np.arange(50))))
    # the 40 replayed queue events land on flush
    assert float(svc.query("y", [777])[0]) >= 20


def test_add_tenant_requires_some_spec():
    svc = CountService(queue_capacity=64)
    with pytest.raises(ValueError):
        svc.add_tenant("nospec")
    svc.add_tenant("ok", spec=SPEC_B)
    svc.enqueue("ok", [1, 2, 3])
    assert float(svc.query("ok", [1])[0]) >= 1


# --------------------------------------------------------------------------
# key validation (no silent uint32 truncation)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("bad,exc", [
    ([1.5, 2.0], TypeError),
    (np.array([0.25]), TypeError),
    ([-1, 3], ValueError),
    ([1 << 32], ValueError),
    (np.array([5, -7], np.int64), ValueError),
])
def test_enqueue_and_query_reject_bad_keys(bad, exc):
    svc = CountService(SPEC_B, tenants=("t",), queue_capacity=64)
    with pytest.raises(exc):
        svc.enqueue("t", bad)
    with pytest.raises(exc):
        svc.query("t", bad)
    with pytest.raises(exc):
        svc.query_all(bad)
    assert svc.stats["events"] == 0  # rejected batches never count


def test_enqueue_accepts_plain_ints_and_uint32():
    svc = CountService(SPEC_B, tenants=("t",), queue_capacity=64)
    svc.enqueue("t", [1, 2, 2**32 - 1])
    svc.enqueue("t", np.asarray([3], np.uint32))
    assert svc.stats["events"] == 4


# --------------------------------------------------------------------------
# auto-flush under multi-tenant pressure
# --------------------------------------------------------------------------

def test_autoflush_multi_tenant_overflow_single_calls():
    """A single enqueue call larger than queue_capacity, for several
    tenants with pending residue: the auto-flush loop must spill ALL
    tenants' queues and lose nothing."""
    spec = SketchSpec(width=2048, depth=3, counter=CMLS16)
    svc = CountService(spec, tenants=("a", "b", "c"), queue_capacity=256)
    svc.enqueue("b", np.full(100, 5, np.uint32))   # residue below capacity
    svc.enqueue("c", np.full(30, 9, np.uint32))
    # 1000 > 256 forces repeated flushes mid-call; b/c residue rides along
    svc.enqueue("a", np.full(1000, 3, np.uint32))
    svc.enqueue("b", np.full(700, 5, np.uint32))
    assert svc.stats["events"] == 1830
    assert svc.stats["flushes"] >= 2
    est_a = float(svc.query("a", [3])[0])
    est_b = float(svc.query("b", [5])[0])
    est_c = float(svc.query("c", [9])[0])
    assert abs(est_a - 1000) / 1000 < 0.25
    assert abs(est_b - 800) / 800 < 0.25
    assert abs(est_c - 30) / 30 < 0.35


def test_enqueue_many_one_launch_and_overflow_fallback():
    spec = SketchSpec(width=2048, depth=2, counter=CMLS16)
    svc = CountService(spec, tenants=("a", "b"), queue_capacity=512)
    svc.add_tenant("m", spec=SPEC_B)
    svc.enqueue_many({"a": np.full(200, 1, np.uint32),
                      "b": np.full(300, 2, np.uint32),
                      "m": np.full(100, 3, np.uint32)})
    assert svc.stats["events"] == 600
    # overflowing batch falls back to the splitting enqueue loop
    svc.enqueue_many({"a": np.full(900, 1, np.uint32)})
    assert svc.stats["events"] == 1500
    assert abs(float(svc.query("a", [1])[0]) - 1100) / 1100 < 0.25
    assert abs(float(svc.query("b", [2])[0]) - 300) / 300 < 0.25
    assert abs(float(svc.query("m", [3])[0]) - 100) / 100 < 0.25


# --------------------------------------------------------------------------
# watermark plumbing: windowed tenants
# --------------------------------------------------------------------------

WSPEC = WindowSpec(sketch=SketchSpec(width=1024, depth=2, counter=CMLS16),
                   buckets=4, interval=60.0)


def test_windowed_tenant_matches_manual_window_ops():
    """Service-managed watermark rotation tracks the manual
    window_advance_to / window_update sequence: same epochs, same cursor,
    statistically matching estimates (the RNG lanes differ — the service
    draws uniforms over its padded queue slice — so the probabilistic
    counters agree in expectation, not bit for bit)."""
    svc = CountService(queue_capacity=8192, seed=0)
    svc.add_tenant("trend", window=WSPEC)
    manual = window_init(WSPEC)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(1)
    ts = 0.0
    for _ in range(10):
        ts += float(rng.exponential(40.0))
        ev = _zipf(600, 200, seed=int(ts * 1000) % 9973)
        svc.enqueue("trend", ev, ts=ts)
        svc.flush()
        manual = window_advance_to(manual, ts)
        key, k = jax.random.split(key)
        manual = window_update(manual, jnp.asarray(ev), k)
    probe = jnp.arange(1, 64, dtype=jnp.uint32)
    got = np.asarray(svc.query("trend", probe))
    want = np.asarray(window_query(manual, probe))
    assert svc.epoch_of("trend") == int(manual.epoch)
    from repro.stream.service import WindowPlane
    plane, row = svc._where["trend"]
    assert isinstance(plane, WindowPlane)
    assert int(plane.wins[row].cursor) == int(manual.cursor)
    # same live buckets -> same keys present/absent, close counts
    np.testing.assert_array_equal(got > 0, want > 0)
    live = want > 0
    assert np.mean(np.abs(got[live] - want[live]) /
                   np.maximum(want[live], 1)) < 0.2
    # windowed query kwargs forward (lazy decay in the fused kernel)
    got_d = np.asarray(svc.query("trend", probe, gamma=0.8))
    want_d = np.asarray(window_query(manual, probe, gamma=0.8))
    np.testing.assert_array_equal(got_d > 0, want_d > 0)


def test_windowed_tenant_boundary_flushes_into_own_bucket():
    """Events buffered in interval e must land in interval e's bucket even
    when the flush happens after the watermark has moved on."""
    svc = CountService(queue_capacity=8192)
    svc.add_tenant("trend", window=WSPEC)
    svc.enqueue("trend", np.full(50, 7, np.uint32), ts=10.0)    # epoch 0
    svc.enqueue("trend", np.full(20, 7, np.uint32), ts=70.0)    # epoch 1
    svc.enqueue("trend", np.full(10, 7, np.uint32), ts=130.0)   # epoch 2
    # last-1-bucket query sees only epoch 2's events
    est_now = float(svc.query("trend", [7], n_buckets=1)[0])
    est_all = float(svc.query("trend", [7])[0])
    assert abs(est_now - 10) / 10 < 0.35
    assert abs(est_all - 80) / 80 < 0.25
    # advancing past the whole ring expires everything
    svc.enqueue("trend", np.asarray([], np.uint32), ts=130.0 + 60.0 * 5)
    assert float(svc.query("trend", [7])[0]) == 0.0
    with pytest.raises(ValueError):  # non-monotone watermark still raises
        svc.enqueue("trend", [7], ts=1.0)


def test_windowed_tenant_snapshot_restore(tmp_path):
    svc = CountService(SPEC_A, tenants=("plain",), queue_capacity=4096)
    svc.add_tenant("trend", window=WSPEC)
    svc.enqueue("plain", _zipf(500, 100, seed=1))
    svc.enqueue("trend", np.full(40, 7, np.uint32), ts=10.0)
    svc.enqueue("trend", np.full(25, 7, np.uint32), ts=70.0)
    before = float(svc.query("trend", [7])[0])
    svc.snapshot(str(tmp_path), step=1)
    svc2 = CountService.restore(str(tmp_path))
    assert svc2.tenants == ["plain", "trend"]
    assert svc2.epoch_of("trend") == 1
    assert float(svc2.query("trend", [7])[0]) == before
    with pytest.raises(ValueError):
        svc2.epoch_of("plain")


def test_ts_on_plain_tenant_rejected():
    svc = CountService(SPEC_B, tenants=("t",), queue_capacity=64)
    with pytest.raises(ValueError):
        svc.enqueue("t", [1], ts=5.0)
    with pytest.raises(ValueError):
        svc.enqueue_many({"t": [1]}, ts=5.0)  # same contract as enqueue
    with pytest.raises(ValueError):
        svc.query("t", [1], gamma=0.9)


def test_restore_preserves_service_seed(tmp_path):
    """A restored service must keep drawing the same RNG stream as the
    uninterrupted original: identical post-restore ingest => identical
    tables."""
    svc = CountService(SPEC_A, tenants=("a",), queue_capacity=512, seed=7)
    svc.enqueue("a", _zipf(400, 100, seed=1))
    svc.flush()
    svc.snapshot(str(tmp_path), step=1)
    svc2 = CountService.restore(str(tmp_path))
    more = _zipf(900, 100, seed=2)
    svc.enqueue("a", more)
    svc2.enqueue("a", more)
    np.testing.assert_array_equal(np.asarray(svc.query("a", np.arange(64))),
                                  np.asarray(svc2.query("a",
                                                        np.arange(64))))


# --------------------------------------------------------------------------
# traced watermark advance (the sharded/windowed plumbing)
# --------------------------------------------------------------------------

def test_window_advance_steps_matches_rotate_loop():
    spec = WindowSpec(sketch=SketchSpec(width=512, depth=2, counter=CMLS8),
                      buckets=5)
    win = window_init(spec)
    key = jax.random.PRNGKey(0)
    for r in range(4):
        key, k = jax.random.split(key)
        win = window_update(win, jnp.asarray(_zipf(300, 80, seed=r)), k)
        win = window_rotate(win)
    for steps in range(0, 7):
        want = win
        for _ in range(steps):
            want = window_rotate(want)
        got = jax.jit(window_advance_steps)(win, jnp.asarray(steps))
        np.testing.assert_array_equal(np.asarray(got.tables),
                                      np.asarray(want.tables))
        assert int(got.cursor) == int(want.cursor)


def test_routed_window_update_consumes_epoch():
    """Epoch-driven advance inside the routed update: stale epochs are
    no-ops, forward epochs rotate, and the data still lands (1-shard mesh
    keeps this in the fast suite; the multidevice path is exercised by
    tests/test_distributed.py)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.core import sharded

    spec = WindowSpec(sketch=SketchSpec(width=512, depth=2, counter=CMLS16),
                      buckets=4, interval=60.0)
    win = window_init(spec, epoch=0)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def upd(tables, cursor, epoch_leaf, keys, rng, epoch):
        import dataclasses
        w_ = dataclasses.replace(win, tables=tables, cursor=cursor,
                                 epoch=epoch_leaf)
        out = sharded.routed_window_update(w_, keys[0], rng[0], "data",
                                           capacity=1024, epoch=epoch)
        return out.tables, out.cursor, out.epoch

    run = shard_map(upd, mesh=mesh,
                    in_specs=(P(), P(), P(), P("data"), P("data"), P()),
                    out_specs=(P(), P(), P()), check_vma=False)
    keys = jnp.asarray(np.full((1, 128), 42, np.uint32))
    rngs = jax.random.split(jax.random.PRNGKey(0), 1)
    tb, cur, ep = run(win.tables, win.cursor, win.epoch, keys, rngs,
                      jnp.asarray(0, jnp.int32))
    assert int(ep) == 0 and int(cur) == 0
    # epoch 2: two rotations before the update
    tb, cur, ep = run(tb, cur, ep, keys, rngs, jnp.asarray(2, jnp.int32))
    assert int(ep) == 2 and int(cur) == 2
    # stale epoch (1 < 2) clamps to no-op instead of erroring in the trace
    tb, cur, ep = run(tb, cur, ep, keys, rngs, jnp.asarray(1, jnp.int32))
    assert int(ep) == 2 and int(cur) == 2
    import dataclasses
    final = dataclasses.replace(win, tables=tb, cursor=cur, epoch=ep)
    # three 128-key batches landed: epoch 0 -> bucket 0, epoch 2 -> bucket
    # 2, and the stale-epoch batch also lands in the (unrotated) bucket 2
    est = float(window_query(final, jnp.asarray([42], jnp.uint32))[0])
    assert abs(est - 384) / 384 < 0.25
    est1 = float(window_query(final, jnp.asarray([42], jnp.uint32),
                              n_buckets=1)[0])
    assert abs(est1 - 256) / 256 < 0.25
