"""Active-row flush pipeline + heavy-hitter plane + single-launch epoch.

Bit-parity of the active-row flush against the dense whole-plane flush
(uniform / hot-tenant / empty-row regimes, windowed plane mid-rotation),
the single-launch fused update+score epoch against the two-launch
update-then-query pipeline (tables AND tracker heaps), launch-count
audits (one launch per tracked flush epoch; one window-query launch per
WindowPlane refresh regardless of flushed-tenant count), and the
`CountService.topk` tracker against exact host counts.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import CMLS16, CMS32, SketchSpec
from repro.core import sketch as sk
from repro.core import topk
from repro.kernels import ops
from repro.stream import CountService, WindowSpec
from repro.train import checkpoint
from tests._hypothesis_compat import given, settings, st

SPEC = SketchSpec(width=2048, depth=3, counter=CMLS16)


def _zipf(n, vocab, seed=0):
    return (np.random.default_rng(seed).zipf(1.3, n) % vocab).astype(np.uint32)


# --------------------------------------------------------------------------
# active-row flush == dense flush, bit for bit
# --------------------------------------------------------------------------

def test_update_rows_bit_identical_to_zero_weighted_dense():
    """ops.update_rows on the R-row subset == ops.update_many on the whole
    plane with the inactive rows' weights zeroed, across random subsets
    (including rows whose entire batch is weight-0 padding)."""
    rng = np.random.default_rng(5)
    t = 7
    for it in range(4):
        keys = jnp.asarray(rng.integers(0, 900, (t, ops.CHUNK),
                                        dtype=np.uint32))
        weights = np.zeros((t, ops.CHUNK), np.float32)
        r = int(rng.integers(1, t))
        rows = np.sort(rng.choice(t, r, replace=False)).astype(np.int32)
        for row in rows[:-1] if it == 2 else rows:
            # it == 2 leaves the last active row fully weight-0 (an "empty"
            # row riding in the active set must still be a no-op)
            weights[row, :int(rng.integers(1, ops.CHUNK))] = 1.0
        weights = jnp.asarray(weights)
        tables = jnp.stack([sk.init(SPEC).table] * t)
        lane = np.asarray([0, it], np.uint32)
        dense = ops.update_many(tables, SPEC, keys, lane, weights=weights)
        sel = jnp.asarray(rows)
        active = ops.update_rows(tables, SPEC, keys[sel], lane, rows,
                                 weights=weights[sel])
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(active))


@pytest.mark.parametrize("regime", ["uniform", "hot1", "subset"])
def test_service_active_row_flush_matches_dense(regime):
    """Two identically-fed services: one flushed through the service's
    active-row path, one forced dense — tables must be bit-identical in
    every skew regime (uniform = all tenants pending, hot1 = one of T,
    subset = some rows pending and some empty)."""
    names = tuple(f"t{i}" for i in range(5))
    svc_a = CountService(SPEC, tenants=names, queue_capacity=4096, seed=3)
    svc_d = CountService(SPEC, tenants=names, queue_capacity=4096, seed=3)
    pending = {"uniform": names, "hot1": names[2:3],
               "subset": (names[0], names[3], names[4])}[regime]
    for cycle in range(3):
        for i, n in enumerate(pending):
            keys = _zipf(600 + 100 * i, 500, seed=cycle * 10 + i)
            svc_a.enqueue(n, keys)
            svc_d.enqueue(n, keys)
        svc_a.flush()
        for plane in svc_d.planes:
            plane.flush(dense=True)
    pa, pd = svc_a.planes[0], svc_d.planes[0]
    np.testing.assert_array_equal(np.asarray(pa.tables), np.asarray(pd.tables))
    probe = np.arange(256, dtype=np.uint32)
    got_a, got_d = svc_a.query_all(probe), svc_d.query_all(probe)
    for n in names:
        np.testing.assert_array_equal(np.asarray(got_a[n]),
                                      np.asarray(got_d[n]))


def test_windowed_plane_active_row_flush_matches_dense_mid_rotation():
    """Windowed plane parity with the ring mid-rotation: tenants sit at
    different cursors/epochs, only a subset has pending fill, and the
    active-row flush must land exactly what the dense gather would."""
    wspec = WindowSpec(sketch=SPEC, buckets=4, interval=60.0)

    def build():
        svc = CountService(queue_capacity=8192, seed=1)
        for n in ("u", "v", "x"):
            svc.add_tenant(n, window=wspec)
        # stagger the watermarks: u at epoch 2, v at epoch 1, x at epoch 0
        svc.enqueue("u", _zipf(300, 200, seed=1), ts=10.0)
        svc.enqueue("v", _zipf(200, 200, seed=2), ts=70.0)
        svc.enqueue("x", _zipf(250, 200, seed=3), ts=20.0)
        svc.flush()
        svc.enqueue("u", _zipf(150, 200, seed=4), ts=130.0)  # rotates u
        # leave a mid-rotation pending subset: u and x, v idle
        svc.enqueue("x", _zipf(180, 200, seed=5), ts=30.0)
        return svc

    svc_a, svc_d = build(), build()
    assert svc_a.planes[0].pending() > 0
    svc_a.flush()
    svc_d.planes[0].flush(dense=True)
    pa, pd = svc_a.planes[0], svc_d.planes[0]
    for wa, wd in zip(pa.wins, pd.wins):
        np.testing.assert_array_equal(np.asarray(wa.tables),
                                      np.asarray(wd.tables))
        assert int(wa.cursor) == int(wd.cursor)
    probe = np.arange(128, dtype=np.uint32)
    for n in ("u", "v", "x"):
        np.testing.assert_array_equal(np.asarray(svc_a.query(n, probe)),
                                      np.asarray(svc_d.query(n, probe)))


# --------------------------------------------------------------------------
# single-launch flush epoch == two-launch pipeline (tables + heaps)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("regime", ["uniform", "hot1", "subset"])
def test_single_launch_epoch_matches_two_launch_pipeline(regime):
    """Two identically-fed TRACKED services: the fused update+score epoch
    (default flush) must land bit-identical tables AND heaps to the dense
    two-launch pipeline (whole-plane update, then a separate fused query
    refresh) in every skew regime."""
    names = tuple(f"t{i}" for i in range(5))
    svc_f = CountService(SPEC, tenants=names, queue_capacity=4096, seed=3,
                         track_top=8)
    svc_2 = CountService(SPEC, tenants=names, queue_capacity=4096, seed=3,
                         track_top=8)
    pending = {"uniform": names, "hot1": names[2:3],
               "subset": (names[0], names[3], names[4])}[regime]
    for cycle in range(3):
        for i, n in enumerate(pending):
            keys = _zipf(600 + 100 * i, 500, seed=cycle * 10 + i)
            svc_f.enqueue(n, keys)
            svc_2.enqueue(n, keys)
        svc_f.flush()
        for plane in svc_2.planes:
            plane.flush(dense=True)
    pf, p2 = svc_f.planes[0], svc_2.planes[0]
    np.testing.assert_array_equal(np.asarray(pf.tables), np.asarray(p2.tables))
    np.testing.assert_array_equal(np.asarray(pf.tracker.keys),
                                  np.asarray(p2.tracker.keys))
    np.testing.assert_array_equal(np.asarray(pf.tracker.estimates),
                                  np.asarray(p2.tracker.estimates))
    np.testing.assert_array_equal(np.asarray(pf.tracker.filled),
                                  np.asarray(p2.tracker.filled))
    for n in pending:
        kf, ef = svc_f.topk(n, 5)
        k2, e2 = svc_2.topk(n, 5)
        np.testing.assert_array_equal(kf, k2)
        np.testing.assert_array_equal(ef, e2)


def test_tracked_flush_epoch_is_one_launch():
    """A tracked TenantPlane flush must issue exactly ONE fused dispatch
    (`update_score_rows`) — no separate query launch — while the dense
    baseline pays the update + query pair."""
    names = tuple(f"t{i}" for i in range(4))
    svc = CountService(SPEC, tenants=names, queue_capacity=4096, track_top=8)
    for i, n in enumerate(names[:2]):
        svc.enqueue(n, _zipf(500, 300, seed=i))
    ops.reset_launch_counts()
    svc.flush()
    got = ops.launch_counts()
    assert got == {"update_score_rows": 1}, got
    # dense two-launch baseline for contrast
    for i, n in enumerate(names[:2]):
        svc.enqueue(n, _zipf(500, 300, seed=10 + i))
    ops.reset_launch_counts()
    for plane in svc.planes:
        plane.flush(dense=True)
    got = ops.launch_counts()
    assert got == {"update_many": 1, "query_many": 1}, got


@pytest.mark.parametrize("flushed", [1, 3])
def test_window_tracker_refresh_is_one_query_launch(flushed):
    """A WindowPlane tracker refresh costs ONE stacked window-query launch
    regardless of how many tenants flushed (previously one per tenant)."""
    wspec = WindowSpec(sketch=SPEC, buckets=4, interval=60.0)
    svc = CountService(queue_capacity=8192, track_top=8)
    for n in ("a", "b", "c"):
        svc.add_tenant(n, window=wspec)
    for i, n in enumerate(("a", "b", "c")[:flushed]):
        svc.enqueue(n, _zipf(300, 200, seed=i), ts=10.0)
    ops.reset_launch_counts()
    svc.flush()
    got = ops.launch_counts()
    assert got == {"update_rows": 1, "window_query_stacked": 1}, got


def test_windowed_tracked_plane_epoch_matches_dense_mid_rotation():
    """Tracked windowed-plane parity mid-rotation: heaps refreshed through
    the stacked multi-ring query must equal the dense pipeline's, with
    tenants at different cursors/epochs and a pending subset."""
    wspec = WindowSpec(sketch=SPEC, buckets=4, interval=60.0)

    def build():
        svc = CountService(queue_capacity=8192, seed=1, track_top=6)
        for n in ("u", "v", "x"):
            svc.add_tenant(n, window=wspec)
        svc.enqueue("u", _zipf(300, 200, seed=1), ts=10.0)
        svc.enqueue("v", _zipf(200, 200, seed=2), ts=70.0)
        svc.enqueue("x", _zipf(250, 200, seed=3), ts=20.0)
        svc.flush()
        svc.enqueue("u", _zipf(150, 200, seed=4), ts=130.0)  # rotates u
        svc.enqueue("x", _zipf(180, 200, seed=5), ts=30.0)
        return svc

    svc_a, svc_d = build(), build()
    svc_a.flush()
    svc_d.planes[0].flush(dense=True)
    pa, pd = svc_a.planes[0], svc_d.planes[0]
    for wa, wd in zip(pa.wins, pd.wins):
        np.testing.assert_array_equal(np.asarray(wa.tables),
                                      np.asarray(wd.tables))
    np.testing.assert_array_equal(np.asarray(pa.tracker.keys),
                                  np.asarray(pd.tracker.keys))
    np.testing.assert_array_equal(np.asarray(pa.tracker.estimates),
                                  np.asarray(pd.tracker.estimates))
    for n in ("u", "v", "x"):
        ka, ea = svc_a.topk(n, 4)
        kd, ed = svc_d.topk(n, 4)
        np.testing.assert_array_equal(ka, kd)
        np.testing.assert_array_equal(ea, ed)
        # the heap estimates ARE the read path's answers
        np.testing.assert_array_equal(ea, np.asarray(svc_a.query(n, ka)))


# --------------------------------------------------------------------------
# service heavy-hitter plane vs exact host counts
# --------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**20), st.floats(1.25, 1.7))
def test_service_topk_tracks_exact_heavy_hitters(seed, skew):
    """Property: on a Zipf stream, every true top-k item whose count
    clears the sketch error bound is in `service.topk`, and the reported
    estimates agree with `query_all` bit for bit."""
    spec = SketchSpec(width=8192, depth=4, counter=CMS32)
    svc = CountService(spec, tenants=("s",), queue_capacity=4096,
                      track_top=16)
    rng = np.random.default_rng(seed)
    stream = (rng.zipf(skew, 12_000) % 600).astype(np.uint32)
    for i in range(0, len(stream), 2500):  # several flushes
        svc.enqueue("s", stream[i:i + 2500])
    k = 8
    keys, est = svc.topk("s", k)
    assert keys.shape == est.shape and keys.shape[0] <= k
    # estimates are the sketch's own answers, exactly
    np.testing.assert_array_equal(est, np.asarray(svc.query_all(keys)["s"]))
    assert (np.diff(est) <= 0).all()  # sorted by descending estimate
    # CM error bound: overestimate <= e * N / w (whp over d rows); any item
    # whose true count beats the k-th true count by that margin MUST be in
    # the returned top-k
    uniq, true = np.unique(stream, return_counts=True)
    bound = np.e * len(stream) / spec.width
    kth = np.sort(true)[::-1][min(k, len(true)) - 1]
    must_have = uniq[true > kth + bound]
    present = set(int(x) for x in keys)
    missing = [int(u) for u in must_have if int(u) not in present]
    assert not missing, f"clear heavy hitters absent from topk: {missing}"


def test_topk_estimates_track_later_collisions():
    """Tracker estimates are re-queried at every refresh: mass landing
    later (even via other keys' flushes) is reflected on the next read."""
    svc = CountService(SPEC, tenants=("s",), queue_capacity=2048,
                      track_top=4)
    svc.enqueue("s", np.full(60, 11, np.uint32))
    k1, e1 = svc.topk("s")
    svc.enqueue("s", np.full(200, 11, np.uint32))
    k2, e2 = svc.topk("s")
    assert e2[list(k2).index(11)] > e1[list(k1).index(11)]
    np.testing.assert_array_equal(e2, np.asarray(svc.query("s", k2)))


def test_topk_requires_tracking_and_validates_k():
    svc = CountService(SPEC, tenants=("s",), queue_capacity=256)
    with pytest.raises(ValueError):
        svc.topk("s")
    svc2 = CountService(SPEC, tenants=("s",), queue_capacity=256, track_top=4)
    svc2.enqueue("s", [1, 2, 3])
    with pytest.raises(ValueError):
        svc2.topk("s", 5)
    with pytest.raises(ValueError):
        svc2.topk("s", gamma=0.9)  # plain tenant: no window kwargs
    keys, est = svc2.topk("s", 2)
    assert len(keys) == 2


def test_windowed_topk_reorders_on_expiry_and_decay():
    """Bucket expiry and query-time decay re-rank the heap without any
    flush: the old leader expires out, and gamma re-weights recency."""
    wspec = WindowSpec(sketch=SPEC, buckets=3, interval=60.0)
    svc = CountService(queue_capacity=8192, track_top=4)
    svc.add_tenant("w", window=wspec)
    svc.enqueue("w", np.full(120, 7, np.uint32), ts=10.0)   # epoch 0 leader
    svc.enqueue("w", np.full(50, 9, np.uint32), ts=70.0)    # epoch 1
    keys, est = svc.topk("w", 2)
    assert list(keys) == [7, 9]
    # two more rotations expire epoch 0: key 7's bucket leaves the ring
    svc.enqueue("w", np.full(40, 9, np.uint32), ts=190.0)
    keys, est = svc.topk("w", 2)
    assert keys[0] == 9
    if 7 in keys:  # the expired leader may survive as a zero-count candidate
        assert est[list(keys).index(7)] == 0.0
    # estimates agree with the window query they were scored by
    np.testing.assert_array_equal(est, np.asarray(svc.query("w", keys)))


def test_windowed_topk_matches_query_with_gamma():
    wspec = WindowSpec(sketch=SPEC, buckets=4, interval=60.0)
    svc = CountService(queue_capacity=8192, track_top=4)
    svc.add_tenant("w", window=wspec)
    svc.enqueue("w", np.full(80, 5, np.uint32), ts=10.0)
    svc.enqueue("w", np.full(60, 6, np.uint32), ts=70.0)
    keys, est = svc.topk("w", 2, gamma=0.5)
    np.testing.assert_array_equal(
        est, np.asarray(svc.query("w", keys, gamma=0.5)))
    assert keys[0] == 6  # decay ranks the recent key above the older one


# --------------------------------------------------------------------------
# persistence: manifest v3 round-trip, v2 back-compat (cold trackers)
# --------------------------------------------------------------------------

def test_topk_snapshot_restore_roundtrip(tmp_path):
    wspec = WindowSpec(sketch=SPEC, buckets=4, interval=60.0)
    svc = CountService(SPEC, tenants=("a", "b"), queue_capacity=2048,
                      track_top=8)
    svc.add_tenant("w", window=wspec)
    svc.enqueue("a", _zipf(3000, 300, seed=1))
    svc.enqueue("b", _zipf(1000, 300, seed=2))
    svc.enqueue("w", _zipf(800, 300, seed=3), ts=10.0)
    before = {n: svc.topk(n, 5) for n in ("a", "b", "w")}
    svc.snapshot(str(tmp_path), step=2)

    svc2 = CountService.restore(str(tmp_path))
    assert svc2.track_top == 8
    for n in ("a", "b", "w"):
        keys, est = svc2.topk(n, 5)
        np.testing.assert_array_equal(keys, before[n][0])
        np.testing.assert_array_equal(est, before[n][1])
        np.testing.assert_array_equal(est,
                                      np.asarray(svc2.query_all(keys)[n]))


def test_v2_checkpoint_restores_with_cold_trackers(tmp_path):
    """A v2-era manifest (no tracker leaves) restores; passing track_top
    re-arms tracking with COLD heaps that refill from new traffic."""
    svc = CountService(SPEC, tenants=("a",), queue_capacity=1024)
    svc.enqueue("a", _zipf(2000, 200, seed=4))
    svc.flush()
    meta = dict(svc._meta(), version=2)
    del meta["track_top"]
    checkpoint.save(str(tmp_path), 5, svc._tree(with_topk=False),
                    metadata=meta)

    svc2 = CountService.restore(str(tmp_path), track_top=6)
    assert svc2.track_top == 6
    plane = svc2.planes[0]
    assert plane.tracker is not None
    assert not bool(np.asarray(plane.tracker.filled).any())  # cold
    np.testing.assert_array_equal(  # tables themselves restored intact
        np.asarray(svc2.query("a", np.arange(64))),
        np.asarray(svc.query("a", np.arange(64))))
    svc2.enqueue("a", np.full(90, 42, np.uint32))
    keys, est = svc2.topk("a", 1)
    assert list(keys) == [42]
    # without track_top the restore is tracker-less, as before
    svc3 = CountService.restore(str(tmp_path))
    assert svc3.track_top is None


# --------------------------------------------------------------------------
# routed top-k (1-shard mesh; the multidevice path lives in
# tests/test_distributed.py)
# --------------------------------------------------------------------------

def test_routed_topk_single_shard_reselects():
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.core import sharded

    spec = SketchSpec(width=4096, depth=4, counter=CMS32)
    s = sk.update_batched(sk.init(spec),
                          jnp.asarray([3, 4, 5], jnp.uint32),
                          jax.random.PRNGKey(0),
                          weights=jnp.asarray([30.0, 50.0, 10.0]))
    tr = topk.refresh(topk.init(4), s, jnp.asarray([3, 4, 5], jnp.uint32))
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def merge(keys, est, filled):
        out = sharded.routed_topk(
            topk.TopK(keys=keys, estimates=est, filled=filled), "data", k=2)
        return out.keys, out.estimates, out.filled

    # the replication checker cannot prove the all_gather+top_k output is
    # replicated (same rule gap as routed_window_query's kernel engine)
    run = shard_map(merge, mesh=mesh, in_specs=(P(), P(), P()),
                    out_specs=(P(), P(), P()), check_vma=False)
    keys, est, filled = run(tr.keys, tr.estimates, tr.filled)
    assert list(np.asarray(keys)) == [4, 3]
    np.testing.assert_allclose(np.asarray(est), [50.0, 30.0])
    assert np.asarray(filled).all()
