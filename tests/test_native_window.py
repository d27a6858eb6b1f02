"""Native (T, B, d, w) window-plane storage: parity + dispatch contracts.

The WindowPlane's state of record is ONE resident stacked leaf; flush
lands events through the row-mapped fused kernel on a free reshape of
that leaf (donated, in/out aliased) and rotation clears expired buckets
with one masked device op for ALL crossing tenants.  Everything here
pins the native paths to the legacy per-ring pipeline bit for bit:

  * native flush == dense restack flush (tables AND tracker heaps)
    across uniform / hot-tenant / subset traffic, mid-rotation, and the
    packed {cms32, log16, log8} storage layouts;
  * multi-tenant watermark rotation is ONE `window_advance_rows`
    dispatch and matches per-ring `window_advance_steps`;
  * `window_weights_stacked` row r == `window_weights` at cursor r;
  * `pmax_merge_window_stack` merges the whole leaf like per-ring
    `pmax_merge_window`;
  * checkpoint manifest v7 roundtrips the native leaf and pre-v7
    (v6..v3) manifests restore into it unchanged;
  * the native DecayedSketch is a 2-leaf pytree whose win/tail views
    cover the (history+1, d, w) leaf.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import CMLS8, CMLS16, CMS32, SketchSpec
from repro.core import sharded
from repro.core import sketch as sk
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.stream import CountService, WindowSpec
from repro.stream import window as w

SPEC = SketchSpec(width=2048, depth=3, counter=CMLS16)
WSPEC = WindowSpec(sketch=SPEC, buckets=4, interval=60.0)
COUNTERS = {"cms32": CMS32, "cmls16": CMLS16, "cmls8": CMLS8}
TENANTS = ("a", "b", "c")


def _zipf(n, vocab, seed=0):
    return (np.random.default_rng(seed).zipf(1.3, n) % vocab).astype(np.uint32)


def _wservice(wspec=WSPEC, track_top=8, seed=3):
    svc = CountService(queue_capacity=8192, seed=seed, track_top=track_top)
    for n in TENANTS:
        svc.add_tenant(n, window=wspec)
    return svc


# traffic regimes: (tenant -> (n_events, seed)) enqueued at ts
UNIFORM = {"a": (400, 1), "b": (300, 2), "c": (350, 3)}
HOT1 = {"b": (900, 4)}
SUBSET = {"a": (500, 5), "c": (250, 6)}
REGIMES = {"uniform": UNIFORM, "hot1": HOT1, "subset": SUBSET}


def _flush_pair(wspec, regime, mid_rotation=False, track_top=8):
    """Two identical services fed the same traffic; one flushed through
    the native zero-copy path, the other through the dense restack
    oracle.  Returns their window planes."""
    svcs = [_wservice(wspec, track_top=track_top) for _ in range(2)]
    for svc in svcs:
        for name, (n, seed) in regime.items():
            svc.enqueue(name, _zipf(n, 200, seed=seed), ts=10.0)
        if mid_rotation:
            svc.flush()
            # stagger the cursors/epochs: a rotates 1 interval, c two
            for name, ts, seed in (("a", 70.0, 11), ("c", 130.0, 12)):
                svc.enqueue(name, _zipf(200, 200, seed=seed), ts=ts)
    native, dense = svcs
    native.flush()
    for p in dense.planes:
        p.flush(dense=True)
    return native.planes[0], dense.planes[0]


def _assert_plane_equal(pa, pb):
    np.testing.assert_array_equal(np.asarray(pa.tables), np.asarray(pb.tables))
    np.testing.assert_array_equal(pa.cursors, pb.cursors)
    assert pa.epochs == pb.epochs
    if pa.tracker is not None:
        np.testing.assert_array_equal(np.asarray(pa.tracker.keys),
                                      np.asarray(pb.tracker.keys))
        np.testing.assert_array_equal(np.asarray(pa.tracker.estimates),
                                      np.asarray(pb.tracker.estimates))
        np.testing.assert_array_equal(np.asarray(pa.tracker.filled),
                                      np.asarray(pb.tracker.filled))


# --------------------------------------------------------------------------
# native flush == dense restack flush, bit for bit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_native_flush_matches_dense_restack(regime):
    """The donated flat-row flush on the native leaf must reproduce the
    legacy gather/update_many/scatter pipeline exactly — tables, cursors,
    and tracker heaps — whichever tenants have pending traffic."""
    _assert_plane_equal(*_flush_pair(WSPEC, REGIMES[regime]))


@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_native_flush_matches_dense_mid_rotation(regime):
    """Same parity with tenants at different cursors/epochs: the flat-row
    map (tenant*B + cursor) must land each batch in its own ACTIVE bucket
    after staggered watermark advances."""
    _assert_plane_equal(*_flush_pair(WSPEC, REGIMES[regime],
                                     mid_rotation=True))


@pytest.mark.parametrize("counter_name", sorted(COUNTERS))
def test_native_flush_matches_dense_packed(counter_name):
    """Packed storage (4x uint8 / 2x uint16 cells per uint32 lane) rides
    the same donated flat-row flush: the packed leaf's cells must equal
    the dense restack pipeline's bit for bit."""
    spec = SketchSpec(width=2048, depth=3, counter=COUNTERS[counter_name],
                      packed=True)
    wspec = WindowSpec(sketch=spec, buckets=4, interval=60.0)
    _assert_plane_equal(*_flush_pair(wspec, UNIFORM, mid_rotation=True))


def test_native_flush_preserves_unlisted_tenants():
    """Rows outside the pending set (and inactive buckets of pending
    rows) must come through the donated/aliased launch untouched."""
    native, _ = _flush_pair(WSPEC, UNIFORM)
    before = np.asarray(native.tables).copy()
    # flush only tenant b (row 1); a and c's rings must not move
    native.ring.append([1], [_zipf(100, 200, seed=9)])
    native.flush()
    after = np.asarray(native.tables)
    np.testing.assert_array_equal(after[0], before[0])
    np.testing.assert_array_equal(after[2], before[2])
    # b's inactive buckets persist too (only the cursor bucket moved)
    cur = int(native.cursors[1])
    for bkt in range(WSPEC.buckets):
        if bkt != cur:
            np.testing.assert_array_equal(after[1, bkt], before[1, bkt])
    assert not np.array_equal(after[1, cur], before[1, cur])


# --------------------------------------------------------------------------
# rotation: one masked dispatch for every crossing tenant
# --------------------------------------------------------------------------

def test_rotation_is_one_dispatch_for_many_tenants():
    """advance_many with several boundary-crossing tenants (empty queues)
    must cost exactly ONE `window_advance_rows` launch — not one
    `window_advance_steps` per tenant — and the host cursor/epoch mirrors
    must advance by each tenant's own step count."""
    svc = _wservice()
    plane = svc.planes[0]
    for name, (n, seed) in UNIFORM.items():
        svc.enqueue(name, _zipf(n, 200, seed=seed), ts=10.0)
    svc.flush()
    disp0 = plane._m_rotation_dispatches.value
    ops.reset_launch_counts()
    plane.advance_many([(0, 70.0), (1, 190.0), (2, 70.0)], svc.flush)
    assert ops.launch_counts() == {"window_advance_rows": 1}, \
        ops.launch_counts()
    assert plane._m_rotation_dispatches.value == disp0 + 1
    np.testing.assert_array_equal(plane.cursors, [1, 3, 1])
    assert plane.epochs == [1, 3, 1]


def test_rotation_matches_per_ring_advance_steps():
    """The masked whole-leaf rotation must clear exactly the buckets the
    per-ring `window_advance_steps` clears, per row, steps == 0 rows
    untouched."""
    rng = np.random.default_rng(7)
    t, b = 5, 4
    spec = SPEC
    tables = jnp.asarray(rng.integers(
        0, 200, (t, b, spec.depth, spec.storage_width)).astype(
        np.asarray(sk.init(spec).table).dtype))
    cursors = np.asarray([0, 1, 2, 3, 1], np.int32)
    steps = np.asarray([0, 1, 2, 5, 3], np.int32)  # incl. >= B fast-forward
    host = np.asarray(tables)  # the stacked op donates its input leaf
    out = np.asarray(ops.window_advance_rows(tables, cursors, steps))
    tables = jnp.asarray(host)
    for r in range(t):
        win = w.WindowedSketch(tables=tables[r],
                               cursor=jnp.asarray(cursors[r], jnp.int32),
                               spec=WSPEC, epoch=None)
        ref = w.window_advance_steps(win, jnp.asarray(steps[r], jnp.int32))
        np.testing.assert_array_equal(out[r], np.asarray(ref.tables),
                                      err_msg=f"row {r}")


def test_rotation_with_pending_fill_flushes_first():
    """A boundary crossing with buffered events must flush them into the
    PRE-rotation bucket, then rotate — bucket b still holds exactly one
    interval's events."""
    svc = _wservice()
    plane = svc.planes[0]
    svc.enqueue("a", np.full(64, 7, np.uint32), ts=10.0)
    # crossing enqueue: the ts=10 events must land in bucket 0, the
    # ts=70 events in bucket 1
    svc.enqueue("a", np.full(32, 7, np.uint32), ts=70.0)
    svc.flush()
    v = plane.win_view(0)
    assert int(plane.cursors[0]) == 1
    b0 = float(sk.query(v.bucket(0), jnp.asarray([7], jnp.uint32))[0])
    b1 = float(sk.query(v.bucket(1), jnp.asarray([7], jnp.uint32))[0])
    assert b0 >= 32 and b1 >= 16
    assert float(w.window_query(v, jnp.asarray([7], jnp.uint32))[0]) \
        >= b0 + b1 - 1e-3


# --------------------------------------------------------------------------
# stacked weights == per-ring weights
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_buckets", [None, 1, 2, 4])
@pytest.mark.parametrize("gamma", [None, 0.5, 1.0])
def test_window_weights_stacked_matches_per_ring(n_buckets, gamma):
    b = WSPEC.buckets
    cursors = np.arange(b, dtype=np.int32)
    stacked = np.asarray(w.window_weights_stacked(
        cursors, b, n_buckets=n_buckets, gamma=gamma))
    zeros = jnp.zeros((b, SPEC.depth, SPEC.storage_width),
                      sk.init(SPEC).table.dtype)
    for i, cur in enumerate(cursors):
        win = w.WindowedSketch(tables=zeros,
                               cursor=jnp.asarray(cur, jnp.int32),
                               spec=WSPEC, epoch=None)
        ref = np.asarray(w.window_weights(win, n_buckets=n_buckets,
                                          gamma=gamma))
        np.testing.assert_array_equal(stacked[i], ref, err_msg=f"cursor {cur}")


def test_window_weights_stacked_validates():
    with pytest.raises(ValueError):
        w.window_weights_stacked(np.zeros(2, np.int32), 4, n_buckets=5)
    with pytest.raises(ValueError):
        w.window_weights_stacked(np.zeros(2, np.int32), 4, gamma=0.0)


# --------------------------------------------------------------------------
# sharded: whole-leaf merge == per-ring merge
# --------------------------------------------------------------------------

def test_pmax_merge_window_stack_matches_per_ring():
    """`pmax_merge_window_stack` on the native (T, B, d, w) leaf must
    produce row r == `pmax_merge_window` on ring r (single-device mesh:
    pmax is the identity on logical states, so this pins the whole-leaf
    unpack -> collective -> repack plumbing and the delegation)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    spec = SketchSpec(width=1024, depth=2, counter=CMLS8, packed=True)
    wspec = WindowSpec(sketch=spec, buckets=3, interval=60.0)
    rng = np.random.default_rng(13)
    t = 4
    tables = jnp.asarray(rng.integers(
        0, np.iinfo(np.uint32).max, (t, wspec.buckets, spec.depth,
                                     spec.storage_width),
        dtype=np.uint32))
    mesh = make_mesh((1,), ("data",))
    merged = shard_map(
        lambda x: sharded.pmax_merge_window_stack(x, spec, "data"),
        mesh=mesh, in_specs=(P(),), out_specs=P())(tables)
    for r in range(t):
        win = w.WindowedSketch(tables=tables[r],
                               cursor=jnp.asarray(0, jnp.int32),
                               spec=wspec, epoch=None)
        ref = shard_map(lambda x: sharded.pmax_merge_window(
            w.WindowedSketch(tables=x, cursor=win.cursor, spec=wspec,
                             epoch=None), "data").tables,
            mesh=mesh, in_specs=(P(),), out_specs=P())(tables[r])
        np.testing.assert_array_equal(np.asarray(merged[r]), np.asarray(ref),
                                      err_msg=f"ring {r}")


# --------------------------------------------------------------------------
# checkpoint: v7 roundtrip + pre-v7 restore
# --------------------------------------------------------------------------

def _staggered_service(tmp_path=None):
    svc = _wservice()
    for name, (n, seed) in UNIFORM.items():
        svc.enqueue(name, _zipf(n, 200, seed=seed), ts=10.0)
    svc.flush()
    svc.enqueue("a", _zipf(150, 200, seed=21), ts=70.0)   # rotates a
    svc.enqueue("c", _zipf(120, 200, seed=22), ts=130.0)  # rotates c twice
    svc.flush()
    svc.enqueue("b", np.full(37, 123, np.uint32), ts=10.0)  # queue residue
    return svc


def _assert_restored_equal(svc, svc2):
    p, p2 = svc.planes[0], svc2.planes[0]
    np.testing.assert_array_equal(np.asarray(p.tables), np.asarray(p2.tables))
    np.testing.assert_array_equal(p.cursors, p2.cursors)
    assert p.epochs == p2.epochs
    probe = np.arange(64, dtype=np.uint32)
    for n in TENANTS:
        np.testing.assert_array_equal(np.asarray(svc.query(n, probe)),
                                      np.asarray(svc2.query(n, probe)))
        kf, ef = svc.topk(n, 5)
        k2, e2 = svc2.topk(n, 5)
        np.testing.assert_array_equal(kf, k2)
        np.testing.assert_array_equal(ef, e2)


def test_manifest_roundtrip_native_leaf(tmp_path):
    """Snapshot writes the native leaf + host mirrors (manifest v8; the
    untiered leaf layout is v7's) and restore rebuilds the identical
    plane: tables, cursors, epochs, queue residue, heaps, and query
    answers."""
    svc = _staggered_service()
    svc.snapshot(str(tmp_path), step=3)
    doc = json.load(open(os.path.join(str(tmp_path), "step_00000003",
                                      "manifest.json")))
    assert doc["metadata"]["version"] == 8
    svc2 = CountService.restore(str(tmp_path))
    # the 37 queued events persisted into the restored ring; both
    # services then replay them identically inside the query-path flush
    assert svc2.planes[0].pending() == 37
    _assert_restored_equal(svc, svc2)
    assert float(svc2.query("b", [123])[0]) >= 18


@pytest.mark.parametrize("version", [6, 5, 4, 3])
def test_pre_v7_manifest_restores_into_native_plane(tmp_path, version):
    """v6-and-earlier checkpoints stacked per-tenant rings into the SAME
    (T, B, d, w) / (T,) leaf shapes the native plane now owns, so a
    downgraded manifest must restore with zero conversion.  Each step
    down strips what that version hadn't introduced yet (v6 packed flag,
    v5 metrics snapshot, v4 admission map)."""
    svc = _staggered_service()
    svc.snapshot(str(tmp_path), step=1)
    mpath = os.path.join(str(tmp_path), "step_00000001", "manifest.json")
    doc = json.load(open(mpath))
    meta = doc["metadata"]
    meta["version"] = version
    if version < 6:
        for pm in meta["planes"]:
            pm["spec"].pop("packed", None)
        for wm in meta["windows"]:
            wm["sketch"].pop("packed", None)
        meta.get("spec", {}).pop("packed", None)
    if version < 5:
        meta.pop("metrics", None)
    if version < 4:
        meta.pop("admission", None)
    with open(mpath, "w") as f:
        json.dump(doc, f)
    svc2 = CountService.restore(str(tmp_path))
    _assert_restored_equal(svc, svc2)


def test_restore_repacks_native_leaf(tmp_path):
    """Repack-on-load converts the whole window leaf in one shot: an
    unpacked v7 snapshot restored with packed=True answers bit-identical
    window queries from packed storage."""
    svc = _staggered_service()
    svc.snapshot(str(tmp_path), step=2)
    svc2 = CountService.restore(str(tmp_path), packed=True)
    p2 = svc2.planes[0]
    assert p2.spec.packed
    assert p2.tables.shape[-1] == SPEC.width * SPEC.counter.bits // 32
    probe = np.arange(64, dtype=np.uint32)
    for n in TENANTS:
        np.testing.assert_array_equal(np.asarray(svc.query(n, probe)),
                                      np.asarray(svc2.query(n, probe)))


# --------------------------------------------------------------------------
# native DecayedSketch
# --------------------------------------------------------------------------

def test_decayed_sketch_is_native_two_leaf_pytree():
    """The decayed ring lives on ONE (history+1, d, w) leaf (ring rows
    [:B], fold tail at [B]) with the win/tail views slicing it — two
    device leaves total, jit-roundtrippable."""
    ds = w.decayed_init(SPEC, gamma=0.9, history=4)
    leaves, _ = jax.tree_util.tree_flatten(ds)
    assert len(leaves) == 2  # the stacked leaf + the cursor
    assert ds.tables.shape == (5, SPEC.depth, SPEC.storage_width)
    assert ds.win.tables.shape == (4, SPEC.depth, SPEC.storage_width)
    assert ds.tail.shape == (SPEC.depth, SPEC.storage_width)

    rng = jax.random.PRNGKey(0)
    keys = jnp.asarray(np.full(128, 5, np.uint32))
    ds = jax.jit(w.decayed_update)(ds, keys, rng)
    ds = jax.jit(w.decayed_rotate)(ds, jax.random.PRNGKey(1))
    est = float(w.decayed_query(ds, jnp.asarray([5], jnp.uint32))[0])
    assert est >= 0.9 * 64  # one decay step over ~128 events
