"""Tracker-fed admission plane + manifest v4 + track_top re-arm.

The admission plane decides embedding-row placement from the heavy-hitter
tracker (refreshed per flush epoch) instead of a host-path sketch nobody
maintains: hot keys get private rows automatically, window expiry revokes
them, shards merge decisions through the routed candidate gather, and the
policies + heaps survive snapshot/restore (including restore at a
DIFFERENT track_top: shrink keeps the best candidates, grow cold-masks).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import CMLS16, CMS32, SketchSpec
from repro.core import admission as adm
from repro.core import sketch as sk
from repro.core import topk
from repro.stream import CountService, WindowSpec

SPEC = SketchSpec(width=4096, depth=3, counter=CMS32)
ASPEC = adm.AdmissionSpec(threshold=5.0, n_fallback=64, table_rows=1024)


def _zipf(n, vocab, seed=0):
    return (np.random.default_rng(seed).zipf(1.3, n) % vocab).astype(np.uint32)


# --------------------------------------------------------------------------
# service admission plane
# --------------------------------------------------------------------------

def test_admission_requires_tracker_and_policy():
    svc = CountService(SPEC, queue_capacity=256)  # no track_top
    with pytest.raises(ValueError):
        svc.add_tenant("emb", admission=ASPEC)
    svc2 = CountService(SPEC, queue_capacity=256, track_top=4)
    svc2.add_tenant("emb", admission=ASPEC)
    svc2.add_tenant("plain")
    with pytest.raises(ValueError):
        svc2.admit("plain", [1, 2])  # no policy registered
    assert svc2.admission_of("plain") is None
    assert svc2.admission_of("emb") == ASPEC
    with pytest.raises(ValueError):
        svc2.admit("emb", [1], gamma=0.9)  # plain tenant: no window kwargs


def test_admit_promotes_hot_ids_and_refreshes_per_epoch():
    """Hot keys acquire private rows automatically once their tracked
    estimate clears the threshold; decisions move with the flush epoch."""
    svc = CountService(SPEC, queue_capacity=4096, track_top=8)
    svc.add_tenant("emb", admission=ASPEC)
    svc.enqueue("emb", np.full(3, 7, np.uint32))  # below threshold
    rows, admitted = svc.admit("emb", [7])
    assert not bool(admitted[0]) and int(rows[0]) < ASPEC.n_fallback
    svc.enqueue("emb", np.full(50, 7, np.uint32))  # next epoch: hot
    rows, admitted = svc.admit("emb", [7])
    assert bool(admitted[0]) and int(rows[0]) >= ASPEC.n_fallback
    # the admitted row agrees with the policy's row map
    want_rows, want_mask = adm.admit_tracked(
        *(jnp.asarray(x) for x in svc.planes[0].topk_row(0)),
        jnp.asarray([7], jnp.uint32), ASPEC)
    assert int(rows[0]) == int(want_rows[0])
    # decisions validate ids like enqueue does
    with pytest.raises(ValueError):
        svc.admit("emb", [-3])
    with pytest.raises(TypeError):
        svc.admit("emb", [1.5])


def test_windowed_admission_expires_with_the_window():
    """Time-scoped admission: an id whose traffic expired out of the ring
    loses its private row on the next decision."""
    wspec = WindowSpec(sketch=SPEC, buckets=3, interval=60.0)
    svc = CountService(queue_capacity=8192, track_top=8)
    svc.add_tenant("w", window=wspec, admission=ASPEC)
    svc.enqueue("w", np.full(40, 5, np.uint32), ts=10.0)
    _, admitted = svc.admit("w", [5])
    assert bool(admitted[0])
    svc.enqueue("w", np.full(1, 9, np.uint32), ts=250.0)  # bucket expired
    _, admitted = svc.admit("w", [5])
    assert not bool(admitted[0])
    # window kwargs scope the decision (n_buckets=1: only the newest)
    svc.enqueue("w", np.full(40, 6, np.uint32), ts=260.0)
    _, a_all = svc.admit("w", [6])
    _, a_new = svc.admit("w", [6], n_buckets=1)
    assert bool(a_all[0]) and bool(a_new[0])


def test_admit_tracked_bounds_set_to_heap():
    """The heap bounds the admitted set: a key hot in the sketch but
    evicted from the top-K heap is not admitted (size K accordingly)."""
    keys = jnp.asarray([3, 4], jnp.uint32)
    est = jnp.asarray([50.0, 2.0], jnp.float32)
    filled = jnp.asarray([True, True])
    rows, admitted = adm.admit_tracked(keys, est, filled,
                                       jnp.asarray([3, 4, 9], jnp.uint32),
                                       ASPEC)
    assert list(np.asarray(admitted)) == [True, False, False]
    # unfilled slots never admit, even at key 0 with a stale estimate
    rows, admitted = adm.admit_tracked(
        jnp.zeros((2,), jnp.uint32), jnp.full((2,), 99.0),
        jnp.asarray([False, False]), jnp.asarray([0], jnp.uint32), ASPEC)
    assert not bool(admitted[0])


# --------------------------------------------------------------------------
# observe_and_admit: kernel engines + key validation (satellite)
# --------------------------------------------------------------------------

def test_observe_and_admit_engines_bit_identical():
    """Kernel vs XLA engine parity — on a MULTI-CHUNK batch (> CHUNK
    deduped keys over a narrow table), where the kernel's sequential
    chunk sweep makes later chunks see earlier chunks' writes: the XLA
    engine must be the chunk-sequential reference (`ops.update_xla`),
    not a one-shot update, or the two backends' admission decisions
    diverge."""
    spec = SketchSpec(width=2048, depth=3, counter=CMLS16)
    ids = jnp.asarray(np.random.default_rng(2).integers(
        0, 4000, 6000, dtype=np.int64).astype(np.uint32))
    assert len(np.unique(np.asarray(ids))) > 1024  # spans several CHUNKs
    rng = jax.random.PRNGKey(4)
    outs = {}
    for engine in ("kernel", "xla", "auto"):
        s, rows, admitted = adm.observe_and_admit(
            sk.init(spec), ids, rng, ASPEC, engine=engine)
        outs[engine] = (np.asarray(s.table), np.asarray(rows),
                        np.asarray(admitted))
    for engine in ("xla", "auto"):
        np.testing.assert_array_equal(outs["kernel"][0], outs[engine][0])
        np.testing.assert_array_equal(outs["kernel"][1], outs[engine][1])
        np.testing.assert_array_equal(outs["kernel"][2], outs[engine][2])
    with pytest.raises(ValueError):
        adm.observe_and_admit(sk.init(spec), ids, rng, ASPEC,
                              engine="banana")


def test_observe_and_admit_validates_keys_like_enqueue():
    spec = SketchSpec(width=512, depth=2, counter=CMLS16)
    rng = jax.random.PRNGKey(0)
    with pytest.raises(ValueError):
        adm.observe_and_admit(sk.init(spec), np.asarray([-1]), rng, ASPEC)
    with pytest.raises(TypeError):
        adm.observe_and_admit(sk.init(spec), np.asarray([0.5]), rng, ASPEC)
    with pytest.raises(ValueError):
        adm.observe_and_admit(sk.init(spec), np.asarray([1 << 33]), rng,
                              ASPEC)
    # traced ids pass through (validated by their producer)
    s, rows, admitted = jax.jit(
        lambda ids: adm.observe_and_admit(sk.init(spec), ids, rng, ASPEC,
                                          engine="xla"))(
        jnp.asarray([1, 2], jnp.uint32))
    assert rows.shape == (2,)


def test_window_query_many_rejects_mixed_specs():
    from repro.stream import window_init, window_query_many
    a = window_init(WindowSpec(sketch=SPEC, buckets=3))
    b = window_init(WindowSpec(sketch=SPEC, buckets=3, interval=60.0))
    keys = jnp.zeros((2, 8), jnp.uint32)
    with pytest.raises(ValueError):
        window_query_many([a, b], keys)  # same geometry, different spec
    with pytest.raises(ValueError):
        window_query_many([], keys)


# --------------------------------------------------------------------------
# manifest v4 + resize restore
# --------------------------------------------------------------------------

def test_admission_persists_through_v4_manifest(tmp_path):
    svc = CountService(SPEC, queue_capacity=2048, track_top=8)
    svc.add_tenant("emb", admission=ASPEC)
    svc.add_tenant("plain")
    svc.enqueue("emb", np.concatenate([np.full(50, 7, np.uint32),
                                       _zipf(300, 100, seed=1)]))
    rows, admitted = svc.admit("emb", [7, 3])
    svc.snapshot(str(tmp_path), step=1)

    svc2 = CountService.restore(str(tmp_path))
    assert svc2.admission_of("emb") == ASPEC
    assert svc2.admission_of("plain") is None
    rows2, admitted2 = svc2.admit("emb", [7, 3])
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows2))
    np.testing.assert_array_equal(np.asarray(admitted), np.asarray(admitted2))


def test_restore_with_smaller_track_top_keeps_best_candidates(tmp_path):
    """Shrink re-arm: the surviving heap is the best K' of the saved heap
    (re-selected by estimate), not a blind truncation."""
    svc = CountService(SPEC, tenants=("s",), queue_capacity=4096,
                       track_top=16)
    svc.enqueue("s", _zipf(8000, 400, seed=3))
    full_keys, full_est = svc.topk("s", 16)
    svc.snapshot(str(tmp_path), step=1)

    svc2 = CountService.restore(str(tmp_path), track_top=4)
    assert svc2.track_top == 4
    assert svc2.planes[0].tracker.keys.shape == (1, 4)
    keys, est = svc2.topk("s", 4)
    np.testing.assert_array_equal(keys, full_keys[:4])
    np.testing.assert_array_equal(est, full_est[:4])
    # estimates still agree with the read path after the resize
    np.testing.assert_array_equal(est, np.asarray(svc2.query("s", keys)))
    with pytest.raises(ValueError):
        svc2.topk("s", 16)  # k now bounded by the new width


def test_restore_with_larger_track_top_cold_masks_new_slots(tmp_path):
    svc = CountService(SPEC, tenants=("s",), queue_capacity=4096,
                       track_top=4)
    svc.enqueue("s", _zipf(5000, 300, seed=6))
    old_keys, old_est = svc.topk("s", 4)
    svc.snapshot(str(tmp_path), step=2)

    svc2 = CountService.restore(str(tmp_path), track_top=12)
    assert svc2.track_top == 12
    tracker = svc2.planes[0].tracker
    assert tracker.keys.shape == (1, 12)
    filled = np.asarray(tracker.filled[0])
    assert filled.sum() == np.asarray(
        CountService.restore(str(tmp_path)).planes[0].tracker.filled).sum()
    assert not filled[4:].any()  # grown slots are cold
    keys, est = svc2.topk("s", 4)
    np.testing.assert_array_equal(keys, old_keys)
    np.testing.assert_array_equal(est, old_est)
    # the grown heap refills from new traffic
    svc2.enqueue("s", np.full(9000, 4_000_000, np.uint32))
    keys, est = svc2.topk("s", 12)
    assert 4_000_000 in keys


def test_resize_stacked_shrink_is_estimate_ordered():
    """Unit-level: shrink keeps the BEST candidates even if the stored
    rows were not estimate-sorted."""
    tk = topk.TopK(
        keys=jnp.asarray([[1, 2, 3, 4]], jnp.uint32),
        estimates=jnp.asarray([[5.0, 50.0, -jnp.inf, 40.0]], jnp.float32),
        filled=jnp.asarray([[True, True, False, True]]))
    out = topk.resize_stacked(tk, 2)
    assert list(np.asarray(out.keys[0])) == [2, 4]
    assert list(np.asarray(out.estimates[0])) == [50.0, 40.0]
    assert np.asarray(out.filled).all()
    same = topk.resize_stacked(tk, 4)
    np.testing.assert_array_equal(np.asarray(same.keys), np.asarray(tk.keys))


# --------------------------------------------------------------------------
# routed admission (1-shard mesh; multidevice in tests/test_distributed.py)
# --------------------------------------------------------------------------

def test_routed_admit_single_shard_matches_local_policy():
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.core import sharded

    spec = SketchSpec(width=4096, depth=4, counter=CMS32)
    s = sk.update_batched(sk.init(spec),
                          jnp.asarray([3, 4, 5], jnp.uint32),
                          jax.random.PRNGKey(0),
                          weights=jnp.asarray([30.0, 50.0, 2.0]))
    tr = topk.refresh(topk.init(4), s, jnp.asarray([3, 4, 5], jnp.uint32))
    aspec = adm.AdmissionSpec(threshold=10.0, n_fallback=16, table_rows=256)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def body(keys, est, filled, ids):
        return sharded.routed_admit(
            topk.TopK(keys=keys, estimates=est, filled=filled), ids, aspec,
            "data")

    run = shard_map(body, mesh=mesh, in_specs=(P(),) * 4,
                    out_specs=(P(), P()), check_vma=False)
    ids = jnp.asarray([3, 4, 5, 6], jnp.uint32)
    rows, admitted = run(tr.keys, tr.estimates, tr.filled, ids)
    assert list(np.asarray(admitted)) == [True, True, False, False]
    # row layout agrees with the single-chip policy on the merged heap
    want_rows, want_adm = adm.admit_tracked(tr.keys, tr.estimates,
                                            tr.filled, ids, aspec)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(want_rows))
    np.testing.assert_array_equal(np.asarray(admitted), np.asarray(want_adm))
