"""Streaming plane: bucket-ring windows, decay semantics, sharded merge."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CMLS8, CMLS16, SketchSpec
from repro.core import sketch as sk
from repro.stream import (DecayedSketch, WindowSpec, decay, decayed_init,
                          decayed_query, decayed_update, window_advance_to,
                          window_init, window_query, window_rotate,
                          window_update)


def _zipf(n, vocab, seed=0):
    return (np.random.default_rng(seed).zipf(1.3, n) % vocab).astype(np.uint32)


def _stream_rotations(win, rotations, seed0=0):
    """Feed one zipf batch per rotation; returns (win, list_of_events)."""
    key = jax.random.PRNGKey(7)
    events = []
    for r in range(rotations):
        ev = _zipf(3000, 1200, seed=seed0 + r)
        events.append(ev)
        key, k = jax.random.split(key)
        win = window_update(win, jnp.asarray(ev), k)
        if r < rotations - 1:
            win = window_rotate(win)
    return win, events


def test_window_property_within_cml_error_envelope():
    """Sliding-window estimates track a brute-force recount of the window's
    events within the single-sketch CML error envelope (ISSUE acceptance)."""
    spec = SketchSpec(width=4096, depth=4, counter=CMLS16)
    win, events = _stream_rotations(
        window_init(WindowSpec(sketch=spec, buckets=6)), rotations=10)
    for w in (1, 3, 4):
        window_events = np.concatenate(events[-w:])
        uniq, true = np.unique(window_events, return_counts=True)
        est = np.asarray(window_query(win, jnp.asarray(uniq), n_buckets=w))
        are = float(np.mean(np.abs(est - true) / true))
        # same envelope as test_counts_track_truth for one sketch of this
        # spec; the ring adds one bucket-boundary estimate per interval
        assert are < 0.35, f"window={w} ARE={are}"
        top = true >= 50
        if top.any():
            rel = np.abs(est[top] - true[top]) / true[top]
            assert rel.mean() < 0.15


def test_window_expired_events_do_not_count():
    spec = SketchSpec(width=1 << 14, depth=4, counter=CMLS16)
    win, events = _stream_rotations(
        window_init(WindowSpec(sketch=spec, buckets=4)), rotations=8)
    window_events = np.concatenate(events[-2:])
    old_only = np.setdiff1d(np.concatenate(events[:4]), window_events)
    assert old_only.size > 0
    est = np.asarray(window_query(win, jnp.asarray(old_only.astype(np.uint32)),
                                  n_buckets=2))
    # wide sketch => essentially no collision mass leaks from live buckets
    assert (est <= 1.0).mean() > 0.95


def test_window_rotate_reuses_and_zeroes_buckets():
    spec = SketchSpec(width=256, depth=2, counter=CMLS8)
    win = window_init(WindowSpec(sketch=spec, buckets=3))
    key = jax.random.PRNGKey(0)
    for r in range(4):  # one more than the ring size: bucket 0 is reused
        key, k = jax.random.split(key)
        win = window_update(win, jnp.asarray(_zipf(500, 100, seed=r)), k)
        if r < 3:
            win = window_rotate(win)
    assert int(win.cursor) == 0  # wrapped around
    # active bucket holds only rotation 3's events; the ring never grew
    assert win.tables.shape == (3, 2, 256)
    assert (np.asarray(win.tables[0]) > 0).any()


def test_window_query_modes_and_validation():
    spec = SketchSpec(width=1024, depth=2, counter=CMLS16)
    win, _ = _stream_rotations(
        window_init(WindowSpec(sketch=spec, buckets=4)), rotations=4)
    probe = jnp.arange(100, dtype=jnp.uint32)
    s = np.asarray(window_query(win, probe, mode="sum"))
    m = np.asarray(window_query(win, probe, mode="max"))
    assert (s >= m - 1e-5).all()  # sum over buckets dominates the max
    with pytest.raises(ValueError):
        window_query(win, probe, n_buckets=5)
    with pytest.raises(ValueError):
        window_query(win, probe, mode="median")


def test_window_is_jit_and_pytree_friendly():
    spec = SketchSpec(width=512, depth=2, counter=CMLS16)
    win = window_init(WindowSpec(sketch=spec, buckets=4))
    upd = jax.jit(window_update)
    rot = jax.jit(window_rotate)
    win = rot(upd(win, jnp.asarray(_zipf(200, 50)), jax.random.PRNGKey(0)))
    leaves, treedef = jax.tree_util.tree_flatten(win)
    win2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert (np.asarray(win2.tables) == np.asarray(win.tables)).all()
    assert int(win2.cursor) == int(win.cursor)


def test_decay_is_unbiased_in_estimate_space():
    """E[decode(decay(c, gamma))] == gamma * decode(c) (ISSUE acceptance)."""
    spec = SketchSpec(width=256, depth=1, counter=CMLS16)
    s = sk.init(spec)
    s = sk.update_batched(s, jnp.asarray([7], jnp.uint32),
                          jax.random.PRNGKey(0),
                          weights=jnp.asarray([1000.0]))
    v0 = float(sk.query(s, jnp.asarray([7], jnp.uint32))[0])
    for gamma in (0.5, 0.9):
        ests = [float(sk.query(decay(s, gamma, jax.random.PRNGKey(i)),
                               jnp.asarray([7], jnp.uint32))[0])
                for i in range(300)]
        assert abs(np.mean(ests) - gamma * v0) / (gamma * v0) < 0.02, gamma


def test_decay_validation_and_identity():
    spec = SketchSpec(width=128, depth=2, counter=CMLS8)
    s = sk.update_batched(sk.init(spec), jnp.asarray(_zipf(300, 60)),
                          jax.random.PRNGKey(1))
    with pytest.raises(ValueError):
        decay(s, 0.0, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        decayed_init(spec, gamma=1.5)
    same = decay(s, 1.0, jax.random.PRNGKey(0))
    # gamma=1: re-encode of an exactly-representable value is the identity
    assert (np.asarray(same.table) == np.asarray(s.table)).all()


def test_decayed_sketch_downweights_old_batches():
    spec = SketchSpec(width=4096, depth=4, counter=CMLS16)
    # history=4 < 7 batches, so the oldest batches live in the tail fold
    ds = decayed_init(spec, gamma=0.5, history=4)
    key = jax.random.PRNGKey(3)
    old_key, new_key = jnp.uint32(11), jnp.uint32(22)
    batches = [jnp.full((256,), old_key)] + \
        [jnp.asarray(_zipf(64, 5, seed=9)) + 100] * 5 + \
        [jnp.full((256,), new_key)]
    for b in batches:
        key, k = jax.random.split(key)
        ds = decayed_update(ds, b, k)
    assert isinstance(ds, DecayedSketch)
    est = np.asarray(decayed_query(ds, jnp.asarray([old_key, new_key])))
    # both keys saw 256 events; the old batch decayed through 6 more steps
    assert est[1] > 4 * est[0]


def test_lazy_decay_matches_eager_decay():
    """E[query(lazy gamma^age ring)] == query(eager decayed table) within
    counter tolerance (ISSUE acceptance) — including tail folds, since the
    stream is longer than the ring."""
    spec = SketchSpec(width=1 << 13, depth=4, counter=CMLS16)
    gamma = 0.6
    batches = [_zipf(2500, 400, seed=100 + r) for r in range(10)]

    # eager: decode -> gamma * value -> re-encode the WHOLE table, per batch
    s = sk.init(spec)
    key = jax.random.PRNGKey(42)
    for b in batches:
        key, k1, k2 = jax.random.split(key, 3)
        s = decay(s, gamma, k1)
        s = sk.update_batched(s, jnp.asarray(b), k2)

    # lazy: plain updates into the ring; decay applied at query time only
    ds = decayed_init(spec, gamma=gamma, history=6)
    key = jax.random.PRNGKey(43)
    for b in batches:
        key, k = jax.random.split(key)
        ds = decayed_update(ds, jnp.asarray(b), k)

    uniq = np.unique(np.concatenate(batches))
    true = np.zeros(uniq.shape)
    for age, b in enumerate(reversed(batches)):
        u, c = np.unique(b, return_counts=True)
        true[np.searchsorted(uniq, u)] += gamma ** age * c
    eager = np.asarray(sk.query(s, jnp.asarray(uniq)))
    lazy = np.asarray(decayed_query(ds, jnp.asarray(uniq)))
    sel = true >= 5.0  # keys with enough decayed mass to measure against
    rel_lazy = np.abs(lazy[sel] - true[sel]) / true[sel]
    rel_eager = np.abs(eager[sel] - true[sel]) / true[sel]
    assert rel_lazy.mean() < 0.2, rel_lazy.mean()
    # the two estimators agree in aggregate (both unbiased for the same
    # decayed count; eager pays B x the re-encode noise)
    assert abs(lazy[sel].mean() - eager[sel].mean()) / eager[sel].mean() \
        < 0.1
    assert rel_lazy.mean() < rel_eager.mean() + 0.05


def test_decayed_microbatching_skips_age_step():
    """age_step=False lands micro-batches in the same rotation interval."""
    spec = SketchSpec(width=4096, depth=3, counter=CMLS16)
    ds = decayed_init(spec, gamma=0.5, history=4)
    key = jax.random.PRNGKey(0)
    for i in range(4):  # 4 micro-batches, ONE decay interval
        key, k = jax.random.split(key)
        ds = decayed_update(ds, jnp.full((64,), 9, jnp.uint32), k,
                            age_step=(i == 0))
    est = float(decayed_query(ds, jnp.asarray([9], jnp.uint32))[0])
    assert abs(est - 256) / 256 < 0.1  # all at age 0: no decay applied


# --------------------------------------------------------------------------
# watermark-driven rotation
# --------------------------------------------------------------------------

def _wm_spec(buckets=4, interval=10.0, width=2048):
    return WindowSpec(sketch=SketchSpec(width=width, depth=3, counter=CMLS16),
                      buckets=buckets, interval=interval)


def test_watermark_rotates_by_event_time():
    win = window_init(_wm_spec())
    win = window_advance_to(win, 105.0)      # first watermark: no rotation
    assert int(win.epoch) == 10 and int(win.cursor) == 0
    key = jax.random.PRNGKey(0)
    win = window_update(win, jnp.full((64,), 1, jnp.uint32), key)
    win = window_advance_to(win, 108.0)      # same interval: no-op
    assert int(win.cursor) == 0
    win = window_advance_to(win, 127.0)      # +2 intervals -> 2 rotations
    assert int(win.cursor) == 2 and int(win.epoch) == 12
    win = window_update(win, jnp.full((64,), 2, jnp.uint32),
                        jax.random.PRNGKey(1))
    est_now = np.asarray(window_query(win, jnp.asarray([1, 2], jnp.uint32),
                                      n_buckets=1))
    est_all = np.asarray(window_query(win, jnp.asarray([1, 2], jnp.uint32)))
    assert est_now[0] <= 1.0 and est_now[1] >= 32   # old key out of window=1
    assert est_all[0] >= 32 and est_all[1] >= 32    # both in the full ring


def test_watermark_advance_past_full_ring_zeroes_everything():
    win = window_init(_wm_spec(buckets=3))
    win = window_advance_to(win, 0.0)
    for i in range(3):
        win = window_update(win, jnp.full((32,), i, jnp.uint32),
                            jax.random.PRNGKey(i))
        win = window_advance_to(win, 10.0 * (i + 1))
    assert (np.asarray(win.tables) > 0).any()
    win = window_advance_to(win, 1e6)        # far future: all expired
    assert (np.asarray(win.tables) == 0).all()
    assert int(win.epoch) == 100_000
    # cursor stays phase-consistent with the number of intervals elapsed
    assert int(win.cursor) == (3 + (100_000 - 3)) % 3


def test_watermark_validation():
    win = window_init(_wm_spec())
    win = window_advance_to(win, 50.0)
    with pytest.raises(ValueError):          # non-monotone: 50 -> 30
        window_advance_to(win, 30.0)
    window_advance_to(win, 51.0)             # jitter inside one interval: ok
    with pytest.raises(ValueError):          # cadence-only ring has no clock
        window_advance_to(window_init(_wm_spec(interval=0.0)), 1.0)
    with pytest.raises(ValueError):
        WindowSpec(sketch=_wm_spec().sketch, interval=-1.0)


# --------------------------------------------------------------------------
# lazy decay weights + engines in window_query
# --------------------------------------------------------------------------

def test_window_query_gamma_weights_and_engines_agree():
    spec = SketchSpec(width=2048, depth=3, counter=CMLS16)
    win, _ = _stream_rotations(
        window_init(WindowSpec(sketch=spec, buckets=4)), rotations=4)
    probe = jnp.arange(200, dtype=jnp.uint32)
    for mode in ("sum", "max"):
        for gamma in (None, 0.5):
            a = np.asarray(window_query(win, probe, mode=mode, gamma=gamma,
                                        engine="kernel"))
            b = np.asarray(window_query(win, probe, mode=mode, gamma=gamma,
                                        engine="jnp"))
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    plain = np.asarray(window_query(win, probe))
    decayed = np.asarray(window_query(win, probe, gamma=0.5))
    assert (decayed <= plain + 1e-5).all()   # downweighting only shrinks
    with pytest.raises(ValueError):
        window_query(win, probe, gamma=1.5)
    with pytest.raises(ValueError):
        window_query(win, probe, engine="cuda")


@pytest.mark.slow
def test_window_pmax_merge_multidevice():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core import SketchSpec, CMLS16, sharded
        from repro.launch.mesh import make_mesh
        from repro.stream import WindowSpec, window_init, window_query
        from repro.stream import window as W

        spec = SketchSpec(width=2048, depth=2, counter=CMLS16)
        wspec = WindowSpec(sketch=spec, buckets=4)
        mesh = make_mesh((8,), ("data",))
        win0 = window_init(wspec)
        tables = jnp.stack([win0.tables] * 8)
        keys = jnp.asarray((np.random.default_rng(0).zipf(1.4, 8 * 512)
                            % 256).astype(np.uint32)).reshape(8, 512)
        rngs = jax.random.split(jax.random.PRNGKey(0), 8)

        def upd(tb, k, r):
            w = W.WindowedSketch(tables=tb[0], cursor=jnp.zeros((), jnp.int32),
                                 spec=wspec)
            w = sharded.lazy_update_window(w, k[0], r[0], jnp.asarray(0), 1,
                                           "data")
            return w.tables[None]

        t2 = shard_map(upd, mesh=mesh,
                       in_specs=(P("data"), P("data"), P("data")),
                       out_specs=P("data"))(tables, keys, rngs)
        t2 = np.asarray(t2)
        assert (t2 == t2[0:1]).all(), "window merge did not synchronize"
        w = W.WindowedSketch(tables=jnp.asarray(t2[0]),
                             cursor=jnp.zeros((), jnp.int32), spec=wspec)
        est = np.asarray(window_query(w, jnp.arange(16, dtype=jnp.uint32)))
        assert (est[1:] >= 1).all()
        print("window-merge ok")
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "window-merge ok" in res.stdout
