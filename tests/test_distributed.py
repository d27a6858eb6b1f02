"""Distributed semantics: sharded sketch, collectives, sharding rules.

Multi-device behaviours run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test
process keeps the real 1-device platform (per the dry-run isolation rule).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.sharding import (GNN_RULES, LM_RULES, RECSYS_RULES, spec_for)


def _run_subprocess(body: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    code = textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


def test_spec_for_basic_mapping():
    mesh = make_mesh((1, 1), ("data", "model"))
    spec = spec_for(("batch", None, "act_embed"), LM_RULES, mesh)
    assert spec == jax.sharding.PartitionSpec(("data",), None, None)


def test_spec_for_drops_missing_mesh_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    spec = spec_for(("batch",), LM_RULES, mesh)        # ("pod","data") -> data
    assert spec == jax.sharding.PartitionSpec(("data",))


def test_spec_for_divisibility_degrades_to_replication():
    mesh = make_mesh((1,), ("model",))
    # trivially divisible by 1
    assert spec_for(("vocab",), LM_RULES, mesh, (50,)) == \
        jax.sharding.PartitionSpec(("model",))


def test_gnn_rules_flatten_edge_parallelism():
    mesh = make_mesh((1, 1), ("data", "model"))
    spec = spec_for(("edges",), GNN_RULES, mesh, (512,))
    assert spec == jax.sharding.PartitionSpec(("data", "model"))


@pytest.mark.slow
def test_key_routed_sketch_multidevice():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import SketchSpec, CMLS16, init
        from repro.core import sketch as sk, sharded

        mesh = make_mesh((8,), ("data",))
        spec = SketchSpec(width=2048, depth=3, counter=CMLS16)
        local = init(spec)
        # replicate local sketch per shard: table (8, d, w) stacked
        tables = jnp.stack([local.table] * 8)
        keys = jnp.asarray((np.random.default_rng(0).zipf(1.3, 8 * 1024)
                            % 4096).astype(np.uint32)).reshape(8, 1024)
        rngs = jax.random.split(jax.random.PRNGKey(0), 8)

        def upd(table, k, r):
            s = sk.Sketch(table=table[0], spec=spec)
            s = sharded.routed_update(s, k[0], r[0], "data", capacity=512)
            return s.table[None]

        tables2 = shard_map(upd, mesh=mesh,
                            in_specs=(P("data"), P("data"), P("data")),
                            out_specs=P("data"))(tables, keys, rngs)

        def q(table, k):
            s = sk.Sketch(table=table[0], spec=spec)
            return sharded.routed_query(s, k[0], "data", capacity=512)[None]

        probe = jnp.tile(jnp.arange(512, dtype=jnp.uint32)[None], (8, 1))
        est = shard_map(q, mesh=mesh, in_specs=(P("data"), P("data")),
                        out_specs=P("data"))(tables2, probe)
        est = np.asarray(est)
        # every shard must see the same global answer for the same probe
        assert np.allclose(est, est[0:1], atol=1e-5), "shards disagree"
        uniq, true = np.unique(np.asarray(keys).ravel(), return_counts=True)
        sel = uniq < 512
        got = est[0][uniq[sel]]
        rel = np.abs(got - true[sel]) / true[sel]
        print("ARE", rel.mean())
        assert rel.mean() < 0.4
    """)
    assert "ARE" in out


@pytest.mark.slow
def test_routed_topk_multidevice():
    """Key-routed heavy hitters: each shard tracks its own partition's
    top-k, and `routed_topk` candidate-set-merges them into one global,
    replicated heap holding the true heavy hitters with their owning
    shard's estimates."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import SketchSpec, CMS32, init
        from repro.core import sketch as sk, sharded, topk

        mesh = make_mesh((8,), ("data",))
        spec = SketchSpec(width=8192, depth=4, counter=CMS32)
        # 16 heavy keys with distinct known counts, spread over the shards
        heavy = np.arange(100, 116, dtype=np.uint32)
        counts = 40 + 10 * np.arange(16)
        stream = np.repeat(heavy, counts).astype(np.uint32)
        np.random.default_rng(0).shuffle(stream)
        stream = stream[: (len(stream) // 8) * 8].reshape(8, -1)
        tables = jnp.stack([init(spec).table] * 8)
        rngs = jax.random.split(jax.random.PRNGKey(0), 8)
        probes = jnp.tile(jnp.asarray(heavy)[None], (8, 1))

        def run(table, k, r, probe):
            s = sk.Sketch(table=table[0], spec=spec)
            s = sharded.routed_update(s, k[0], r[0], "data", capacity=2048)
            tr = topk.refresh(topk.init(6), s, probe[0])
            top = sharded.routed_topk(tr, "data", k=8)
            return top.keys[None], top.estimates[None], top.filled[None]

        keys, est, filled = shard_map(
            run, mesh=mesh,
            in_specs=(P("data"), P("data"), P("data"), P("data")),
            out_specs=(P("data"), P("data"), P("data")))(
                tables, jnp.asarray(stream), rngs, probes)
        keys, est = np.asarray(keys), np.asarray(est)
        assert (keys == keys[0:1]).all(), "shards disagree on the merge"
        assert np.asarray(filled).all()
        true_top = heavy[np.argsort(-counts)][:8]
        assert set(keys[0].tolist()) == set(true_top.tolist())
        want = np.sort(counts)[::-1][:8].astype(np.float32)
        np.testing.assert_array_equal(est[0], want)
        print("MERGED", keys[0].tolist())
    """)
    assert "MERGED" in out


@pytest.mark.slow
def test_routed_admit_multidevice():
    """Tracker-fed admission over key-routed shards: the all-gather
    candidate merge extended to admission masks — every shard reaches the
    same (replicated) decisions, admitting exactly the fleet-wide hot
    keys."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import SketchSpec, CMS32, init
        from repro.core import admission as adm
        from repro.core import sketch as sk, sharded, topk

        mesh = make_mesh((8,), ("data",))
        spec = SketchSpec(width=8192, depth=4, counter=CMS32)
        heavy = np.arange(100, 116, dtype=np.uint32)
        counts = 40 + 10 * np.arange(16)     # 40..190 events per heavy key
        stream = np.repeat(heavy, counts).astype(np.uint32)
        np.random.default_rng(0).shuffle(stream)
        stream = stream[: (len(stream) // 8) * 8].reshape(8, -1)
        tables = jnp.stack([init(spec).table] * 8)
        rngs = jax.random.split(jax.random.PRNGKey(0), 8)
        probes = jnp.tile(jnp.asarray(heavy)[None], (8, 1))
        aspec = adm.AdmissionSpec(threshold=100.0, n_fallback=64,
                                  table_rows=4096)
        ids = np.concatenate([heavy, [7]]).astype(np.uint32)  # +1 cold id
        ids_r = jnp.tile(jnp.asarray(ids)[None], (8, 1))

        def run(table, k, r, probe, query):
            s = sk.Sketch(table=table[0], spec=spec)
            s = sharded.routed_update(s, k[0], r[0], "data", capacity=2048)
            tr = topk.refresh(topk.init(6), s, probe[0])
            rows, ok = sharded.routed_admit(tr, query[0], aspec, "data")
            return rows[None], ok[None]

        rows, ok = shard_map(
            run, mesh=mesh,
            in_specs=(P("data"),) * 5,
            out_specs=(P("data"), P("data")),
            check_vma=False)(tables, jnp.asarray(stream), rngs, probes,
                             ids_r)
        rows, ok = np.asarray(rows), np.asarray(ok)
        assert (ok == ok[0:1]).all(), "shards disagree on admission"
        assert (rows == rows[0:1]).all()
        want = counts >= 100.0               # exact counts (no collisions)
        np.testing.assert_array_equal(ok[0], np.concatenate([want, [False]]))
        assert (rows[0][ok[0]] >= aspec.n_fallback).all()
        assert (rows[0][~ok[0]] < aspec.n_fallback).all()
        print("ADMITTED", int(ok[0].sum()))
    """)
    assert "ADMITTED" in out


@pytest.mark.slow
def test_key_routed_window_multidevice():
    """Key-routed bucket ring: routed update into the active bucket, fused
    routed window query (lazy decay weights included) aligned with keys."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import SketchSpec, CMLS16, sharded
        from repro.stream import WindowSpec, window_init, window_rotate
        from repro.stream import window as W

        mesh = make_mesh((8,), ("data",))
        spec = SketchSpec(width=2048, depth=3, counter=CMLS16)
        wspec = WindowSpec(sketch=spec, buckets=4)
        win0 = window_init(wspec)
        tables = jnp.stack([win0.tables] * 8)
        rng = np.random.default_rng(0)

        def upd(tb, cur, k, r):
            w = W.WindowedSketch(tables=tb[0], cursor=cur[0], spec=wspec)
            w = sharded.routed_window_update(w, k[0], r[0], "data",
                                            capacity=512)
            return w.tables[None]

        def q(tb, cur, k):
            w = W.WindowedSketch(tables=tb[0], cursor=cur[0], spec=wspec)
            return sharded.routed_window_query(w, k[0], "data", capacity=512,
                                               n_buckets=2)[None]

        def q_jnp(tb, cur, k):
            w = W.WindowedSketch(tables=tb[0], cursor=cur[0], spec=wspec)
            return sharded.routed_window_query(w, k[0], "data", capacity=512,
                                               n_buckets=2,
                                               engine="jnp")[None]

        cursor = jnp.zeros((8,), jnp.int32)
        key = jax.random.PRNGKey(0)
        all_rot = []
        for rot in range(3):  # rotations 0,1,2; window = last 2
            keys = jnp.asarray((rng.zipf(1.3, 8 * 1024) % 4096)
                               .astype(np.uint32)).reshape(8, 1024)
            all_rot.append(np.asarray(keys).ravel())
            key, k = jax.random.split(key)
            rngs = jax.random.split(k, 8)
            tables = shard_map(upd, mesh=mesh,
                               in_specs=(P("data"), P("data"), P("data"),
                                         P("data")),
                               out_specs=P("data"))(tables, cursor, keys,
                                                    rngs)
            if rot < 2:
                # every shard rotates on the same replicated schedule
                def rot_fn(tb, cur):
                    w = W.WindowedSketch(tables=tb[0], cursor=cur[0],
                                         spec=wspec)
                    w = window_rotate(w)
                    return w.tables[None], w.cursor[None]
                tables, cursor = shard_map(
                    rot_fn, mesh=mesh, in_specs=(P("data"), P("data")),
                    out_specs=(P("data"), P("data")))(tables, cursor)

        probe = jnp.tile(jnp.arange(512, dtype=jnp.uint32)[None], (8, 1))
        # fused kernel engine: pallas_call has no shard_map replication
        # rule, so the kernel path runs under check_vma=False
        est = np.asarray(shard_map(q, mesh=mesh,
                                   in_specs=(P("data"), P("data"),
                                             P("data")),
                                   out_specs=P("data"),
                                   check_vma=False)(tables, cursor, probe))
        est_jnp = np.asarray(shard_map(q_jnp, mesh=mesh,
                                       in_specs=(P("data"), P("data"),
                                                 P("data")),
                                       out_specs=P("data"))(tables, cursor,
                                                            probe))
        assert np.allclose(est, est_jnp, atol=1e-4), "engines disagree"
        assert np.allclose(est, est[0:1], atol=1e-5), "shards disagree"
        window_events = np.concatenate(all_rot[-2:])
        uniq, true = np.unique(window_events, return_counts=True)
        sel = uniq < 512
        rel = np.abs(est[0][uniq[sel]] - true[sel]) / true[sel]
        print("ARE", rel.mean())
        assert rel.mean() < 0.4
        # expired (rotation-0-only) keys must not leak into the window
        old_only = np.setdiff1d(all_rot[0], window_events)
        old_only = old_only[old_only < 512]
        if old_only.size:
            assert (est[0][old_only] <= 2.0).mean() > 0.9
    """)
    assert "ARE" in out


@pytest.mark.slow
def test_key_routed_window_epoch_driven_multidevice():
    """Watermark plumbing through the routed update: the event stream's
    epoch (replicated scalar) rotates every shard's ring inside
    `routed_window_update` — no caller-cadence window_rotate — and the
    rings stay bucket-aligned fleet-wide."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import SketchSpec, CMLS16, sharded
        from repro.stream import WindowSpec, window_init
        from repro.stream import window as W

        mesh = make_mesh((8,), ("data",))
        spec = SketchSpec(width=2048, depth=3, counter=CMLS16)
        wspec = WindowSpec(sketch=spec, buckets=4, interval=60.0)
        win0 = window_init(wspec, epoch=0)
        tables = jnp.stack([win0.tables] * 8)
        cursor = jnp.zeros((8,), jnp.int32)
        epoch_leaf = jnp.zeros((8,), jnp.int32)
        rng = np.random.default_rng(0)

        def upd(tb, cur, ep, k, r, epoch):
            w = W.WindowedSketch(tables=tb[0], cursor=cur[0], spec=wspec,
                                 epoch=ep[0])
            w = sharded.routed_window_update(w, k[0], r[0], "data",
                                             capacity=512, epoch=epoch)
            return w.tables[None], w.cursor[None], w.epoch[None]

        run = shard_map(upd, mesh=mesh,
                        in_specs=(P("data"), P("data"), P("data"),
                                  P("data"), P("data"), P()),
                        out_specs=(P("data"), P("data"), P("data")))
        key = jax.random.PRNGKey(0)
        all_rot = []
        # event-time epochs 0, 1, 2 (each batch lands in its own bucket)
        for ep in range(3):
            keys = jnp.asarray((rng.zipf(1.3, 8 * 1024) % 4096)
                               .astype(np.uint32)).reshape(8, 1024)
            all_rot.append(np.asarray(keys).ravel())
            key, k = jax.random.split(key)
            rngs = jax.random.split(k, 8)
            tables, cursor, epoch_leaf = run(tables, cursor, epoch_leaf,
                                             keys, rngs,
                                             jnp.asarray(ep, jnp.int32))
        assert (np.asarray(cursor) == 2).all()
        assert (np.asarray(epoch_leaf) == 2).all()

        def q(tb, cur, k):
            w = W.WindowedSketch(tables=tb[0], cursor=cur[0], spec=wspec)
            return sharded.routed_window_query(w, k[0], "data", capacity=512,
                                               n_buckets=2,
                                               engine="jnp")[None]

        probe = jnp.tile(jnp.arange(512, dtype=jnp.uint32)[None], (8, 1))
        est = np.asarray(shard_map(q, mesh=mesh,
                                   in_specs=(P("data"), P("data"),
                                             P("data")),
                                   out_specs=P("data"))(tables, cursor,
                                                        probe))
        assert np.allclose(est, est[0:1], atol=1e-5), "shards disagree"
        window_events = np.concatenate(all_rot[-2:])
        uniq, true = np.unique(window_events, return_counts=True)
        sel = uniq < 512
        rel = np.abs(est[0][uniq[sel]] - true[sel]) / true[sel]
        print("ARE", rel.mean())
        assert rel.mean() < 0.4
    """)
    assert "ARE" in out


@pytest.mark.slow
def test_lazy_pmax_merge_multidevice():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import SketchSpec, CMS32, init
        from repro.core import sketch as sk, sharded

        mesh = make_mesh((8,), ("data",))
        spec = SketchSpec(width=1 << 14, depth=2, counter=CMS32)
        tables = jnp.stack([init(spec).table] * 8)
        keys = jnp.asarray((np.random.default_rng(1).zipf(1.4, 8 * 512)
                            % 1024).astype(np.uint32)).reshape(8, 512)
        rngs = jax.random.split(jax.random.PRNGKey(1), 8)

        def upd(table, k, r):
            s = sk.Sketch(table=table[0], spec=spec)
            s = sharded.lazy_update(s, k[0], r[0], jnp.asarray(0), 1, "data")
            return s.table[None]

        t2 = shard_map(upd, mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
                       out_specs=P("data"))(tables, keys, rngs)
        t2 = np.asarray(t2)
        assert (t2 == t2[0:1]).all(), "merge did not synchronize shards"
        s = sk.Sketch(table=jnp.asarray(t2[0]), spec=spec)
        uniq, true = np.unique(np.asarray(keys).ravel(), return_counts=True)
        est = np.asarray(sk.query(s, jnp.asarray(uniq)))
        # max-merge of disjoint streams lower-bounds the union count but
        # must be >= the max per-shard count (>= true/8 on average)
        assert (est >= 1).all()
        print("ok", est.mean(), true.mean())
    """)
    assert "ok" in out


@pytest.mark.slow
def test_merged_metrics_multidevice():
    """Device half of the fleet metrics merge: per-shard instrument values
    reduce with `sharded.merged_metrics` (sum for counters/histogram
    buckets, max for gauges) and every shard sees the replicated fleet
    view — matching `obs.merge_snapshots` on the same values host-side."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.core import sharded
        from repro import obs

        mesh = make_mesh((8,), ("data",))
        # shard i packs [events counter, ring-fill gauge] as a value row
        vals = jnp.asarray(np.stack([[10.0 * (i + 1), float(i % 3)]
                                     for i in range(8)], 0), jnp.float32)

        def merge(v):
            summed = sharded.merged_metrics(v[0], "data", mode="sum")
            maxed = sharded.merged_metrics(v[0], "data", mode="max")
            return jnp.stack([summed, maxed])[None]

        got = np.asarray(shard_map(merge, mesh=mesh, in_specs=(P("data"),),
                                   out_specs=P("data"))(vals))
        # replicated: every shard holds the same fleet view
        assert (got == got[0:1]).all(), "shards disagree on the merge"
        snaps = [{"counters": {"events": 10.0 * (i + 1)},
                  "gauges": {"fill": {"value": float(i % 3),
                                      "high_water": float(i % 3)}}}
                 for i in range(8)]
        host = obs.merge_snapshots(snaps)
        assert got[0][0][0] == host["counters"]["events"]
        assert got[0][1][1] == host["gauges"]["fill"]["value"]
        print("ok", got[0][0][0], got[0][1][1])
    """)
    assert "ok" in out


@pytest.mark.slow
def test_compressed_allreduce_multidevice():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.train.compression import compressed_allreduce_mean

        mesh = make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 4096))

        def f(x):
            return compressed_allreduce_mean(x[0], "data")[None]

        got = shard_map(f, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"))(g)
        want = jnp.mean(g, axis=0)
        err = float(jnp.abs(got[0] - want).max())
        bound = float(jnp.abs(g).max()) / 127.0 + 1e-6
        print("err", err, "bound", bound)
        assert err <= bound
    """)
    assert "err" in out
