"""The key-routed plane: `CountService(mesh=...)` with tables sharded by key.

The four-shard comparisons run once, in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4 (the test process keeps
its one-device platform), and the tests below read its findings:

  * against four one-device `TenantPlane`s, each fed its own key partition
    (routed on the host with `route_of`) and drawing from the same shard
    lane: with linear cells and every key owning a cell of its own the
    tables and answers are bit-identical; with the paper's Morris cells the
    answers agree within the counter's own spread;
  * against exact counts, and `topk` against one device's top-k of the
    union.

Bit-identity with Morris cells cannot hold: a plain plane's epoch places
each key (its chunk and its uniform) by how many queue slots sort before
it, duplicates and stale slots included, while a routed owner receives
each source's keys already merged, so the same key sits elsewhere and
draws another uniform.  The estimator is the same.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh

from repro.core import sharded
from repro.core.counters import CounterSpec
from repro.core.sketch import SketchSpec
from repro.kernels import ops
from repro.stream import CountService, TierSpec, WindowSpec

SHARDS = 4

FOUR_SHARDS = r"""
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, Mesh
from repro.core import sharded
from repro.core.counters import CounterSpec
from repro.core.hashing import host_row_seeds, row_hashes
from repro.core.sketch import SketchSpec
from repro.stream import CountService, TenantPlane

N = 4
mesh = Mesh(np.asarray(jax.devices()[:N]), ("keys",),
            axis_types=(AxisType.Auto,))
SEED, CAP, TOP = 11, 1200, 8
rng = np.random.default_rng(5)
# two tenants, Zipf streams over 2,000 keys (the heavy hitters well apart)
streams = {t: (rng.zipf(1.25, 12000) % 2000).astype(np.uint32) + 1
           for t in ("a", "b")}
out = {}


def routed(spec):
    svc = CountService(queue_capacity=CAP, seed=SEED, track_top=TOP,
                       mesh=mesh)
    for t in streams:
        svc.add_tenant(t, spec=spec)
    for i in range(0, 12000, 1000):   # queue-pressure epochs, then a flush
        svc.enqueue_many({t: s[i:i + 1000] for t, s in streams.items()})
    svc.flush()
    return svc


def by_shard(spec):
    # shard s's reference: a one-device plane fed the keys s owns, drawing
    # from shard s's lane
    owner = {t: np.asarray(sharded.route_of(jnp.asarray(s), N))
             for t, s in streams.items()}
    refs = []
    for s, lane in enumerate(sharded.shard_seeds(SEED, N)):
        p = TenantPlane(spec, queue_capacity=12000, seed=lane,
                        track_top=TOP)
        for t in streams:
            p.add(t)
        p.queue_append_rows([0, 1], [streams[t][owner[t] == s]
                                     for t in streams])
        p.flush()
        refs.append(p)
    return owner, refs


def answers(svc):
    return {t: np.asarray(svc.query(t, np.unique(s))) for t, s in
            streams.items()}


def ref_answers(owner, refs):
    got = {}
    for row, (t, s) in enumerate(streams.items()):
        u = np.unique(s)
        own = np.asarray(sharded.route_of(jnp.asarray(u), N))
        got[t] = np.zeros(u.size, np.float32)
        for sh, p in enumerate(refs):
            est = np.asarray(p.query_row(row, jnp.asarray(u)))
            got[t][own == sh] = est[own == sh]
    return got


# ---- linear cells: bit-identical where every key owns a cell ----
lin = SketchSpec(width=1 << 17, depth=2,
                 counter=CounterSpec(kind="linear", bits=32), seed=3)
svc = routed(lin)
owner, refs = by_shard(lin)
private = True
rs = jnp.asarray(host_row_seeds(lin.seed, lin.depth), jnp.uint32)
for t, s in streams.items():
    for sh in range(N):
        u = np.unique(s[owner[t] == sh])
        cols = np.asarray(row_hashes(jnp.asarray(u), rs, lin.width))
        hits = [np.bincount(c, minlength=lin.width)[c] == 1 for c in cols]
        private &= bool(np.logical_or.reduce(hits).all())
tables = np.asarray(svc.planes[0].tables)
out["linear_private_cells"] = private
out["linear_tables_equal"] = all(
    np.array_equal(tables[sh], np.asarray(p.tables))
    for sh, p in enumerate(refs))
got, want = answers(svc), ref_answers(owner, refs)
out["linear_answers_equal"] = all(np.array_equal(got[t], want[t])
                                  for t in streams)
exact = {t: np.unique(s, return_counts=True)[1] for t, s in streams.items()}
out["linear_exact"] = all(np.array_equal(got[t], exact[t]) for t in streams)

# ---- Morris cells (the paper's CMLS16): within the counter's spread ----
log = SketchSpec(width=1 << 15, depth=2, counter=CounterSpec(), seed=3)
svc = routed(log)
owner, refs = by_shard(log)
got, want = answers(svc), ref_answers(owner, refs)
b = log.counter.base
out["morris_vs_shards"] = max(
    float(np.max(np.abs(got[t] - want[t])
                 / (4 * np.sqrt(b - 1) * exact[t] + 1)))
    for t in streams)
out["morris_vs_exact"] = max(
    float(np.max(np.abs(got[t] - exact[t])
                 / (4 * np.sqrt((b - 1) / 2) * exact[t] + 1)))
    for t in streams)
out["morris_are"] = max(float(np.mean(np.abs(got[t] - exact[t]) / exact[t]))
                        for t in streams)
tables = np.asarray(svc.planes[0].tables)
out["morris_cells_touched_equal"] = all(
    np.array_equal(tables[sh] > 0, np.asarray(p.tables) > 0)
    for sh, p in enumerate(refs))

# ---- top-k: one device's top-k of the union, and the query answers ----
one = CountService(queue_capacity=12000, seed=SEED, track_top=TOP)
topk_ok, topk_is_query = True, True
for t, s in streams.items():
    one.add_tenant(t, spec=log)
    one.enqueue(t, s)
    u, c = np.unique(s, return_counts=True)
    k_routed, e_routed = svc.topk(t)
    k_one, _ = one.topk(t)
    top = u[np.argsort(-c, kind="stable")[:TOP]]
    topk_ok &= (set(k_routed.tolist()) == set(k_one.tolist())
                == set(top.tolist()))
    topk_is_query &= bool(np.array_equal(
        e_routed, np.asarray(svc.query(t, k_routed))))
out["topk_matches_one_device"] = bool(topk_ok)
out["topk_estimates_are_answers"] = bool(topk_is_query)
every = svc.query_all(np.unique(streams["a"]))
out["query_all_is_query"] = all(
    np.array_equal(np.asarray(every[t]),
                   np.asarray(svc.query(t, np.unique(streams["a"]))))
    for t in streams)

m = svc.metrics
out["routed_keys"] = m.counter("routed_keys", plane="p0").value
out["routed_dropped"] = m.counter("routed_dropped", plane="p0").value
out["skew"] = m.gauge("routed_shard_keys_max", plane="p0").value
out["events"] = int(sum(s.size for s in streams.values()))
out["flushes"] = m.counter("plane_flushes", plane="p0").value
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_shards():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={SHARDS}")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(FOUR_SHARDS)],
                         capture_output=True, text=True, env=env, cwd=root,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_linear_cells_bit_identical_to_one_device_planes(four_shards):
    assert four_shards["linear_private_cells"]
    assert four_shards["linear_tables_equal"]
    assert four_shards["linear_answers_equal"]
    assert four_shards["linear_exact"]


def test_morris_cells_within_the_counters_spread(four_shards):
    # every answer within 4 standard deviations of the counter's own
    # noise, of the shard planes' answers and of the exact counts; the
    # same cells touched
    assert four_shards["morris_vs_shards"] <= 1.0
    assert four_shards["morris_vs_exact"] <= 1.0
    assert four_shards["morris_are"] < 0.01
    assert four_shards["morris_cells_touched_equal"]


def test_topk_is_the_one_device_topk_of_the_union(four_shards):
    assert four_shards["topk_matches_one_device"]
    assert four_shards["topk_estimates_are_answers"]
    assert four_shards["query_all_is_query"]


def test_routing_counters(four_shards):
    assert four_shards["routed_keys"] == four_shards["events"]
    assert four_shards["routed_dropped"] == 0
    assert four_shards["skew"] >= 1.0
    assert four_shards["flushes"] >= 10


# ---- one process, one device ----

def _mesh():
    return Mesh(np.asarray(jax.devices()[:1]), ("keys",),
                axis_types=(AxisType.Auto,))


def test_inplace_update_score_rows_is_the_xla_engine():
    """The in-place engine, inside a traced program as the routed epoch
    runs it, is bit-identical to the jitted XLA engine (duplicates, zero
    weights and chunks of no-ops included)."""
    spec = SketchSpec(width=4096, depth=2,
                      counter=CounterSpec(kind="log", base=1.08, bits=8))
    rng = np.random.default_rng(0)
    tables = jnp.asarray(rng.integers(0, 5, (5, 2, 4096)).astype(np.uint8))
    keys = jnp.asarray((rng.zipf(1.3, (3, 3000)) % 3000).astype(np.uint32))
    weights = jnp.asarray((rng.random((3, 3000)) < 0.7).astype(np.float32))
    weights = weights.at[:, :1500].set(0.0)
    key = jnp.asarray([7, 3], jnp.uint32)
    rows = np.array([4, 0, 2], np.int32)
    cand = keys[:, :64]
    want = ops.update_score_rows(tables, spec, keys, key, rows, cand,
                                 weights=weights)
    got = jax.jit(lambda t, k, w, r, c: ops.update_score_rows(
        t, spec, k, key, r, c, weights=w, engine="inplace"))(
            tables, keys, weights, jnp.asarray(rows), cand)
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])
    assert not np.array_equal(got[0], tables)


def test_route_rows_lands_every_entry_with_its_owner():
    rng = np.random.default_rng(1)
    keys = jnp.asarray(rng.integers(0, 2 ** 32, (3, 512), dtype=np.uint32))
    weights = jnp.asarray(rng.integers(0, 3, (3, 512)).astype(np.float32))
    send_k, send_w, dropped = sharded.route_rows(keys, weights, SHARDS)
    assert send_k.shape == (SHARDS, 3, 512) and float(dropped) == 0.0
    send_k, send_w = np.asarray(send_k), np.asarray(send_w)
    owner = np.asarray(sharded.route_of(keys, SHARDS))
    for r in range(3):
        live = np.asarray(weights[r]) > 0
        for s in range(SHARDS):
            mine = live & (owner[r] == s)
            n = int(mine.sum())
            assert send_k[s, r, :n].tolist() == np.asarray(
                keys[r])[mine].tolist()
            assert send_w[s, r, :n].tolist() == np.asarray(
                weights[r])[mine].tolist()
            assert not send_w[s, r, n:].any()


def test_shard_lanes_differ():
    lanes = sharded.shard_seeds(11, SHARDS)
    assert len(set(lanes)) == SHARDS
    assert sharded.shard_seeds(11, SHARDS) == lanes


def test_one_shard_plane_counts_exactly():
    """A routed plane on a one-device mesh: linear cells over a wide table
    count exactly, through pressure epochs, explicit flushes and reads."""
    spec = SketchSpec(width=1 << 16, depth=2,
                      counter=CounterSpec(kind="linear", bits=32))
    svc = CountService(queue_capacity=700, seed=2, track_top=4, mesh=_mesh())
    svc.add_tenant("x", spec=spec)
    keys = (np.random.default_rng(2).zipf(1.4, 3000) % 400).astype(np.uint32)
    for i in range(0, 3000, 500):
        svc.enqueue_many({"x": keys[i:i + 500]})
    u, c = np.unique(keys, return_counts=True)
    assert np.array_equal(np.asarray(svc.query("x", u)), c)
    got, est = svc.topk("x")
    assert got.tolist() == u[np.argsort(-c, kind="stable")[:4]].tolist()
    assert svc.metrics.counter("routed_keys", plane="p0").value == 3000


def test_routed_service_refuses_what_it_does_not_support(tmp_path):
    spec = SketchSpec(width=1 << 12, depth=2)
    with pytest.raises(ValueError, match="tiered"):
        CountService(mesh=_mesh(), tier=TierSpec(max_hot_tenants=2))
    svc = CountService(queue_capacity=64, mesh=_mesh())
    with pytest.raises(ValueError, match="windows"):
        svc.add_tenant("w", window=WindowSpec(sketch=spec, buckets=2))
    with pytest.raises(ValueError, match="packed"):
        svc.add_tenant("p", spec=SketchSpec(width=1 << 12, packed=True))
    svc.add_tenant("x", spec=spec)
    with pytest.raises(ValueError, match="split over"):
        svc.sketch_of("x")
    with pytest.raises(ValueError, match="snapshot"):
        svc.snapshot(str(tmp_path), 0)
    with pytest.raises(ValueError, match="32 bits"):
        svc.enqueue("x", np.array([2 ** 32], np.int64))
