"""TPU v5e compiles of the engines "auto" selects on TPU, and a guard that
no "auto" path reaches a Pallas kernel there.

The compile tests lower each main-path op for a DESCRIBED v5e chip
(`jax.experimental.topologies`, nothing runs) at the serving state size:
w = 2^22, d = 4 CMLS16 tables, 17 tenants, 4096-key batches.  The op is
traced with `ops.on_tpu` reporting a TPU, so what compiles is exactly the
engine a chip would select.  The topology is described inside a module
fixture, never at import: only one process may load the TPU compiler at a
time, and a worker that describes it at collection would change which
tests its siblings collect.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import CMLS16, CMS32, SketchSpec
from repro.kernels import ops

WIDTH = 1 << 22          # 4 x 2^22 CMLS16 cells: past the VMEM budget
VMEM_WIDTH = 1 << 20     # 4 x 2^20 CMLS16 cells: within it
DEPTH = 4
TENANTS = 17
BATCH = 4096
CAND = 16 + BATCH        # tracked heap + the flushed batch
BUCKETS = 8
CAPW = ops.ring_width(8192)

PALLAS_WRAPPERS = ("fused_query_pallas", "fused_update_pallas",
                   "fused_update_rows_pallas", "fused_update_score_pallas",
                   "query_pallas", "queue_append_dense_pallas",
                   "queue_append_pallas", "update_pallas",
                   "window_query_pallas", "window_query_stacked_pallas",
                   "window_query_stacked_rows_pallas")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Engine selection as it runs on a TPU backend."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _spec(width=WIDTH):
    return SketchSpec(width=width, depth=DEPTH, counter=CMLS16)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    scope = ops.audit_scope()
    with scope:
        compiled = jax.jit(fn).lower(*args).compile()
    return compiled, dict(scope.engines)


def _tables(spec, n=TENANTS):
    return (n, spec.depth, spec.storage_width), spec.storage_dtype


RNG = ((2,), jnp.uint32)


def test_update_score_rows_compiles(as_tpu, one_chip):
    spec, rows = _spec(), np.arange(TENANTS)
    _, engines = _compile(
        lambda t, k, w, r, c: ops.update_score_rows(
            t, spec, k, r, rows, c, weights=w),
        one_chip, _tables(spec), ((TENANTS, BATCH), jnp.uint32),
        ((TENANTS, BATCH), jnp.float32), RNG, ((TENANTS, CAND), jnp.uint32))
    assert engines == {("update_score_rows", "xla"): 1}


@pytest.mark.parametrize("width,engine", [(VMEM_WIDTH, "xla"),
                                          (WIDTH, "jnp")])
def test_update_rows_compiles(as_tpu, one_chip, width, engine):
    """Within VMEM the new chunk-sequential XLA engine, past it the jnp
    update (the window leaf's flush at serving width)."""
    spec, rows = _spec(width), np.arange(0, TENANTS * BUCKETS, BUCKETS)
    _, engines = _compile(
        lambda t, k, w, r: ops.update_rows(t, spec, k, r, rows, weights=w,
                                           uniform_rows=(TENANTS,
                                                         rows // BUCKETS)),
        one_chip, _tables(spec, TENANTS * BUCKETS),
        ((TENANTS, BATCH), jnp.uint32), ((TENANTS, BATCH), jnp.float32), RNG)
    assert engines == {("update_rows", engine): 1}


def test_update_rows_xla_engine_compiles_at_serving_width(one_chip):
    spec = _spec()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        _tables(spec), ((TENANTS, BATCH), jnp.uint32),
        ((TENANTS, BATCH), jnp.float32), RNG, ((TENANTS,), jnp.int32),
        ((TENANTS,), jnp.int32))]
    ops._update_rows_xla_donated_jit.lower(*args, spec=spec,
                                           total=TENANTS).compile()


@pytest.mark.parametrize("dense", [True, False])
def test_queue_append_compiles(as_tpu, one_chip, dense):
    n = TENANTS if dense else 3
    rows = np.arange(n, dtype=np.int32)
    fill = np.zeros(n, np.int32) if dense else np.full(n, 128, np.int32)
    count = np.full(n, BATCH, np.int32)
    _, engines = _compile(
        lambda q, k: ops.queue_append(q, k, rows, fill, count),
        one_chip, ((TENANTS, CAPW), jnp.uint32), ((n, BATCH), jnp.uint32))
    assert engines == {("queue_append", "xla"): 1}


@pytest.mark.parametrize("spec", [_spec(), SketchSpec(width=1024, depth=2,
                                                      counter=CMS32)],
                         ids=["cmls16_w2^22", "cms32_w1024"])
def test_query_stacked_compiles(as_tpu, one_chip, spec):
    _, engines = _compile(lambda t, k: ops.query_many(t, spec, k), one_chip,
                          _tables(spec), ((TENANTS, 8), jnp.uint32))
    assert engines == {("query_many", "jnp"): 1}


def test_query_row_compiles_at_cell_shapes(as_tpu, one_chip):
    """A plain tenant's read at the PMI deployment's shapes (64 tenants of
    2 x 2^20 CMLS16 cells, 3,072 probes) is one XLA program that gathers
    straight from the stack: no row is sliced out first."""
    spec = SketchSpec(width=1 << 20, depth=2, counter=CMLS16)
    compiled, engines = _compile(
        lambda t, r, k: ops.query_row(t, spec, r, k), one_chip,
        _tables(spec, 64), ((), jnp.int32), ((3072,), jnp.uint32))
    assert engines == {("query", "xla"): 1}
    assert "dynamic-slice" not in compiled.as_text()


def test_window_query_stacked_rows_compiles(as_tpu, one_chip):
    spec = _spec()
    leaf = ((1, BUCKETS, spec.depth, spec.storage_width), spec.storage_dtype)
    _, engines = _compile(
        lambda t, k, w: ops.window_query_stacked(t, spec, k, w, rows=[0]),
        one_chip, leaf, ((1, CAND), jnp.uint32),
        ((1, BUCKETS), jnp.float32))
    assert engines == {("window_query_stacked", "xla"): 1}


def test_window_advance_rows_compiles(one_chip):
    spec = _spec()
    leaf = ((1, BUCKETS, spec.depth, spec.storage_width), spec.storage_dtype)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (leaf, ((1,), jnp.int32), ((1,), jnp.int32))]
    ops._window_advance_rows_jit.lower(*args).compile()


def test_flush_rows_inputs_compiles(one_chip):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((TENANTS, CAPW), jnp.uint32), ((TENANTS,), jnp.int32),
        ((TENANTS,), jnp.int32))]
    ops.flush_rows_inputs.lower(*args, cols=BATCH).compile()


@pytest.mark.parametrize("score", [False, True])
def test_tier_spill_compiles(one_chip, score):
    spec, cold = _spec(), 4
    urows = np.arange(cold) + TENANTS

    def spill(t, k, w, r, *cand):
        return ops.tier_spill(t, spec, k, r, w, (TENANTS + cold, urows),
                              *cand)

    shapes = [_tables(spec, cold), ((cold, BATCH), jnp.uint32),
              ((cold, BATCH), jnp.float32), RNG]
    if score:
        shapes.append(((cold, CAND), jnp.uint32))
    _, engines = _compile(spill, one_chip, *shapes)
    assert engines == {("tier_spill", "xla"): 1}


def test_auto_never_reaches_pallas_on_tpu(as_tpu, monkeypatch):
    """With the backend reported as a TPU, the serving stack and every
    "auto" op wrapper run without calling a single Pallas wrapper."""
    from repro.core import admission, init
    from repro.core import sketch as sk
    from repro.stream import CountService, TierSpec, WindowSpec

    def refuse(*a, **k):
        raise AssertionError("a Pallas kernel was reached on TPU")
    for name in PALLAS_WRAPPERS:
        monkeypatch.setattr(ops, name, refuse)

    small = SketchSpec(width=1024, depth=2, counter=CMLS16)
    assert ops.fits_vmem(small)
    rng = np.random.default_rng(0)
    scope = ops.audit_scope()
    with scope:
        svc = CountService(small, tenants=["a", "b", "c"], queue_capacity=512,
                           track_top=4, tier=TierSpec(max_hot_tenants=2))
        svc.add_tenant("w", window=WindowSpec(sketch=small, buckets=3,
                                              interval=60.0))
        svc.add_tenant("m", spec=SketchSpec(width=512, depth=2,
                                            counter=CMS32))
        for step in range(4):
            svc.enqueue_many({t: rng.integers(0, 50, 64).astype(np.uint32)
                              for t in ("a", "b", "c", "m")})
            svc.enqueue("w", rng.integers(0, 50, 64).astype(np.uint32),
                        ts=60.0 * step)
            svc.enqueue("a", rng.integers(0, 50, 7).astype(np.uint32))
        probe = np.arange(8, dtype=np.uint32)
        svc.query_all(probe)
        svc.topk("a", 3)
        svc.topk("w", 3)
        svc.query("w", probe, gamma=0.5)
        for t in ("a", "b", "c", "m"):     # hot slots, a cold tenant
            svc.query(t, probe)

        s = init(small)
        keys = jnp.asarray(probe)
        s = ops.update(s, keys, jax.random.PRNGKey(0))
        ops.query(s, keys)
        stack = jnp.stack([s.table] * 2)
        ops.query_many(stack, small, keys)
        ops.update_many(stack, small, jnp.stack([keys] * 2),
                        jax.random.PRNGKey(1))
        ops.window_query_tables(stack, small, keys, jnp.ones(2))
        admission.observe_and_admit(s, keys, jax.random.PRNGKey(2),
                                    admission.AdmissionSpec(threshold=1.0))
        sk.query(s, keys)
    engines = {eng for (_, eng) in scope.engines}
    assert "kernel" not in engines and engines <= {"xla", "jnp"}
