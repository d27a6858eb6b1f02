"""Serve-path epoch scheduler: scoped dirty-plane flush + one-launch reads.

Two contracts under test:

BIT-EQUALITY — a service whose read ops flush only the plane they touch
(`CountService._flush_plane`) must answer every read identically to the
pre-scheduler always-full-flush service, because a plane's tables depend
only on how its enqueued batches GROUP into flush epochs (queue content
at flush + that flush's PRNG draw), never on when other planes flush;
skipping a clean plane's epoch consumes no draw and is indistinguishable
from landing an empty one.  `FullFlushService` reconstructs the old
behavior by overriding the single scoping point, and the parity matrix
sweeps traffic regimes x packed cell formats x tiered/windowed planes.

DISPATCH SCOPING — launch audits prove the scheduler's structure: a read
on a clean service issues ZERO update dispatches, a read never flushes
ANOTHER plane's dirty ring, `query_all` answers W windowed tenants in
ONE row-stacked `window_query_stacked` dispatch (bit-identical to the W
per-ring queries it replaced), and `enqueue`'s queue-pressure fallback
flushes only the owning plane.
"""
import numpy as np
import pytest

from repro.core import CMLS8, CMLS16, CMS32, SketchSpec
from repro.core.admission import AdmissionSpec
from repro.kernels import ops
from repro.stream import CountService, TierSpec, WindowSpec

WIDTH = 256
PROBES = np.arange(32, dtype=np.uint32)


class FullFlushService(CountService):
    """The pre-scheduler oracle: every scoped flush sweeps every plane."""

    def _flush_plane(self, plane, reason):
        return self.flush()


def _spec(counter=CMLS16, **kw):
    return SketchSpec(width=WIDTH, depth=2, counter=counter, **kw)


def _batch(rng, n=300, vocab=5_000):
    return (rng.zipf(1.3, n) % vocab).astype(np.uint32)


def _groups(regime: str, names, rounds: int):
    """Per-round active tenant groups for the three traffic regimes."""
    t = len(names)
    if regime == "uniform":
        return [list(names)] * rounds
    if regime == "hot1":
        return [[names[0]]] * rounds
    return [[names[(2 * r + i) % t] for i in range(3)]
            for r in range(rounds)]  # churn: shifting working set


def _mixed_pair(cls_a=CountService, cls_b=FullFlushService, counter=CMLS16,
                packed=False, tier=None, track_top=4):
    """Two same-seed services with two sketch planes + tenants split
    across them (the geometry where scoped vs full flush differ)."""
    spec = _spec(counter, packed=packed)
    spec2 = SketchSpec(width=128, depth=2, counter=CMS32)
    out = []
    for cls in (cls_a, cls_b):
        svc = cls(spec, tenants=["a0", "a1", "a2"], queue_capacity=2048,
                  seed=5, track_top=track_top, tier=tier)
        svc.add_tenant("b0", spec=spec2)
        svc.add_tenant("b1", spec=spec2)
        out.append(svc)
    return out


def _drive_rounds(scoped, full, names, regime, rounds=5, seed=11):
    """Identical round-structured streams: enqueue to the round's group,
    then read EVERY tenant enqueued this round (per-tenant `query` — the
    scoped service flushes each dirty plane through its own read; the
    full-flush oracle sweeps everything at the first).  Reads are
    asserted bit-equal along the way, not just at the end."""
    rng = np.random.default_rng(seed)
    for group in _groups(regime, names, rounds):
        events = {n: _batch(rng) for n in group}
        scoped.enqueue_many(events)
        full.enqueue_many(events)
        for n in group:
            ea = np.asarray(scoped.query(n, PROBES))
            eb = np.asarray(full.query(n, PROBES))
            np.testing.assert_array_equal(ea, eb,
                                          err_msg=f"query diverged on {n}")


def _assert_parity(scoped, full, names, k=3):
    a, b = scoped.query_all(PROBES), full.query_all(PROBES)
    for n in names:
        np.testing.assert_array_equal(np.asarray(a[n]), np.asarray(b[n]),
                                      err_msg=f"query_all diverged on {n}")
        ka, va = scoped.topk(n, k)
        kb, vb = full.topk(n, k)
        np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb),
                                      err_msg=f"topk keys diverged on {n}")
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                      err_msg=f"topk estimates diverged "
                                              f"on {n}")


# --------------------------------------------------------------------------
# scoped flush == full flush, bit for bit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("regime", ["uniform", "hot1", "churn"])
def test_scoped_flush_matches_full_flush(regime):
    scoped, full = _mixed_pair()
    names = scoped.tenants
    _drive_rounds(scoped, full, names, regime)
    _assert_parity(scoped, full, names)


@pytest.mark.parametrize("counter", [CMS32, CMLS16, CMLS8])
def test_scoped_flush_matches_full_flush_packed(counter):
    scoped, full = _mixed_pair(counter=counter, packed=True)
    names = scoped.tenants
    _drive_rounds(scoped, full, names, "churn")
    _assert_parity(scoped, full, names)


@pytest.mark.parametrize("regime", ["uniform", "churn"])
def test_scoped_flush_matches_full_flush_tiered(regime):
    """Cold tenants must stay bit-identical under scoped flush: the
    spill epochs regroup exactly like the resident ones."""
    scoped, full = _mixed_pair(tier=TierSpec(max_hot_tenants=2))
    names = scoped.tenants
    _drive_rounds(scoped, full, names, regime)
    _assert_parity(scoped, full, names)


def test_scoped_flush_matches_full_flush_windowed():
    """Watermark rotation's flush callback is scoped to the window plane;
    the rotation-triggered epoch must regroup identically."""
    spec = _spec()
    wspec = WindowSpec(sketch=spec, buckets=4, interval=10.0)
    svcs = []
    for cls in (CountService, FullFlushService):
        svc = cls(spec, tenants=["p0"], queue_capacity=2048, seed=5,
                  track_top=4)
        svc.add_tenant("w0", window=wspec)
        svc.add_tenant("w1", window=wspec)
        svcs.append(svc)
    scoped, full = svcs
    rng = np.random.default_rng(23)
    ts = 0.0
    for r in range(6):
        ts += 4.0 if r % 2 else 11.0  # alternate same-interval / crossing
        for svc in (scoped, full):
            svc.enqueue("p0", _batch(rng := np.random.default_rng(100 + r)))
            svc.enqueue("w0", _batch(rng), ts=ts)
            svc.enqueue("w1", _batch(rng), ts=ts * 0.7)
        for n in ("p0", "w0", "w1"):
            np.testing.assert_array_equal(
                np.asarray(scoped.query(n, PROBES)),
                np.asarray(full.query(n, PROBES)),
                err_msg=f"query diverged on {n} at round {r}")
    _assert_parity(scoped, full, ["p0", "w0", "w1"])


# --------------------------------------------------------------------------
# read-your-writes + dispatch scoping
# --------------------------------------------------------------------------

def _update_ops(tally) -> dict:
    """The dispatch tallies that mutate plane state (a read on a clean
    or foreign plane must produce none of these)."""
    mutating = ("update_many", "update_rows", "update_score_rows",
                "tier_spill", "tier_promote", "tier_demote",
                "window_advance_rows", "queue_append")
    return {op: n for op, n in tally.items() if op in mutating}


def test_read_your_writes_scoped_to_own_plane():
    scoped, _ = _mixed_pair(cls_b=CountService)
    rng = np.random.default_rng(7)
    keys = np.full(257, 42, np.uint32)
    scoped.enqueue("a0", keys)
    scoped.enqueue("b0", _batch(rng))
    other = scoped._lookup("b0")[0]
    before = other.pending()
    assert before > 0
    est = np.asarray(scoped.query("a0", np.asarray([42], np.uint32)))
    assert est[0] > 0, "pending writes must be visible to same-plane query"
    assert other.pending() == before, \
        "a read must leave other planes' rings buffered"
    # ... and the other plane's writes are still there for ITS read
    with ops.audit_scope() as tally:
        scoped.query("b0", PROBES)
    assert any(op.startswith("update") for op in tally), \
        "the deferred plane flushes on its own read"
    assert other.pending() == 0


def test_read_your_writes_topk_admit():
    spec = _spec()
    svc = CountService(spec, tenants=["a0"], queue_capacity=2048, seed=5,
                       track_top=4)
    svc.add_tenant("adm", admission=AdmissionSpec(
        threshold=8.0, n_fallback=64, table_rows=1 << 10))
    svc.add_tenant("m", spec=SketchSpec(width=128, depth=2, counter=CMS32))
    m_plane = svc._lookup("m")[0]
    rng = np.random.default_rng(9)
    svc.enqueue("m", _batch(rng))
    dirty = m_plane.pending()
    svc.enqueue("a0", np.full(300, 7, np.uint32))
    keys, est = svc.topk("a0", 2)
    assert 7 in np.asarray(keys), "pending writes must reach topk"
    svc.enqueue("adm", np.full(300, 9, np.uint32))
    rows, admitted = svc.admit("adm", np.asarray([9], np.uint32))
    assert bool(np.asarray(admitted)[0]), \
        "pending writes must reach admission decisions"
    assert m_plane.pending() == dirty, \
        "topk/admit reads must not flush other planes"


def test_clean_read_zero_update_dispatches():
    scoped, _ = _mixed_pair(cls_b=CountService)
    rng = np.random.default_rng(13)
    scoped.enqueue_many({n: _batch(rng) for n in scoped.tenants})
    scoped.flush()
    assert scoped.dirty_planes == []
    for read in (lambda: scoped.query("a0", PROBES),
                 lambda: scoped.query_all(PROBES),
                 lambda: scoped.topk("a1", 2),
                 lambda: scoped.sketch_of("b0")):
        with ops.audit_scope() as tally:
            read()
        assert _update_ops(tally) == {}, \
            f"clean read dispatched mutations: {dict(tally)}"


def test_enqueue_pressure_flushes_owning_plane_only():
    spec = _spec()
    svc = CountService(spec, tenants=["a0"], queue_capacity=256, seed=5)
    svc.add_tenant("m", spec=SketchSpec(width=128, depth=2, counter=CMS32))
    rng = np.random.default_rng(15)
    svc.enqueue("m", _batch(rng, n=100))
    m_plane = svc._lookup("m")[0]
    dirty = m_plane.pending()
    svc.enqueue("a0", _batch(rng, n=900))  # 3.5x the ring: pressure flush
    assert m_plane.pending() == dirty, \
        "queue-pressure flush must scope to the owning plane"
    a_plane = svc._lookup("a0")[0]
    assert a_plane.pending() > 0  # the tail past the last pressure flush


def test_dirty_planes_tracks_pending():
    svc, _ = _mixed_pair(cls_b=CountService)
    assert svc.dirty_planes == []
    rng = np.random.default_rng(17)
    svc.enqueue("a0", _batch(rng))
    assert [p.label for p in svc.dirty_planes] == \
        [svc._lookup("a0")[0].label]
    svc.flush()
    assert svc.dirty_planes == []


# --------------------------------------------------------------------------
# one-launch windowed query_all
# --------------------------------------------------------------------------

def _windowed_service(n=3, packed=False, tier=None, buckets=4):
    spec = _spec(packed=packed)
    wspec = WindowSpec(sketch=spec, buckets=buckets, interval=10.0)
    svc = CountService(queue_capacity=2048, seed=5, tier=tier)
    for i in range(n):
        svc.add_tenant(f"w{i}", window=wspec)
    return svc, [f"w{i}" for i in range(n)]


@pytest.mark.parametrize("packed", [False, True])
def test_windowed_query_all_single_launch(packed):
    svc, names = _windowed_service(packed=packed)
    rng = np.random.default_rng(19)
    # stagger the cursors: tenants rotate different step counts, so the
    # stacked weight rows genuinely differ per tenant
    for i, n in enumerate(names):
        svc.enqueue(n, _batch(rng), ts=10.5 * (i + 1))
        svc.enqueue(n, _batch(rng), ts=10.5 * (i + 2))
    svc.flush()
    with ops.audit_scope() as tally:
        out = svc.query_all(PROBES)
    assert tally.get("window_query_stacked") == 1, \
        f"W windowed tenants must answer in ONE stacked launch: " \
        f"{dict(tally)}"
    assert "window_query" not in tally
    for i, n in enumerate(names):
        np.testing.assert_array_equal(
            np.asarray(out[n]), np.asarray(svc.query(n, PROBES)),
            err_msg=f"stacked query_all diverged from query on {n}")


def test_windowed_query_all_per_tenant_probes():
    svc, names = _windowed_service()
    svc.add_tenant("p0", spec=_spec())
    rng = np.random.default_rng(21)
    for i, n in enumerate(names):
        svc.enqueue(n, _batch(rng), ts=3.0 * (i + 1))
    svc.enqueue("p0", _batch(rng))
    probes = np.stack([(PROBES + 17 * i).astype(np.uint32)
                       for i in range(len(svc.tenants))])
    out = svc.query_all(probes)
    row_of = {n: i for i, n in enumerate(svc.tenants)}
    for n in svc.tenants:
        np.testing.assert_array_equal(
            np.asarray(out[n]),
            np.asarray(svc.query(n, probes[row_of[n]])),
            err_msg=f"per-tenant probes diverged on {n}")


def test_windowed_query_all_tiered_matches_per_tenant():
    """Hot tenants answer off the device leaf, cold off uploaded host
    leaves — both through the stacked query family, all bit-identical
    to the per-tenant read path."""
    svc, names = _windowed_service(n=5, tier=TierSpec(max_hot_tenants=2))
    rng = np.random.default_rng(25)
    ts = 0.0
    for r in range(3):
        ts += 10.5
        for n in names:
            svc.enqueue(n, _batch(rng), ts=ts)
    out = svc.query_all(PROBES)
    assert svc.planes[0].tier.cold_count > 0
    for n in names:
        np.testing.assert_array_equal(
            np.asarray(out[n]), np.asarray(svc.query(n, PROBES)),
            err_msg=f"tiered stacked query_all diverged on {n}")


# --------------------------------------------------------------------------
# one program per plain read: `TenantPlane.query_row` / `ops.query_row`
# --------------------------------------------------------------------------

READ_SPECS = {
    "cms32": SketchSpec(width=1024, depth=2, counter=CMS32),
    "log16": SketchSpec(width=1024, depth=2, counter=CMLS16),
    "log16_packed": SketchSpec(width=1024, depth=2, counter=CMLS16,
                               packed=True),
    "log8_packed": SketchSpec(width=1024, depth=2, counter=CMLS8,
                              packed=True),
}
READ_NAMES = [f"r{i}" for i in range(7)]
_FILLED: dict = {}


def _filled(spec_id: str, tiered: bool) -> CountService:
    """A flushed seven-tenant service (tiered: three hot slots), built
    once per spec and layout."""
    key = (spec_id, tiered)
    if key not in _FILLED:
        svc = CountService(READ_SPECS[spec_id], tenants=READ_NAMES,
                           queue_capacity=2048, seed=3, track_top=4,
                           tier=TierSpec(max_hot_tenants=3) if tiered
                           else None)
        rng = np.random.default_rng(17)
        for r in range(3):
            group = READ_NAMES if r == 0 else READ_NAMES[r::2]
            svc.enqueue_many({n: _batch(rng, 500, 3_000) for n in group})
            svc.flush()
        _FILLED[key] = svc
    return _FILLED[key]


def _tenant_at(svc: CountService, placement: str, position: str) -> str:
    """The first, middle or last tenant of a placement: every row of an
    all-resident plane, or the hot slots / cold tenants of a tiered one."""
    plane = svc.planes[0]
    if placement == "resident":
        rows = list(range(len(plane.names)))
    else:
        hot = plane.tier.slot >= 0
        rows = list(np.flatnonzero(hot if placement == "hot" else ~hot))
    assert rows, f"no {placement} tenant"
    pick = {"first": 0, "middle": len(rows) // 2, "last": -1}[position]
    return plane.names[int(rows[pick])]


@pytest.fixture
def as_tpu(monkeypatch):
    """Engine selection as it runs on a TPU backend."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


@pytest.mark.parametrize("engine", ["xla", "kernel"])
@pytest.mark.parametrize("position", ["first", "middle", "last"])
@pytest.mark.parametrize("placement", ["resident", "hot", "cold"])
@pytest.mark.parametrize("spec_id", sorted(READ_SPECS))
def test_plain_read_bit_identical_to_eager_query(monkeypatch, spec_id,
                                                 placement, position,
                                                 engine):
    """A plain tenant's read is byte for byte the eager `sk.query` over its
    table, on either engine, for every cell format and tier placement."""
    import jax.numpy as jnp
    from repro.core import sketch as sk
    svc = _filled(spec_id, placement != "resident")
    name = _tenant_at(svc, placement, position)
    plane, row = svc._lookup(name)
    keys = np.arange(0, 3_000, 7, dtype=np.uint32)
    want = sk.query(sk.Sketch(table=plane.table_row(row), spec=plane.spec),
                    jnp.asarray(keys))
    monkeypatch.setattr(ops, "on_tpu", lambda: engine == "xla")
    with ops.audit_scope() as tally:
        got = svc.query(name, keys)
    assert dict(tally) == {"query": 1}
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype == np.float32
    assert got.tobytes() == want.tobytes(), f"{name} diverged"
    assert float(want.max()) > 0


def test_clean_read_is_one_xla_program(as_tpu):
    """On the TPU engine a clean read is exactly one `query` dispatch on
    the XLA engine; a read of a dirty plane lands its epoch first and
    sees its writes."""
    svc = CountService(_spec(), tenants=["a0", "a1"], queue_capacity=2048,
                       seed=5, track_top=4)
    rng = np.random.default_rng(3)
    svc.enqueue_many({n: _batch(rng) for n in svc.tenants})
    svc.flush()
    scope = ops.audit_scope()
    with scope:
        svc.query("a1", PROBES)
    assert dict(scope.engines) == {("query", "xla"): 1}
    assert _update_ops(scope.tally) == {}

    svc.enqueue("a0", np.full(300, 1234, np.uint32))
    scope = ops.audit_scope()
    with scope:
        est = np.asarray(svc.query("a0", np.asarray([1234], np.uint32)))
    assert scope.engines[("query", "xla")] == 1
    assert _update_ops(scope.tally) == {"update_score_rows": 1}
    assert est[0] >= 250, "the read must see its plane's pending writes"
    assert svc.dirty_planes == []


@pytest.mark.parametrize("packed", [False, True])
def test_plain_reads_share_one_compile(as_tpu, packed):
    """Every row of a 16-tenant plane, read twice over, runs one compiled
    program: the row is a traced argument, not part of the shape."""
    spec = SketchSpec(width=1152, depth=3, counter=CMLS16, packed=packed)
    names = [f"c{i:02d}" for i in range(16)]
    svc = CountService(spec, tenants=names, queue_capacity=1024, seed=2)
    rng = np.random.default_rng(8)
    svc.enqueue_many({n: _batch(rng, 200) for n in names})
    svc.flush()
    probes = np.arange(41, dtype=np.uint32)
    before = ops._query_row_xla._cache_size()
    for _ in range(2):
        for n in names:
            svc.query(n, probes)
    assert ops._query_row_xla._cache_size() - before == 1
