"""Telemetry plane: registry, scoped dispatch tallies, spans, SLO probes.

Covers the contracts the observability subsystem promises:

  * the registry's instruments, snapshot/load identity, and the
    host-side shard merge (`merge_snapshots`);
  * `ops.audit_scope` isolation (including the Counter-equality pitfall
    list.remove would have) and the legacy launch_counts wrappers;
  * the tracked flush epoch auditing as ONE `update_score_rows`
    dispatch under a scoped tally;
  * the service's `cms.*` host spans in a CPU `jax.profiler` trace:
    nested by cause, with the counts sent, never blocking and adding no
    kernel launches (spy-tested), `serve_counts --trace-out` included;
  * probe exactness + ARE-by-decile, and the accuracy envelope gate
    tripping when a table is corrupted;
  * service metrics (stats parity, ring/watermark gauges) and the
    manifest v5 metrics roundtrip + pre-v5 cold-metrics restore.
"""
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmarks.check_regression import check_accuracy
from repro import obs
from repro.core import CMLS16, SketchSpec
from repro.kernels import ops
from repro.stream import CountService, WindowSpec

SPEC = SketchSpec(width=1024, depth=2, counter=CMLS16)


def _zipf(n, vocab, seed=0):
    return (np.random.default_rng(seed).zipf(1.3, n) % vocab).astype(np.uint32)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def test_registry_instruments_and_identity():
    m = obs.MetricsRegistry()
    c = m.counter("events", plane="p0")
    c.inc(5)
    c.inc(2.5)
    assert m.counter("events", plane="p0") is c  # get-or-create identity
    assert m.counter("events", plane="p0").value == 7.5
    assert m.counter("events", plane="p1").value == 0  # labels distinguish
    with pytest.raises(ValueError):
        c.inc(-1)

    g = m.gauge("fill")
    g.set(10)
    g.set(3)
    assert (g.value, g.high_water) == (3, 10)

    h = m.histogram("lat", lo=0, hi=3)
    assert h.bounds() == [1.0, 2.0, 4.0, 8.0]
    for v in (0.5, 2.0, 3.0, 100.0, -1.0):
        h.observe(v)
    # 0.5 and -1.0 in bucket 0; 2.0 in <=2; 3.0 in <=4; 100 overflows
    assert h.counts == [2, 1, 1, 0, 1]
    assert h.count == 5


def test_registry_snapshot_load_keeps_objects_live():
    m = obs.MetricsRegistry()
    m.counter("events").inc(11)
    m.gauge("fill").set(4)
    m.histogram("lat", lo=0, hi=2).observe(3.0)
    snap = m.snapshot()
    assert json.loads(json.dumps(snap)) == snap  # plain JSON

    m2 = obs.MetricsRegistry()
    c = m2.counter("events")      # handed out BEFORE the load
    m2.load(snap)
    assert c.value == 11          # restored in place, object stays live
    c.inc()
    assert m2.snapshot()["counters"]["events"] == 12
    assert m2.snapshot()["histograms"]["lat"] == snap["histograms"]["lat"]


def test_merge_snapshots_sum_counters_max_gauges():
    def shard(events, fill, hw):
        m = obs.MetricsRegistry()
        m.counter("events").inc(events)
        m.gauge("fill").set(hw)
        m.gauge("fill").set(fill)
        m.histogram("are", lo=-2, hi=2).observe(0.5)
        return m.snapshot()

    merged = obs.merge_snapshots([shard(10, 3, 9), shard(32, 7, 8)])
    assert merged["counters"]["events"] == 42
    assert merged["gauges"]["fill"] == {"value": 7, "high_water": 9}
    assert merged["histograms"]["are"]["count"] == 2
    bad = shard(1, 1, 1)
    bad["histograms"]["are"]["lo"] = -5  # bound mismatch must be loud
    with pytest.raises(ValueError):
        obs.merge_snapshots([merged, bad])


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------

def test_prometheus_exposition_shape():
    m = obs.MetricsRegistry()
    m.counter("plane_events", plane="p0").inc(7)
    m.gauge("ring_fill", plane="p0").set(3)
    h = m.histogram("accuracy_are", lo=-1, hi=1, tenant="a")
    h.observe(0.4)
    h.observe(3.0)
    text = obs.to_prometheus(m)
    lines = text.splitlines()
    assert 'plane_events_total{plane="p0"} 7' in lines
    assert 'ring_fill{plane="p0"} 3' in lines
    assert 'ring_fill_high_water{plane="p0"} 3' in lines
    # cumulative buckets: 0.4 <= 0.5, then both under +Inf
    assert 'accuracy_are_bucket{tenant="a",le="0.5"} 1' in lines
    assert 'accuracy_are_bucket{tenant="a",le="+Inf"} 2' in lines
    assert 'accuracy_are_count{tenant="a"} 2' in lines


# --------------------------------------------------------------------------
# scoped dispatch tallies
# --------------------------------------------------------------------------

def test_audit_scope_isolation_and_legacy_wrappers():
    ops.reset_launch_counts()
    s = CountService(SPEC, tenants=("a", "b"), queue_capacity=512)
    with ops.audit_scope() as outer:
        s.enqueue("a", _zipf(100, 50))
        with ops.audit_scope() as inner:
            s.flush()                # one pending row of two: active path
        s.query("a", [1])
    assert "queue_append" in outer and "query" in outer
    assert "queue_append" not in inner          # nothing from outside
    assert inner["update_rows"] == 1
    assert outer["update_rows"] == 1            # nesting sees everything
    # the default scope (legacy wrappers) saw the same window
    assert ops.launch_counts()["queue_append"] == outer["queue_append"]
    ops.reset_launch_counts()
    assert ops.launch_counts() == {}


def test_audit_scope_equal_tallies_do_not_detach_default():
    """Counters compare by VALUE: exiting a scope whose tally equals the
    default scope's contents must not remove the default from the active
    list (the list.remove failure mode)."""
    ops.reset_launch_counts()
    with ops.audit_scope():
        pass                        # empty tally == freshly-reset default
    s = CountService(SPEC, tenants=("a",), queue_capacity=512)
    s.enqueue("a", _zipf(50, 20))
    assert ops.launch_counts().get("queue_append") == 1
    ops.reset_launch_counts()


def test_tracked_flush_epoch_is_one_dispatch_under_scope():
    svc = CountService(SPEC, tenants=("a", "b"), queue_capacity=4096,
                       track_top=8)
    svc.enqueue("a", _zipf(300, 100, seed=1))
    svc.enqueue("b", _zipf(300, 100, seed=2))
    with ops.audit_scope() as tally:
        svc.flush()
    assert dict(tally) == {"update_score_rows": 1}
    # the service's own registry folded the same audit in
    snap = svc.metrics.snapshot()["counters"]
    assert snap['dispatch{op="update_score_rows"}'] == 1


# --------------------------------------------------------------------------
# host spans (jax.profiler annotations)
# --------------------------------------------------------------------------

def _record(tmp_path, fn) -> list[dict]:
    """Run `fn` inside a CPU `jax.profiler` session and return its `cms.*`
    host spans: name, start/end (ns), counts, and the line (thread)."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        fn()
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("cms."):
                    spans.append({"name": e.name, "start": e.start_ns,
                                  "end": e.start_ns + e.duration_ns,
                                  "args": dict(e.stats),
                                  "line": (plane.name, line.name)})
    return sorted(spans, key=lambda s: (s["start"], -s["end"]))


def _inside(child: dict, parent: dict) -> bool:
    return (child["line"] == parent["line"]
            and parent["start"] <= child["start"]
            and child["end"] <= parent["end"])


def _named(spans, name, **args) -> list[dict]:
    return [s for s in spans if s["name"] == name
            and all(s["args"].get(k) == v for k, v in args.items())]


def test_tracer_spans_record_and_summarize(tmp_path):
    """The service's spans land in the profiler's trace, nested by cause:
    an `enqueue_many` whose batch overflows runs the tenant's own
    `enqueue`, whose queue-pressure epoch holds its gather, candidates,
    update and reselect; a read of a dirty plane holds its read epoch.
    The counts are those of the events and rows sent."""
    svc = CountService(SPEC, tenants=("a", "b"), queue_capacity=512,
                       track_top=4)
    svc.enqueue_many({"a": _zipf(400, 80, seed=1), "b": _zipf(100, 80)})

    def work():
        # a: 400 buffered + 300 > 512 -> overflow path; b fits
        svc.enqueue_many({"a": _zipf(300, 80, seed=2),
                          "b": _zipf(50, 80, seed=3)})
        svc.query("b", np.arange(16))       # b's plane is dirty again

    spans = _record(tmp_path, work)
    (many,) = _named(spans, "cms.enqueue_many")
    assert 0 < many["args"].pop("cpu_ns") <= many["end"] - many["start"]
    assert many["args"] == {"tenants": 2, "events": 350, "overflow": 1}
    (stage,) = _named(spans, "cms.stage")
    assert stage["args"] == {"events": 350} and _inside(stage, many)
    (enq,) = _named(spans, "cms.enqueue")
    assert enq["args"] == {"events": 300} and _inside(enq, many)
    (press,) = _named(spans, "cms.flush_epoch", reason="pressure")
    assert _inside(press, enq)
    assert 0 < press["args"].pop("cpu_ns") <= press["end"] - press["start"]
    # the epoch landed everything buffered: a's 400 + 112 that still fit,
    # b's 100 + 50
    assert press["args"] == {"plane": "p0", "rows": 2, "events": 662,
                             "classes": 1, "reason": "pressure"}
    for step in ("gather", "candidates", "update", "reselect"):
        (sp,) = [s for s in _named(spans, f"cms.flush.{step}")
                 if _inside(s, press)]
    (gather,) = [s for s in _named(spans, "cms.flush.gather")
                 if _inside(s, press)]
    assert gather["args"] == {"rows": 2, "cols": 512}
    appends = [s for s in _named(spans, "cms.queue_append")
               if _inside(s, many)]
    assert sum(s["args"]["events"] for s in appends) == 350
    assert {s["args"]["plane"] for s in appends} == {"p0"}

    (q,) = _named(spans, "cms.query")
    assert q["args"]["tenant"] == "b" and q["args"]["probes"] == 16
    (read,) = _named(spans, "cms.flush_epoch", reason="read")
    assert _inside(read, q) and read["args"]["events"] == 188
    for step in ("upload", "dispatch"):
        (sp,) = _named(spans, f"cms.query.{step}")
        assert _inside(sp, q)
    assert _named(spans, "cms.query.row") == []
    # every epoch step lies inside an epoch
    epochs = _named(spans, "cms.flush_epoch")
    for s in spans:
        if s["name"].startswith("cms.flush."):
            assert any(_inside(s, ep) for ep in epochs), s


def test_clean_read_spans_upload_and_dispatch(tmp_path):
    """A plain tenant's clean read records `cms.query` holding its upload
    and its one dispatch, in that order, and nothing else: no epoch and
    no separate table slice."""
    svc = CountService(SPEC, tenants=("a", "b"), queue_capacity=512)
    svc.enqueue_many({"a": _zipf(200, 80), "b": _zipf(200, 80, seed=4)})
    svc.flush()
    spans = _record(tmp_path, lambda: svc.query("a", np.arange(24)))
    (q,) = _named(spans, "cms.query")
    assert q["args"] == {"tenant": "a", "probes": 24}
    (up,) = _named(spans, "cms.query.upload")
    (disp,) = _named(spans, "cms.query.dispatch")
    assert up["args"] == {"probes": 24}
    assert _inside(up, q) and _inside(disp, q)
    assert up["end"] <= disp["start"]
    assert {s["name"] for s in spans} == {"cms.query", "cms.query.upload",
                                          "cms.query.dispatch"}


def test_disabled_tracer_costs_nothing(tmp_path):
    """Spans never block: an enqueue/flush/query loop adds ZERO
    block_until_ready calls, and issues the same kernel launches with and
    without a recording profiler session."""
    def loop(svc):
        for i in range(3):
            svc.enqueue("a", _zipf(200, 80, seed=i))
            svc.enqueue_many({"a": _zipf(100, 80, seed=10 + i)})
            svc.flush()
            svc.query("a", np.arange(8))

    blocks = []
    orig_block = jax.block_until_ready

    def spy_block(x):
        blocks.append(1)
        return orig_block(x)

    tallies = []
    for recording in (False, True):
        svc = CountService(SPEC, tenants=("a",), queue_capacity=512,
                           track_top=4)
        try:
            jax.block_until_ready = spy_block
            with ops.audit_scope() as tally:
                if recording:
                    spans = _record(tmp_path, lambda: loop(svc))
                else:
                    loop(svc)
        finally:
            jax.block_until_ready = orig_block
        tallies.append(dict(tally))
    assert blocks == []                    # zero added sync points
    assert tallies[0] == tallies[1]        # recording adds no launches
    assert len(_named(spans, "cms.query")) == 3


def test_cpu_span_leaves_out_blocked_time(tmp_path):
    """A `cpu=True` span's `cpu_ns` is the calling thread's CPU time
    inside it: a sleep adds wall time and next to no CPU time, a busy loop
    adds both."""
    def work():
        with obs.span("enqueue_many", cpu=True, tenants=0):
            time.sleep(0.05)
        with obs.span("flush_epoch", cpu=True):
            stop = time.thread_time_ns() + 20_000_000
            while time.thread_time_ns() < stop:
                pass

    spans = _record(tmp_path, work)
    (sleep,) = _named(spans, "cms.enqueue_many")
    (busy,) = _named(spans, "cms.flush_epoch")
    assert sleep["end"] - sleep["start"] >= 50_000_000
    assert sleep["args"]["cpu_ns"] < 10_000_000
    assert 20_000_000 <= busy["args"]["cpu_ns"] <= busy["end"] - busy["start"]


def test_span_without_session_records_nothing(tmp_path):
    """Outside a profiler session a span is one shared inert object that
    drops its counts; inside one it is a `cms.`-named annotation."""
    assert not obs.recording()
    with obs.span("flush_epoch", plane="p0") as sp:
        sp.set_metadata(events=1)
    assert sp is obs.span("query")
    seen = []

    def work():
        assert obs.recording()
        seen.append(obs.span("query", tenant="a"))
    spans = _record(tmp_path, work)
    assert isinstance(seen[0], jax.profiler.TraceAnnotation)
    assert spans == []          # made but never entered: nothing recorded


def test_watermark_epoch_nests_in_its_enqueue(tmp_path):
    """A windowed tenant's interval crossing flushes inside the call that
    crossed it (`reason="watermark"`) and rotates in one `window_rotate`."""
    wspec = WindowSpec(sketch=SPEC, buckets=4, interval=10.0)
    svc = CountService(queue_capacity=512, track_top=4)
    svc.add_tenant("w", window=wspec)
    svc.enqueue_many({"w": _zipf(100, 20)}, ts=5.0)
    spans = _record(tmp_path, lambda: svc.enqueue_many(
        {"w": _zipf(100, 20, seed=1)}, ts=25.0))
    (many,) = _named(spans, "cms.enqueue_many")
    (ep,) = _named(spans, "cms.flush_epoch", reason="watermark")
    (rot,) = _named(spans, "cms.window_rotate")
    assert _inside(ep, many) and _inside(rot, many)
    assert ep["args"]["plane"] == "w0" and ep["args"]["events"] == 100
    assert rot["args"] == {"plane": "w0", "rows": 1}
    assert ep["end"] <= rot["start"]     # flush first, then rotate


def test_serve_counts_trace_out_writes_profiler_trace(tmp_path):
    """`serve_counts --trace-out DIR` records a `jax.profiler` trace whose
    host plane holds the service's `cms.*` spans."""
    from jax.profiler import ProfileData
    from repro.launch import serve_counts
    out = tmp_path / "trace"
    serve_counts.main(["--tenants", "2", "--batches", "2", "--batch", "256",
                       "--width", "1024", "--depth", "2", "--queue-cap",
                       "512", "--trace-out", str(out)])
    (path,) = out.rglob("*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             for line in p.lines for e in line.events}
    assert {"cms.enqueue_many", "cms.flush_epoch", "cms.query_all",
            "cms.query", "cms.topk", "cms.admit"} <= names


# --------------------------------------------------------------------------
# accuracy probes + envelope gate
# --------------------------------------------------------------------------

def test_probe_shadow_counts_are_exact():
    probe = obs.AccuracyProbe(rate=1.0, capacity=1 << 16)
    batches = [_zipf(500, 200, seed=i) for i in range(3)]
    for b in batches:
        probe.observe("t", b)
    keys, true = probe.shadowed("t")
    uniq, counts = np.unique(np.concatenate(batches), return_counts=True)
    assert sorted(keys.tolist()) == uniq.tolist()
    got = dict(zip(keys.tolist(), true.tolist()))
    assert got == dict(zip(uniq.tolist(), counts.tolist()))
    assert probe.dropped == 0


def test_probe_sampling_is_deterministic_and_bounded():
    probe = obs.AccuracyProbe(rate=0.25, capacity=8)
    keys = np.arange(4096, dtype=np.uint32)
    mask = probe.sampled(keys)
    np.testing.assert_array_equal(mask, probe.sampled(keys))  # deterministic
    assert 0.1 < mask.mean() < 0.4      # roughly the asked-for rate
    probe.observe("t", keys)
    assert len(probe.counts["t"]) == 8  # capacity cap held
    assert probe.dropped > 0            # and the cost was counted


def test_probe_are_by_decile_orders_cold_to_hot():
    probe = obs.AccuracyProbe(rate=1.0)
    rng = np.random.default_rng(0)
    probe.observe("t", rng.zipf(1.3, 4000) % 500)
    assert probe.are_by_decile(lambda k: k, "nope") is None  # unknown tenant
    keys, true = probe.shadowed("t")
    exact = dict(zip(keys.tolist(), true.tolist()))

    # a query that overestimates every key by +3: relative error shrinks
    # with frequency, so deciles must decrease cold -> hot
    ares = probe.are_by_decile(
        lambda k: np.array([exact[int(x)] + 3 for x in k], np.float64), "t")
    assert len(ares) == 10
    assert ares[0] > ares[-1]
    # exact answers score a flat zero
    assert probe.are_by_decile(
        lambda k: np.array([exact[int(x)] for x in k], np.float64), "t") \
        == [0.0] * 10


def test_probe_record_lands_registry_metrics():
    probe = obs.AccuracyProbe(rate=1.0)
    svc = CountService(SPEC, tenants=("a",), queue_capacity=4096,
                       probe=probe)
    svc.enqueue("a", _zipf(2000, 300, seed=3))
    out = probe.record(svc)
    assert set(out) == {"a"} and len(out["a"]) == 10
    snap = svc.metrics.snapshot()
    assert snap["histograms"]['accuracy_are{tenant="a"}']["count"] == 10
    assert 'accuracy_are_decile{decile="0",tenant="a"}' in snap["gauges"]


def test_accuracy_envelope_gate_trips_on_corruption():
    """The CI accuracy gate end-to-end: a healthy service passes its own
    envelope; corrupting its tables trips `check_accuracy`."""
    probe = obs.AccuracyProbe(rate=1.0)
    svc = CountService(SPEC, tenants=("a",), queue_capacity=4096,
                       probe=probe, seed=7)
    for i in range(3):
        svc.enqueue("a", _zipf(2000, 400, seed=10 + i))
    svc.flush()
    baseline = {"are_by_decile": probe.record(svc)}
    assert check_accuracy({"are_by_decile": probe.record(svc)},
                          baseline) == []
    # corrupt the plane: zero the tables, so every estimate collapses
    plane = svc.planes[0]
    plane.tables = plane.tables * 0
    problems = check_accuracy({"are_by_decile": probe.record(svc)}, baseline)
    assert problems, "gate must trip on corrupted counts"
    assert any("decile" in p for p in problems)
    # and a missing tenant is its own loud failure
    assert check_accuracy({"are_by_decile": {}}, baseline) \
        == ["a: missing from fresh accuracy results"]


# --------------------------------------------------------------------------
# service wiring + manifest v5
# --------------------------------------------------------------------------

def test_service_metrics_parity_and_plane_gauges():
    svc = CountService(SPEC, tenants=("a", "b"), queue_capacity=256)
    svc.enqueue("a", np.full(100, 7, np.uint32))
    svc.enqueue("b", np.full(300, 8, np.uint32))  # forces a pressure flush
    svc.flush()
    snap = svc.metrics.snapshot()
    assert snap["counters"]["events"] == svc.stats["events"] == 400
    assert snap["counters"]["flushes"] == svc.stats["flushes"]
    assert snap["counters"]['plane_events{plane="p0"}'] == 400
    fill = snap["gauges"]['ring_fill{plane="p0"}']
    assert fill["value"] == 0 and fill["high_water"] >= 100
    assert snap["gauges"]['plane_tenants{plane="p0"}']["value"] == 2


def test_window_plane_watermark_gauges():
    wspec = WindowSpec(sketch=SPEC, buckets=4, interval=10.0)
    svc = CountService(queue_capacity=512)
    svc.add_tenant("w", window=wspec)
    svc.enqueue("w", _zipf(50, 20), ts=25.0)   # epoch 2
    snap = svc.metrics.snapshot()["gauges"]
    assert snap['watermark_epoch{plane="w0",tenant="w"}']["value"] == 2
    assert snap['watermark_lag{plane="w0",tenant="w"}']["value"] == 0
    svc.enqueue("w", _zipf(50, 20, seed=1), ts=57.0)  # epoch 5: lag 3 seen
    snap = svc.metrics.snapshot()["gauges"]
    assert snap['watermark_epoch{plane="w0",tenant="w"}']["value"] == 5
    assert snap['watermark_lag{plane="w0",tenant="w"}']["high_water"] == 3
    assert svc.metrics.snapshot()["counters"][
        'plane_rotations{plane="w0"}'] == 3


def test_manifest_v5_metrics_roundtrip(tmp_path):
    svc = CountService(SPEC, tenants=("a",), queue_capacity=512, track_top=4)
    svc.enqueue("a", _zipf(400, 100))
    svc.flush()
    before = svc.metrics.snapshot()
    assert before["counters"]["events"] == 400
    svc.snapshot(str(tmp_path), step=1)

    svc2 = CountService.restore(str(tmp_path))
    after = svc2.metrics.snapshot()
    assert after["counters"] == before["counters"]
    assert after["gauges"]['ring_fill{plane="p0"}'] \
        == before["gauges"]['ring_fill{plane="p0"}']
    # restored instruments keep counting into the same objects
    svc2.enqueue("a", _zipf(10, 5))
    assert svc2.stats["events"] == 410


def test_pre_v5_checkpoint_restores_with_cold_metrics(tmp_path):
    """A v4 manifest (no `metrics` snapshot) must load with zeroed
    registry metrics — only the legacy events/flushes stats carry over."""
    svc = CountService(SPEC, tenants=("a",), queue_capacity=512)
    svc.enqueue("a", _zipf(400, 100))
    svc.flush()
    svc.snapshot(str(tmp_path), step=1)
    # rewrite the manifest as a pre-v5 checkpoint
    mpath = os.path.join(str(tmp_path), "step_00000001", "manifest.json")
    doc = json.load(open(mpath))
    assert doc["metadata"]["version"] == 8
    doc["metadata"]["version"] = 4
    del doc["metadata"]["metrics"]
    with open(mpath, "w") as f:
        json.dump(doc, f)

    svc2 = CountService.restore(str(tmp_path))
    assert svc2.stats == {"events": 400, "flushes": 1}  # stats carried
    snap = svc2.metrics.snapshot()
    assert snap["counters"]['plane_events{plane="p0"}'] == 0  # cold
    assert snap["gauges"]['ring_fill{plane="p0"}']["high_water"] == 0
    # counts themselves restored fine
    assert float(svc2.query("a", [1])[0]) >= 1
