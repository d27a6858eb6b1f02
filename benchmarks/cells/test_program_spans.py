"""Tests of the readers of the program's own `cms.*` spans (run them with
`PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/cells`):
their nesting, each reader on hand-built spans over `test_cells`'s
synthetic device ops, and a traced tiny run of each cell."""
import json

import pytest

# test_cells puts this directory and src/ on the path
from test_cells import BENCH, CELLS, PEAKS, ROOT, _synthetic, run_tiny

import harness  # noqa: E402
import program_spans  # noqa: E402
import tracefile  # noqa: E402

PROGRAM_METRICS = ("append_host_ns_per_event", "flush_host_ms",
                   "read_host_ms", "idle_in_program_pct.ingest",
                   "idle_in_program_pct.read")


def test_program_span_nesting_depth():
    got = program_spans.nest([
        ("cms.flush_epoch", 30, 40, {}), ("cms.enqueue_many", 0, 100, {}),
        ("cms.enqueue", 20, 60, {}), ("cms.query", 100, 120, {}),
        ("cms.flush.update", 32, 38, {})])
    assert [(s.name, s.depth) for s in got] == [
        ("cms.enqueue_many", 0), ("cms.enqueue", 1), ("cms.flush_epoch", 2),
        ("cms.flush.update", 3), ("cms.query", 0)]
    call, _, epoch, _, query = got
    assert call.holds(epoch) and not call.holds(query)


def _with_program_spans() -> tracefile.Trace:
    """`_synthetic`'s device ops (idle gaps [50, 100], [200, 210],
    [230, 400], [450, 460], [470, 900] in the window [50, 1000]) under
    hand-built program spans."""
    tr = _synthetic()
    tr.program_spans = program_spans.nest([
        ("cms.query", 10, 40, {}),                   # before the window
        ("cms.enqueue_many", 60, 80, {"events": 100, "cpu_ns": 15}),
        ("cms.enqueue_many", 240, 390, {"events": 100, "cpu_ns": 90}),
        ("cms.enqueue", 290, 360, {"events": 40}),
        ("cms.flush_epoch", 300, 350, {"reason": "pressure",
                                       "cpu_ns": 30}),
        ("cms.flush.update", 310, 340, {}),
        ("cms.query", 470, 685, {"probes": 30}),     # half of a gap
        ("cms.query.dispatch", 600, 680, {}),
        ("cms.flush", 920, 980, {"planes": 1}),
        ("cms.flush_epoch", 930, 970, {"reason": "explicit",
                                       "cpu_ns": 35}),
    ])
    return tr


def test_program_span_readers_on_hand_built_spans():
    tr = _with_program_spans()
    r = {m: harness.metric_reader(m)(tr) for m in PROGRAM_METRICS}
    # CPU time, the nested epoch's (30 ns) off its call's: (15 + 90 - 30)
    # / 200; the epoch outside any enqueue_many stays out
    assert r["append_host_ns_per_event"] == pytest.approx(0.375)
    assert r["flush_host_ms"] == pytest.approx(45e-6)
    # the outermost query only; the one before the window is left out
    assert r["read_host_ms"] == pytest.approx(215e-6)
    # idle 670 ns; inside outermost spans: 20 of [50, 100], 150 of
    # [230, 400], and 215 of the 430 of [470, 900]; the flush lies in
    # busy time
    assert r["idle_in_program_pct.ingest"] == pytest.approx(385 / 670 * 100)
    assert r["idle_in_program_pct.read"] == r["idle_in_program_pct.ingest"]


def test_program_span_readers_silent_without_spans():
    tr = _synthetic()
    tr.program_spans = []
    for m in PROGRAM_METRICS:
        assert harness.metric_reader(m)(tr) is None, m
    # calls that carry no CPU time give no host time per event
    tr.program_spans = program_spans.nest([
        ("cms.enqueue_many", 60, 80, {"events": 100})])
    assert harness.metric_reader("append_host_ns_per_event")(tr) is None


@pytest.mark.parametrize("cell", CELLS)
def test_traced_tiny_run_reads_program_spans(cell, monkeypatch):
    """A traced CPU run of the cell reports its metrics of the program's
    spans.  A CPU trace has no device plane, so the idle share is read
    from the run's own spans under one device op laid over the window's
    middle third."""
    monkeypatch.chdir(ROOT)
    out = run_tiny(cell, trace=True)
    assert out["correct"], out["checks"]
    wanted = [m["name"] for m in harness.cell_metrics(BENCH, cell,
                                                      "per_layer")
              if m["name"] in PROGRAM_METRICS]
    for name in wanted:
        if not name.startswith("idle_in_program_pct"):
            assert out["metrics"][name]["value"] > 0, name
    ctx = json.loads((ROOT / ".bench_trace" / cell / "ctx.json").read_text())
    tr = tracefile.load(tracefile.find_xplane(ROOT / ".bench_trace" / cell),
                        ctx, PEAKS)
    assert program_spans.spans(tr)
    third = (tr.hi - tr.lo) / 3
    tr.ops = [("/device:TPU:0", tr.lo + third, tr.hi - third, "fusion",
               "jit_x", 0)]
    tr.devices = ["/device:TPU:0"]
    (idle,) = [n for n in wanted if n.startswith("idle_in_program_pct")]
    v = harness.metric_reader(idle)(tr)
    assert v is not None and 0 < v <= 100
