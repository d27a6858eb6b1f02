"""CPU tests of the chip benchmark (run them explicitly; the repository's
pytest configuration collects only `tests/`):

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/cells

  * the trace reduction, on synthetic intervals and on traces recorded on a
    v5e (`testdata/`), down to every per-layer metric;
  * the least-byte counts (distinct keys are counted per tenant);
  * every cell end to end through `harness.run_cell` at the tiny size of
    the `cpu_cut` its configuration and traffic files hold, sound (correct)
    and with its control counter (not correct);
  * the timed path broken underneath, once per fault the cells can have:
    a flush that leaves the tables unchanged, half of each batch dropped, one
    read's answers altered where they are produced.  `correct` comes out
    false for each;
  * the real command without a TPU, and in a directory without the program,
    exits non-zero and prints no result.
"""
from __future__ import annotations

import gzip
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import reference  # noqa: E402
import tracefile  # noqa: E402

BENCH = harness.load_json(ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
PEAKS = harness.load_json(HERE / "peaks.json")["TPU v5 lite"]


def cut(d: dict, over: dict) -> dict:
    """`d` with a `cpu_cut`'s overrides: an object under a key merges into
    the object there, or into each object of the list there (every plane)."""
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(d.get(k), list):
            for item in d[k]:
                cut(item, v)
        elif isinstance(v, dict) and isinstance(d.get(k), dict):
            cut(d[k], v)
        else:
            d[k] = v
    return d


def tiny(cell: str, bench: dict = BENCH, cells: pathlib.Path = HERE):
    """The cell's own configuration and traffic, cut to a CPU-sized run by
    the `cpu_cut` each file holds."""
    wl = next(w for w in bench["workloads"] if w["name"] == cell)
    cfg = harness.load_json(cells / "configs" / f"{wl['config']}.json")
    trf = harness.load_json(cells / "traffic" / f"{wl['traffic']}.json")
    return cut(cfg, cfg["cpu_cut"]), cut(trf, trf["cpu_cut"])


def run_tiny(cell: str, seed: int = 2 ** 31 + 77, control: bool = False,
             trace: bool = False, bench: dict = BENCH,
             cells: pathlib.Path = HERE, root: pathlib.Path = ROOT) -> dict:
    cfg, trf = tiny(cell, bench, cells)
    return harness.run_cell(bench, cell, seed, 1.0, trace, root=root,
                            t_start=time.perf_counter(), require_tpu=False,
                            cfg=cfg, trf=trf, peaks=PEAKS, control=control,
                            cells=cells)


# ---- trace reduction ----

def test_union_and_gaps():
    iv = np.array([[0, 10], [5, 20], [30, 40], [35, 38]], float)
    assert tracefile.union_ns(iv) == 30
    assert tracefile.gaps_ns(iv, 0, 50) == [(20, 30), (40, 50)]
    assert tracefile.gaps_ns(iv, -5, 12) == [(-5, 0)]


def _synthetic() -> tracefile.Trace:
    dev = "/device:TPU:0"
    ops = [  # (device, start, end, op, program, run)
        (dev, 100, 200, "fusion.1", "jit__update_score_rows_xla_jit", 0),
        (dev, 150, 180, "scatter.2", "jit__update_score_rows_xla_jit", 0),
        (dev, 210, 230, "fusion.3", "jit_flush_rows_inputs", 1),
        (dev, 400, 450, "gather.4", "jit_gather", 2),
        (dev, 460, 470, "fusion.5", "jit__queue_append_dense_xla", 3),
        (dev, 900, 1100, "fusion.6", "jit__update_score_rows_xla_jit", 4),
    ]
    spans = [("bench.window", 50, 1000), ("bench.read", 390, 480),
             ("bench.enqueue", 240, 390)]
    ctx = {"epochs": [{"events": 100, "distinct": 10, "cell_bytes": 2,
                       "depth": 2}],
           "reads": [{"probes": 30, "distinct": 20, "cell_bytes": 2,
                      "depth": 2}],
           "enqueue_s": 2e-6, "enqueue_events": 1000,
           "lag_s": [0.001] * 19 + [0.003],
           "lat_s": [0.002] * 19 + [0.010]}
    return tracefile.Trace(ops, spans, ctx, {"hbm_bytes_per_s": 1e9})


def test_busy_idle_attribution():
    tr = _synthetic()
    # busy: [100, 200] + [210, 230] + [400, 450] + [460, 470] + [900, 1000]
    assert tr.window_s() == pytest.approx(950e-9)
    assert tr.busy_s() == pytest.approx(280e-9)
    # by name: nested scatter inside its fusion counted once; the second
    # epoch is cut at the window's end
    pat = ("update_score_rows", "flush_rows_inputs")
    assert tr.program_time_s(pat) == pytest.approx(220e-9)
    assert tr.program_runs(("update_score_rows",)) == 2
    # by span: ops starting inside bench.read, minus the queue append
    assert tr.span_time_s("bench.read", ("queue_append",)) == \
        pytest.approx(50e-9)
    bd = tr.breakdown()
    assert bd["device_ops"][0][0] == "jit__update_score_rows_xla_jit"
    assert bd["device_ops"][0][1] == pytest.approx(200e-9)
    # each gap goes to the span its middle falls in
    gaps = dict(bd["idle_gaps"])
    assert gaps["bench.enqueue"] == pytest.approx(170e-9)
    assert gaps["bench.read"] == pytest.approx(10e-9)
    assert gaps["between harness calls"] == pytest.approx(
        (50 + 10 + 430) * 1e-9)


def test_readers_on_synthetic():
    tr = _synthetic()
    r = {m["name"]: harness.metric_reader(m["name"])(tr)
         for m in BENCH["per_layer"]}
    assert r["flush_device_ms"] == pytest.approx(220e-9 / 2 * 1e3)
    least = 4 * 100 + 2 * 2 * 2 * 10
    assert r["flush_roofline_pct"] == pytest.approx(
        least / 1e9 / 220e-9 * 100)
    assert r["read_roofline_pct"] == pytest.approx(
        (8 * 30 + 2 * 2 * 20) / 1e9 / 50e-9 * 100)
    assert r["device_idle_pct.ingest"] == pytest.approx(
        (1 - 280 / 950) * 100)
    assert r["gen_lag_p95_ms"] == pytest.approx(
        np.percentile([0.001] * 19 + [0.003], 95) * 1e3)
    assert r["enqueue_host_ns_per_event"] == pytest.approx(2.0)
    assert r["read_latency_p95_ms"] == pytest.approx(
        np.percentile([0.002] * 19 + [0.010], 95) * 1e3)


def test_readers_silent_without_their_input():
    tr = tracefile.Trace([], [("bench.window", 0, 10)], {}, PEAKS)
    for m in BENCH["per_layer"]:
        assert harness.metric_reader(m["name"])(tr) is None, m["name"]


RECORDED = sorted((HERE / "testdata").glob("*.xplane.pb.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace(path, tmp_path):
    """A trace recorded on a v5e (kept gzipped) reduces to every per-layer
    metric of its cell, each share within (0, 100]."""
    cell = path.name.split(".xplane")[0]
    ctx = json.loads(path.with_name(f"{cell}.ctx.json").read_text())
    assert ctx["cell"] == cell
    xplane = tmp_path / f"{cell}.xplane.pb"
    xplane.write_bytes(gzip.decompress(path.read_bytes()))
    tr = tracefile.load(xplane, ctx, PEAKS)
    assert tr.devices and 0 < tr.busy_s() <= tr.window_s()
    for m in harness.cell_metrics(BENCH, cell, "per_layer"):
        v = harness.metric_reader(m["name"])(tr)
        assert v is not None and np.isfinite(v), m["name"]
        if m["unit"] == "%":
            assert 0 < v <= 100, (m["name"], v)
    bd = tr.breakdown()
    assert bd["device_ops"] and bd["idle_gaps"]


# ---- the numbers compared ----

def test_one_colliding_key_moves_only_the_untrimmed_mean():
    """A group of 5,000 exact answers but one count-1 key read as 335 (a
    key colliding with heavy keys in both rows) moves the group's mean past
    0.06, and neither its median nor its trimmed mean; a group altered x1.5
    moves all three to 0.5."""
    exact = np.ones(5000)
    one_key = exact.copy()
    one_key[7] = 335.0
    got = reference.group_errors([(one_key, exact), (exact, exact)])
    assert got["worst_are"] > 0.06
    assert got["worst_median_re"] == 0 and got["worst_trimmed_are"] == 0
    got = reference.group_errors([(exact * 1.5, exact), (exact, exact)])
    for k in ("worst_are", "worst_median_re", "worst_trimmed_are"):
        assert got[k] == pytest.approx(0.5), k


# ---- least bytes: distinct keys per tenant ----

def test_least_bytes_count_distinct_per_tenant():
    cfg, trf = tiny("ngram_pmi.ingest")
    svc, names = harness.build_service(cfg, 5)
    loop = harness.ClosedIngest(svc, names, cfg, trf, 5)
    loop.n, loop.slices = 4, 2
    # the same keys in every tenant: calls 0 and 1 send the two slices
    loop.streams = np.tile(np.array([1, 1, 2, 3, 3, 4, 4, 4], np.uint32),
                           (len(names), 1))
    loop.epochlog.epochs = [(0, [0, 1])]
    (e,) = loop.least_bytes()["epochs"]
    # per tenant {1, 2, 3, 4}: 4 distinct, not 4 over all tenants
    assert e["distinct"] == 4 * len(names)
    assert e["events"] == 8 * len(names)
    assert e["cell_bytes"] == 2 and e["depth"] == 2


# ---- every cell end to end at a tiny size ----

@pytest.mark.parametrize("cell", CELLS)
def test_cell_sound_run_is_correct(cell):
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    for m in harness.cell_metrics(BENCH, cell, "end_to_end"):
        assert out["metrics"][m["name"]]["value"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_control_is_not_correct(cell):
    assert not run_tiny(cell, control=True)["correct"]


def test_traced_tiny_run_is_correct():
    out = run_tiny("ngram_pmi.query", trace=True)
    assert out["correct"] and "window_s" in out["device"]


# ---- the timed path broken underneath ----

def _state_unchanged(monkeypatch):
    from repro.kernels import ops
    orig = ops.update_score_rows

    def frozen(tables, *a, **kw):
        return tables, orig(tables, *a, **kw)[1]
    monkeypatch.setattr(ops, "update_score_rows", frozen)


def _half_batch(monkeypatch):
    from repro.stream import CountService
    orig = CountService.enqueue_many

    def half(self, events, ts=None):
        return orig(self, {k: np.asarray(v)[: np.asarray(v).size // 2]
                           for k, v in events.items()}, ts)
    monkeypatch.setattr(CountService, "enqueue_many", half)


def _answer_altered(monkeypatch):
    """The third read of the window (or of the read-back after it) returns
    its answers altered by half."""
    from repro.stream import CountService
    seen = {"armed": False, "reads": 0}
    run_window = harness.run_window

    def armed(*a, **kw):
        seen["armed"] = True
        return run_window(*a, **kw)
    monkeypatch.setattr(harness, "run_window", armed)

    def altered(orig):
        def f(self, *a, **kw):
            out = orig(self, *a, **kw)
            if not seen["armed"]:
                return out
            seen["reads"] += 1
            if seen["reads"] != 3:
                return out
            if isinstance(out, dict):
                return {k: v * 1.5 for k, v in out.items()}
            return out * 1.5
        return f
    for name in ("query", "query_all"):
        monkeypatch.setattr(CountService, name,
                            altered(getattr(CountService, name)))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run_tiny(cell)
    assert not out["correct"], out["checks"]


# ---- no chip, no program: no result ----

def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/cells/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_without_a_tpu_exits_nonzero():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for rel in BENCH["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""
