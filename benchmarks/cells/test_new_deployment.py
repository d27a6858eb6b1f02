"""A deployment enters the benchmark as files only (run with
`PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/cells`):

  * a deployment whose build function (`deploy/<config>.py`) and loop kind
    (`loops/<kind>.py`) exist only as files in a copy of the cells directory
    runs end to end through `harness.run_cell`, cut by its own `cpu_cut`,
    and comes out correct; with its control counter, or with the timed path
    broken as `test_cells` breaks it, not correct;
  * a traffic file naming a loop kind found nowhere stops the real command
    before set-up: non-zero exit, no result;
  * the existing cells' `cpu_cut` blocks give the sizes their CPU tests ran
    at before the blocks existed;
  * a traced window's idle share is over the cell's chips.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

# test_cells puts this directory and src/ on the path
from test_cells import BENCH, FAULTS, HERE, PEAKS, ROOT, run_tiny, tiny

import harness  # noqa: E402
import tracefile  # noqa: E402

CELL = "demo.step"

DEMO_FILES = {
    "configs/demo.json": {
        "planes": [
            {"tenants": 6, "prefix": "a", "depth": 2, "width": 1 << 16,
             "counter": {"kind": "log", "base": 1.00025, "bits": 16}},
            {"tenants": 6, "prefix": "b", "depth": 3, "width": 1 << 16,
             "counter": {"kind": "log", "base": 1.00025, "bits": 16}},
        ],
        "queue_capacity": 4096, "track_top": 8, "sketch_seed": 11,
        "stream": {"events": 1 << 16, "keys": 50000, "zipf_a": 1.2},
        "control_counter": {"kind": "log", "base": 1.08, "bits": 8},
        "cpu_cut": {"planes": {"tenants": 2, "width": 1 << 14},
                    "stream": {"events": 1 << 13}, "queue_capacity": 512},
    },
    "traffic/steps.json": {
        "loop": "step_reads", "events_per_step": 1024, "probes": 256,
        "cpu_cut": {"events_per_step": 256, "probes": 64},
    },
    "limits/demo.step.json": {"are": 0.05, "worst_are": 0.08},
    "deploy/demo.py": '''
        """Two planes whose tenants register in turn (a00, b00, a01, ...), so
        registry order is not plane order."""
        import harness
        from repro.core.sketch import SketchSpec
        from repro.stream import CountService


        def build(cfg, seed, counter):
            svc = CountService(queue_capacity=int(cfg["queue_capacity"]),
                               seed=int(seed), track_top=cfg["track_top"],
                               probe=None)
            planes = [(p, SketchSpec(
                width=int(p["width"]), depth=int(p["depth"]),
                counter=harness.counter_spec(counter or p["counter"]),
                seed=int(cfg["sketch_seed"]))) for p in cfg["planes"]]
            names = []
            for i in range(max(int(p["tenants"]) for p, _ in planes)):
                for p, spec in planes:
                    if i < int(p["tenants"]):
                        names.append(f"{p['prefix']}{i:02d}")
                        svc.add_tenant(names[-1], spec=spec)
            return svc, names
        ''',
    "loops/step_reads.py": '''
        """Closed loop of steps: every tenant's next slice of a Zipf stream
        in one `enqueue_many`, then one `query_all` of each tenant's probe
        keys, answered on the host."""
        import time

        import numpy as np

        import harness
        import reference


        class StepReads(harness.Loop):
            def setup(self):
                s = self.cfg["stream"]
                self.streams = (self.rng.zipf(float(s["zipf_a"]), size=(
                    len(self.names), int(s["events"]))) % int(s["keys"])
                                ).astype(np.uint32)
                self.n = int(self.trf["events_per_step"])
                self.slices = self.streams.shape[1] // self.n
                # keys of each tenant's first step, so each was sent
                self.probes = self.streams[:, :int(self.trf["probes"])]
                self.steps = 0
                self.answers = []

            def batch(self, c, t):
                s = (c % self.slices) * self.n
                return self.streams[t, s:s + self.n]

            def op(self):
                c = self.steps
                self.enqueue({nm: self.batch(c, t)
                              for t, nm in enumerate(self.names)}, c)
                self.steps += 1
                with self.span("bench.read"):
                    t0 = time.perf_counter()
                    est = {nm: np.asarray(v) for nm, v in
                           self.svc.query_all(self.probes).items()}
                    t1 = time.perf_counter()
                self.epochlog.after(None)
                self.rec["lat_s"].append(t1 - t0)
                self.answers.append((self.steps, est))
                self.count_op()

            def warm(self):
                for _ in range(2):
                    self.op()
                self.drain()

            def compare(self):
                """Every answer against the exact counts of what its tenant
                had been sent by that step."""
                L = self.streams.shape[1]
                srt = [np.sort(s) for s in self.streams]
                whole = [reference.counts_in(s, p)
                         for s, p in zip(srt, self.probes)]
                groups = []
                for steps, est in self.answers:
                    full, part = divmod(steps * self.n, L)
                    for t, nm in enumerate(self.names):
                        exact = full * whole[t] + reference.counts_in(
                            np.sort(self.streams[t, :part]), self.probes[t])
                        groups.append((est[nm], exact))
                return reference.group_errors(groups)
        ''',
}


@pytest.fixture(scope="module")
def demo(tmp_path_factory):
    """A copy of the cells directory with the demo deployment's files added,
    and a benchmark whose cell `demo.step` reports `events_per_s` and
    `reads_per_s`."""
    cells = tmp_path_factory.mktemp("checkout") / "cells"
    shutil.copytree(HERE, cells, ignore=shutil.ignore_patterns("__pycache__"))
    for rel, body in DEMO_FILES.items():
        path = cells / rel
        path.parent.mkdir(exist_ok=True)
        path.write_text(json.dumps(body) if isinstance(body, dict)
                        else textwrap.dedent(body).lstrip())
    bench = copy.deepcopy(BENCH)
    bench["workloads"].append({"name": CELL, "config": "demo",
                               "traffic": "steps", "chips": 1})
    for m in bench["end_to_end"]:
        if m["name"] in ("events_per_s", "reads_per_s"):
            m["workloads"].append(CELL)
    return bench, cells


@pytest.mark.parametrize("trace", [False, True])
def test_files_only_deployment_is_correct(demo, trace, tmp_path):
    bench, cells = demo
    out = run_tiny(CELL, trace=trace, bench=bench, cells=cells, root=tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    if trace:
        ctx = json.loads(
            (tmp_path / ".bench_trace" / CELL / "ctx.json").read_text())
        assert ctx["chips"] == 1 and ctx["epochs"]
    else:
        for m in harness.cell_metrics(bench, CELL, "end_to_end"):
            assert out["metrics"][m["name"]]["value"] > 0, m["name"]
    # the deployment file's own order: tenants of the two planes in turn
    cfg, _ = tiny(CELL, bench, cells)
    svc, names = harness.build_for("demo", cells)(cfg, 1, None)
    assert names[:2] == ["a00", "b00"] and len(svc.planes) == 2


@pytest.mark.parametrize("fault", ["control"] + sorted(FAULTS))
def test_files_only_deployment_broken_is_not_correct(demo, fault, tmp_path,
                                                     monkeypatch):
    bench, cells = demo
    if fault != "control":
        FAULTS[fault](monkeypatch)
    out = run_tiny(CELL, control=fault == "control", bench=bench,
                   cells=cells, root=tmp_path)
    assert not out["correct"], out["checks"]


def test_unknown_loop_kind_exits_nonzero(tmp_path):
    bench = copy.deepcopy(BENCH)
    bench["workloads"].append({"name": "ngram_pmi.nowhere",
                               "config": "ngram_pmi", "traffic": "nowhere",
                               "chips": 1})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for rel in BENCH["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cells = tmp_path / "benchmarks" / "cells"
    (cells / "traffic" / "nowhere.json").write_text('{"loop": "nowhere"}')
    (cells / "limits" / "ngram_pmi.nowhere.json").write_text('{"are": 1}')
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/cells/run.py", "--workload",
         "ngram_pmi.nowhere", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "unknown loop kind 'nowhere'" in p.stderr
    assert "compile cache" not in p.stderr      # stopped before set-up


# the CPU sizes of the existing cells before their files held a `cpu_cut`
TODAYS_CUT = {"width": 1 << 16, "tenants": 4, "corpus_tokens": 1 << 13,
              "queue_capacity": 1024, "events_per_call": 512,
              "prefill_call": 1024, "read_bigrams": 64, "pool_reads": 32,
              "rate_per_s": 50}


@pytest.mark.parametrize("cell", ["ngram_pmi.ingest", "ngram_pmi.query"])
def test_cpu_cut_gives_the_former_sizes(cell):
    cfg, trf = tiny(cell)
    for p in cfg["planes"]:
        assert (p["width"], p["tenants"]) == (TODAYS_CUT["width"],
                                              TODAYS_CUT["tenants"])
    assert cfg["stream"]["corpus_tokens"] == TODAYS_CUT["corpus_tokens"]
    assert cfg["queue_capacity"] == TODAYS_CUT["queue_capacity"]
    # every traffic key the former cut set, where the loop reads it, has
    # its former value; no other key moved
    wl = next(w for w in BENCH["workloads"] if w["name"] == cell)
    full = harness.load_json(HERE / "traffic" / f"{wl['traffic']}.json")
    for k, v in trf.items():
        if k in TODAYS_CUT:
            assert v == TODAYS_CUT[k], k
        else:
            assert v == full[k], k
    assert {k for k in TODAYS_CUT if k in full} <= set(full["cpu_cut"])


def _window(ops, ctx) -> tracefile.Trace:
    return tracefile.Trace(ops, [("bench.window", 0, 100)], ctx, PEAKS)


@pytest.mark.parametrize("ctx, idle", [({}, 40.0), ({"chips": 1}, 40.0),
                                       ({"chips": 2}, 70.0)])
def test_idle_share_is_over_the_cells_chips(ctx, idle):
    """One chip busy for 60 of the window's 100 ns: a one-chip cell reads
    as before; in a two-chip cell the chip that ran nothing is idle."""
    reader = harness.metric_reader("device_idle_pct.ingest")
    ops = [("/device:TPU:0", 10, 70, "fusion", "jit_x", 0)]
    assert reader(_window(ops, ctx)) == pytest.approx(idle)


def test_idle_share_one_chip_busy_one_without_ops():
    reader = harness.metric_reader("device_idle_pct.ingest")
    ops = [("/device:TPU:0", 0, 100, "fusion", "jit_x", 0)]
    assert reader(_window(ops, {"chips": 2})) == pytest.approx(50.0)
