"""One cell of the chip benchmark: build the deployment's `CountService`, warm
its shapes, measure a window of its traffic, and check the answers.

Everything a cell needs is found by name under the cells directory (this
file's, or one handed to `run_cell`): its configuration
(`configs/<config>.json`: planes, specs, stream), its traffic mix
(`traffic/<traffic>.json`: the loop kind and its parameters), the limits of
its comparison (`limits/<cell>.json`), and a reader per per-layer metric
(`metrics/<name>.py`).  Two lookups let a deployment bring its own code as
files, with no edit here:

  deployment  `deploy/<config>.py`, where it exists, defines
              `build(cfg, seed, counter) -> (svc, names)`; else
              `build_service` builds the configuration's planes.  `counter`
              is None, or the configuration's `control_counter` in a
              control run, to stand in for each plane's own counter.
  loop kind   the traffic's `loop` names one of `LOOPS` below, else the one
              subclass of `Loop` that `loops/<loop>.py` defines, with its
              own `setup`, `warm`, `op` (or `run`), `compare` and, where
              the inherited epoch count does not fit, `least_bytes`.  A
              kind found in neither stops the run before its set-up, with
              no result.

What the harness reads of a built `svc` (the whole contract): `svc.planes`,
each plane with `spec` (a `SketchSpec`: `counter.bits`, `packed`,
`storage_dtype`, `depth`), `names` (its tenants), `label`, and `tables` and
`tracker` (waited on by `Loop.drain`); and the counter
`svc.metrics.counter("plane_flushes", plane=label)` that each flush epoch
moves.  `names` lists every tenant once, each in some plane's `names`;
the loop's tenant t is `names[t]`.

The built-in loop kinds:

  closed_ingest  one `enqueue_many` per call, every tenant, as fast as the
                 service takes it; the window ends once a last `flush` has
                 landed, so every counted event is visible.
  open_read      a prefilled service; reads `query(tenant, keys)` due at
                 Poisson times at a fixed rate, timed from their due time.

The program's accuracy probe is off.  Its own `cms.*` spans never block
and record only while a profiler session runs (`--trace 1`); the harness
times its own calls on the host clock and, in a traced run, marks them with
`TraceAnnotation`s.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

import numpy as np

import reference
import streams
import tracefile

HERE = pathlib.Path(__file__).resolve().parent

TRACE_LEAD_S = 1.0   # steady window before the traced span
TRACE_SPAN_S = 3.0   # traced span of a --trace 1 run
SCHEDULE_SEED = 0    # the open loop's arrival order, the same for every seed


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# JAX set-up: compile cache in the checkout, compile counting, devices
# --------------------------------------------------------------------------

def cache_dir_for(root: pathlib.Path) -> pathlib.Path:
    """`JAX_COMPILATION_CACHE_DIR` where it lies inside the checkout, else
    `<checkout>/.jax_cache` (a fixed path: the path is part of the key)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        p = pathlib.Path(env).resolve()
        if p == root or root in p.parents:
            return p
    return root / ".jax_cache"


def setup_jax(root: pathlib.Path) -> str:
    import jax
    cache = cache_dir_for(root)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_enable_compilation_cache", True)
    # most of the program's programs are small eager ones, which the
    # default 1 s threshold would leave out of the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(cache)


class CompileCounter:
    """Counts JAX's compile and compile-cache events while `on`."""

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.counts: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _note(self, name: str) -> None:
        if self.on and ("compile" in name or "cache" in name):
            self.counts[name] = self.counts.get(name, 0) + 1

    def _dur(self, name, secs, **kw) -> None:
        self._note(name)

    def _ev(self, name, **kw) -> None:
        self._note(name)


def devices(require_tpu: bool, chips: int):
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise SystemExit(f"no TPU: JAX found {devs[0].platform}")
        if len(devs) < chips:
            raise SystemExit(f"the cell needs {chips} chips, JAX found "
                             f"{len(devs)}")
    return devs


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# --------------------------------------------------------------------------
# the deployment
# --------------------------------------------------------------------------

def counter_spec(d: dict):
    from repro.core.counters import CounterSpec
    return CounterSpec(kind=d["kind"], base=float(d["base"]),
                       bits=int(d["bits"]))


def tenant_names(plane: dict) -> list[str]:
    return [f"{plane['prefix']}{i:02d}" for i in range(int(plane["tenants"]))]


def build_service(cfg: dict, seed: int, counter: dict | None = None):
    """The configuration's `CountService`, tenants registered plane by plane
    (so registry order is plane order), probe off."""
    from repro.core.sketch import SketchSpec
    from repro.stream import CountService
    svc = CountService(queue_capacity=int(cfg["queue_capacity"]),
                       seed=int(seed), track_top=cfg["track_top"],
                       probe=None)
    names = []
    for plane in cfg["planes"]:
        spec = SketchSpec(width=int(plane["width"]),
                          depth=int(plane["depth"]),
                          counter=counter_spec(counter or plane["counter"]),
                          seed=int(cfg["sketch_seed"]),
                          packed=bool(cfg["packed"]))
        for n in tenant_names(plane):
            svc.add_tenant(n, spec=spec)
            names.append(n)
    return svc, names


def cell_bytes(spec) -> float:
    """Bytes of one stored cell (a packed lane holds several)."""
    return spec.counter.bits / 8 if spec.packed else \
        np.dtype(spec.storage_dtype).itemsize


class EpochLog:
    """Which calls each flush epoch landed, per plane, read off the
    service's `plane_flushes` counters around each harness call."""

    def __init__(self, svc):
        self.svc = svc
        self.planes = svc.planes
        self.pending = [[] for _ in self.planes]
        self.seen = self._counts()
        self.epochs: list[tuple[int, list[int]]] = []   # (plane, calls)
        self.recording = False

    def _counts(self) -> list[int]:
        return [int(self.svc.metrics.counter("plane_flushes",
                                             plane=p.label).value)
                for p in self.planes]

    def after(self, added: int | None) -> None:
        """After call number `added` (or None for a call that enqueued
        nothing): a plane whose counter moved landed what was pending
        before the call; an epoch inside a call that also enqueued runs
        before its appends."""
        now = self._counts()
        for i, (a, b) in enumerate(zip(self.seen, now)):
            if b > a:
                if self.recording:
                    self.epochs.append((i, self.pending[i]))
                self.pending[i] = []
            if added is not None:
                self.pending[i].append(added)
        self.seen = now


# --------------------------------------------------------------------------
# loops
# --------------------------------------------------------------------------

class Loop:
    """Shared machinery: timing records, annotations, drains."""

    def __init__(self, svc, names, cfg, trf, seed):
        self.svc, self.names, self.cfg, self.trf = svc, names, cfg, trf
        self.rng = np.random.default_rng(seed)
        self.annotate = False
        self.epochlog = EpochLog(svc)
        self.reset_record()
        self.ops_total = 0

    def span(self, name: str, **kw):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name, **kw)

    def count_op(self) -> None:
        self.rec["ops"] += 1
        self.ops_total += 1

    def reset_record(self) -> None:
        self.rec = {"ops": 0, "events": 0, "enqueue_s": 0.0,
                    "enqueue_events": 0, "lat_s": [], "lag_s": [],
                    "reads": []}
        self.epochlog.epochs = []

    def drain(self) -> None:
        import jax
        jax.block_until_ready([(p.tables, p.tracker)
                               for p in self.svc.planes])

    def enqueue(self, batch: dict, added: int) -> None:
        n = sum(v.size for v in batch.values())
        with self.span("bench.enqueue", events=n):
            t0 = time.perf_counter()
            self.svc.enqueue_many(batch)
            t1 = time.perf_counter()
        self.epochlog.after(added)
        self.rec["enqueue_s"] += t1 - t0
        self.rec["enqueue_events"] += n
        self.rec["events"] += n

    def run(self, seconds: float) -> None:
        """A closed loop: the next operation as soon as the last returns."""
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            self.op()

    def finish(self) -> None:
        """End of the window: nothing the window did may still be in flight."""
        self.drain()

    def plane_of(self):
        """tenant index -> plane index; each plane's (cell bytes, depth)."""
        where = {}
        geo = []
        for i, p in enumerate(self.svc.planes):
            geo.append((cell_bytes(p.spec), p.spec.depth))
            for n in p.names:
                where[self.names.index(n)] = i
        return where, geo

    def epoch_bytes(self) -> list[dict]:
        """Events and distinct keys per tenant (summed) of each recorded
        flush epoch, from the calls it landed."""
        where, geo = self.plane_of()
        out = []
        for plane, calls in self.epochlog.epochs:
            n = u = 0
            for t in where:
                if where[t] == plane and calls:
                    keys = np.concatenate([self.batch(c, t) for c in calls])
                    n += keys.size
                    u += np.unique(keys).size
            c, d = geo[plane]
            out.append({"events": n, "distinct": u, "cell_bytes": c,
                        "depth": d})
        return out

    def least_bytes(self) -> dict:
        return {"epochs": self.epoch_bytes()}

    def e2e(self, window_s: float) -> dict:
        out = {"events_per_s": (self.rec["events"] / window_s
                                if self.rec["events"] else None)}
        lat = self.rec["lat_s"]
        out["reads_per_s"] = len(lat) / window_s if lat else None
        return out


class ClosedIngest(Loop):
    """Closed loop of `enqueue_many` calls, no reads.  Call c sends every
    tenant the c-th slice of its stream, starting over at the stream's end,
    so each tenant's whole corpus loads its table."""

    def setup(self) -> None:
        self.base, self.streams = corpus_streams(self.rng, self.cfg["stream"],
                                                 len(self.names))
        self.n = int(self.trf["events_per_call"])
        if self.streams.shape[1] % self.n:
            raise ValueError("events_per_call must divide the stream")
        self.slices = self.streams.shape[1] // self.n
        self.calls = 0

    def batch(self, c: int, t: int) -> np.ndarray:
        s = (c % self.slices) * self.n
        return self.streams[t, s:s + self.n]

    def op(self) -> None:
        c = self.calls
        self.enqueue({nm: self.batch(c, t) for t, nm in enumerate(self.names)},
                     c)
        self.calls += 1
        self.count_op()

    def flush(self) -> None:
        with self.span("bench.flush"):
            self.svc.flush()
        self.epochlog.after(None)

    def warm(self) -> None:
        # batched appends (aligned and not), the overflow path with its
        # queue-pressure epoch and per-tenant appends, and explicit flushes
        # of a half-full and a full queue: every shape the window runs
        for _ in range(3):
            self.op()
        self.flush()
        for _ in range(int(self.trf["warm_calls"]) - 3):
            self.op()
        self.flush()
        self.drain()

    def finish(self) -> None:
        self.flush()
        self.drain()

    def compare(self) -> dict:
        """Every tenant's table, read back through the service after the
        window, at the keys it was sent among a hash-sampled slice of the
        corpus's keys.  Tenant t sent its stream `full` times over and then
        its first `part` events."""
        L = self.streams.shape[1]
        full, part = divmod(self.calls * self.n, L)
        srt = np.sort(self.base)
        keys = np.unique(srt[reference.hash_sampled(srt, 1 / 32)])
        in_base = reference.counts_in(srt, keys)
        groups = []
        for t, nm in enumerate(self.names):
            salted = keys ^ streams.tenant_salt(t)
            exact = full * in_base + reference.counts_in(
                np.sort(self.streams[t, :part]), salted)
            est = np.asarray(self.svc.query(nm, salted))
            groups.append((est[exact > 0], exact[exact > 0]))
        return reference.group_errors(groups)


class OpenRead(Loop):
    """Open loop of reads over a service prefilled with every tenant's
    whole stream."""

    def setup(self) -> None:
        _, self.streams = corpus_streams(self.rng, self.cfg["stream"],
                                         len(self.names))
        T, L = self.streams.shape
        # probe sets: every tenant equally often, in an order drawn from
        # the seed, and for each of `read_bigrams` positions of its stream
        # the bigram and both its unigrams (events 2i, 2i+1, 2i+2)
        R, m = int(self.trf["pool_reads"]), int(self.trf["read_bigrams"])
        self.read_tenant = self.rng.permutation(np.arange(R) % T)
        pos = self.rng.integers(0, (L - 3) // 2, size=(R, m)) * 2
        idx = pos[:, :, None] + np.arange(3)
        self.read_keys = np.stack(
            [self.streams[self.read_tenant[r]][idx[r]].ravel()
             for r in range(R)])
        self.answers: list[tuple[int, np.ndarray]] = []
        self.rate = float(self.trf["rate_per_s"])

    def prefill(self) -> None:
        k = int(self.trf["prefill_call"])
        for a in range(0, self.streams.shape[1], k):
            self.svc.enqueue_many({nm: self.streams[t, a:a + k]
                                   for t, nm in enumerate(self.names)})
            self.svc.flush()
        self.drain()

    def read(self, r: int) -> np.ndarray:
        t = int(self.read_tenant[r])
        return np.asarray(self.svc.query(self.names[t], self.read_keys[r]))

    def warm(self) -> None:
        self.prefill()
        # one read per tenant: each reads its own table row
        first = {}
        for r, t in enumerate(self.read_tenant):
            first.setdefault(int(t), r)
        for r in first.values():
            self.read(r)
        self.drain()

    def schedule(self, seconds: float) -> np.ndarray:
        """Due times (s from the window's start): one fixed order of one
        set of exponential gaps, the same in every run whatever the seed,
        so that seeds change which reads are due and not when.  Above
        capacity the backlog's age follows the arrivals' order closely."""
        k = int(np.ceil(seconds * self.rate * 1.25)) + 16
        q = (np.arange(k) + 0.5) / k
        gaps = -np.log1p(-q) / self.rate
        order = np.random.default_rng(SCHEDULE_SEED).permutation(k)
        return np.cumsum(gaps[order])

    def run(self, seconds: float) -> None:
        due = self.schedule(seconds)
        reads = self.rng.integers(0, self.read_keys.shape[0], size=due.size)
        t0 = time.perf_counter()
        for i in range(due.size):
            if due[i] > seconds:
                break
            at = t0 + due[i]
            now = time.perf_counter()
            if at - now > 2e-3:
                time.sleep(at - now - 1e-3)
            while time.perf_counter() < at:
                pass
            r = int(reads[i])
            with self.span("bench.read", read=r):
                t1 = time.perf_counter()
                est = self.read(r)
                t2 = time.perf_counter()
            self.rec["lag_s"].append(t1 - at)
            self.rec["lat_s"].append(t2 - at)
            self.rec["reads"].append(r)
            self.count_op()
            self.answers.append((r, est))
        # the window lasts its full length even when reads run out early
        while time.perf_counter() < t0 + seconds:
            time.sleep(1e-3)

    def compare(self) -> dict:
        """Every read answered in the window, against the exact counts of
        the tenant's stream."""
        sorted_t, exact = {}, {}
        for r in sorted({r for r, _ in self.answers}):
            t = int(self.read_tenant[r])
            if t not in sorted_t:
                sorted_t[t] = np.sort(self.streams[t])
            exact[r] = reference.counts_in(sorted_t[t], self.read_keys[r])
        return reference.group_errors(
            [(est, exact[r]) for r, est in self.answers])

    def least_bytes(self) -> dict:
        where, geo = self.plane_of()
        reads = []
        for r in self.rec["reads"]:
            cb, d = geo[where[int(self.read_tenant[r])]]
            reads.append({"probes": int(self.read_keys[r].size),
                          "distinct": int(np.unique(self.read_keys[r]).size),
                          "cell_bytes": cb, "depth": d})
        return {"reads": reads}


LOOPS = {"closed_ingest": ClosedIngest, "open_read": OpenRead}


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def loop_class(kind: str, cells: pathlib.Path = HERE) -> type:
    """The loop kind a traffic file names: a built-in of `LOOPS`, else the
    one subclass of `Loop` that `loops/<kind>.py` defines."""
    if kind in LOOPS:
        return LOOPS[kind]
    path = cells / "loops" / f"{kind}.py"
    if not path.is_file():
        raise SystemExit(f"unknown loop kind {kind!r}: not built in, and no "
                         f"{path}")
    mod = load_module(path, f"loop_{kind}")
    found = [v for v in vars(mod).values()
             if isinstance(v, type) and issubclass(v, Loop)
             and v.__module__ == mod.__name__]
    if len(found) != 1:
        raise SystemExit(f"{path} defines {len(found)} subclasses of "
                         "harness.Loop, not one")
    return found[0]


def build_for(config: str, cells: pathlib.Path = HERE):
    """`build` of `deploy/<config>.py` where the file exists, else
    `build_service`."""
    path = cells / "deploy" / f"{config}.py"
    if not path.is_file():
        return build_service
    return load_module(path, f"deploy_{config}").build


def corpus_streams(rng, s: dict, tenants: int):
    """The corpus's event stream (2 * corpus_tokens events), and each
    tenant's copy: rotated by an even offset of its own (so unigram-bigram
    alignment holds) and xor-salted (a bijection: each tenant counts keys
    of its own, with the corpus's multiplicities)."""
    if s["kind"] != "ngram_corpus":
        raise ValueError(f"unknown stream kind {s['kind']!r}")
    n = int(s["corpus_tokens"])
    tokens = streams.corpus_tokens(
        rng, n + 1, int(s["vocab_size"]), float(s["zipf_s"]),
        float(s["zipf_q"]), float(s["p_copy"]), int(s["copy_len"]))
    base = streams.ngram_events(tokens)[:2 * n]
    offs = rng.integers(0, n, size=tenants) * 2
    out = np.empty((tenants, base.size), np.uint32)
    for t in range(tenants):
        out[t] = np.roll(base, -int(offs[t])) ^ streams.tenant_salt(t)
    return base, out


def run_window(loop: Loop, seconds: float) -> None:
    loop.run(seconds)


# --------------------------------------------------------------------------
# a run
# --------------------------------------------------------------------------

def metric_reader(name: str, cells: pathlib.Path = HERE):
    return load_module(cells / "metrics" / f"{name}.py",
                       f"metric_{name}").read


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def run_cell(bench: dict, cell: str, seed: int, seconds: float, trace: bool,
             *, root: pathlib.Path, t_start: float, require_tpu: bool = True,
             cfg: dict | None = None, trf: dict | None = None,
             limits: dict | None = None, peaks: dict | None = None,
             control: bool = False, cells: pathlib.Path = HERE) -> dict:
    """Run one cell and return its result line (a dict).  A traced run
    leaves its trace and the harness's counts (`ctx.json`) in
    `<checkout>/.bench_trace/<cell>/` until the next traced run."""
    wl = next(w for w in bench["workloads"] if w["name"] == cell)
    cfg = cfg or load_json(cells / "configs" / f"{wl['config']}.json")
    trf = dict(trf or load_json(cells / "traffic" / f"{wl['traffic']}.json"))
    loop_cls = loop_class(trf["loop"], cells)
    build = build_for(wl["config"], cells)
    limits = limits or load_json(cells / "limits" / f"{cell}.json")
    cache = setup_jax(root)
    log(f"compile cache: {cache}")
    devs = devices(require_tpu, int(wl["chips"]))
    import jax
    from repro.kernels import ops
    if peaks is None:
        table = load_json(cells / "peaks.json")
        if devs[0].device_kind not in table:
            raise SystemExit(
                f"no peaks for device kind {devs[0].device_kind!r}")
        peaks = table[devs[0].device_kind]
    compiles = CompileCounter()

    svc, names = build(cfg, seed,
                       cfg["control_counter"] if control else None)
    loop = loop_cls(svc, names, cfg, trf, seed)
    loop.setup()
    loop.warm()
    loop.reset_record()
    loop.ops_total = 0
    setup_s = time.perf_counter() - t_start

    compiles.on = True
    trace_dir = root / ".bench_trace" / cell
    window_s = None
    with ops.audit_scope() as dispatches:
        if not trace:
            t0 = time.perf_counter()
            run_window(loop, seconds)
            loop.finish()
            window_s = time.perf_counter() - t0
        else:
            lead = min(TRACE_LEAD_S, seconds / 4)
            span_s = min(TRACE_SPAN_S, seconds)
            run_window(loop, lead)
            loop.drain()
            loop.reset_record()
            loop.epochlog.recording = True
            loop.annotate = True
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    run_window(loop, span_s)
                    loop.drain()
            finally:
                jax.profiler.stop_trace()
            loop.annotate = False
            loop.epochlog.recording = False
            ctx = {"cell": cell, "chips": int(wl["chips"]),
                   "enqueue_s": loop.rec["enqueue_s"],
                   "enqueue_events": loop.rec["enqueue_events"],
                   "lag_s": loop.rec["lag_s"], "lat_s": loop.rec["lat_s"],
                   **loop.least_bytes()}
            loop.finish()
    compiles.on = False
    ops_done = loop.ops_total
    log(f"window: {ops_done} ops, dispatches per op "
        + json.dumps({k: v / max(ops_done, 1)
                      for k, v in sorted(dispatches.items())}))
    log("compile events inside the window: " + json.dumps(compiles.counts))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(devs)}

    metrics = {}
    breakdown = None
    if not trace:
        e2e = loop.e2e(window_s)
        e2e["setup_s"] = setup_s
        for m in cell_metrics(bench, cell, "end_to_end"):
            v = e2e.get(m["name"])
            if v is None:
                raise RuntimeError(f"{m['name']} was not measured")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        with open(trace_dir / "ctx.json", "w") as f:
            json.dump(ctx, f)
        tr = tracefile.load(tracefile.find_xplane(trace_dir), ctx, peaks)
        for m in cell_metrics(bench, cell, "per_layer"):
            v = metric_reader(m["name"], cells)(tr)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = tr.breakdown()

    # the reference runs once the window has closed and the peak is read
    t_ref = time.perf_counter()
    got = loop.compare()
    log(f"reference: {got['compared']} answers compared in "
        f"{time.perf_counter() - t_ref:.1f} s")
    checks = {}
    for name, lim in limits.items():
        checks[name] = {"value": got[name], "limit": lim}
    correct = all(bool(c["value"] <= c["limit"]) for c in checks.values())
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": correct, "attempted": ops_done, "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
