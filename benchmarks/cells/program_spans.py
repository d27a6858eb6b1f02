"""The program's own host spans in a traced run.

`CountService` marks each layer boundary of its host path with a
`jax.profiler.TraceAnnotation` named `cms.<name>` (enqueue, flush epoch and
its steps, query and its steps), whose event stats are the span's counts.
The spans land in the `.xplane.pb` the traced run leaves in
`.bench_trace/<cell>/` under the working directory, where `run.py` puts
it, on the same clock as `tracefile.Trace` (`tr.lo`, `tr.hi`, `tr.ops`).

`spans(tr)` gives those that start inside the traced window, each with its
name, start, end, stats, thread and nesting depth among the program's
spans on its thread (0 = outermost).  The file is parsed once per process;
a test can hand spans in directly as `tr.program_spans = nest(...)`.  A
program without these spans yields none, and their readers report nothing.
"""
from __future__ import annotations

import dataclasses
import pathlib

import numpy as np

import tracefile

PREFIX = "cms."

_PARSED: dict = {}


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float        # ns, the trace's clock
    end: float
    stats: dict
    thread: str
    depth: int

    @property
    def dur(self) -> float:
        return self.end - self.start

    def holds(self, other: "Span") -> bool:
        return (other.thread == self.thread and self.start <= other.start
                and other.end <= self.end)


def nest(raw, thread: str = "main") -> list[Span]:
    """Spans of one thread from (name, start, end, stats) tuples, each
    with its depth: how many of the others enclose it."""
    out, ends = [], []
    for name, s, e, stats in sorted(raw, key=lambda r: (r[1], -r[2])):
        while ends and ends[-1] <= s:
            ends.pop()
        out.append(Span(name, s, e, dict(stats), thread, len(ends)))
        ends.append(e)
    return out


def load(path) -> list[Span]:
    """Every `cms.*` span of an `.xplane.pb`, over all host threads."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            raw = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for e in line.events if e.name.startswith(PREFIX)]
            out += nest(raw, f"{plane.name}/{line.name}")
    return out


def _of_file(cell: str) -> list[Span]:
    d = pathlib.Path.cwd() / ".bench_trace" / cell
    try:
        path = tracefile.find_xplane(d)
    except FileNotFoundError:
        return []
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if key not in _PARSED:
        _PARSED[key] = load(path)
    return _PARSED[key]


def spans(tr) -> list[Span]:
    """The program's spans that start inside `tr`'s traced window."""
    got = getattr(tr, "program_spans", None)
    if got is None:
        cell = tr.ctx.get("cell")
        got = _of_file(cell) if cell else []
    return [s for s in got if tr.lo <= s.start <= tr.hi]


def outer(tr, name: str) -> list[Span]:
    """The outermost spans called `cms.<name>` in the window."""
    return [s for s in spans(tr) if s.depth == 0 and s.name == PREFIX + name]


def named(tr, name: str) -> list[Span]:
    return [s for s in spans(tr) if s.name == PREFIX + name]


def mean_ms(sel: list[Span]):
    """Mean duration of the spans, in ms (None without any)."""
    if not sel:
        return None
    return float(np.mean([s.dur for s in sel])) * 1e-6
