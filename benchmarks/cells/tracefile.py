"""Reduction of a `jax.profiler` trace to what the per-layer readers need.

A traced run writes one `.xplane.pb`.  From it this keeps:

  * device ops: every event on a device plane's "XLA Ops" line, with the
    program ("XLA Modules" event) it ran in;
  * the harness's spans: host events named `bench.*` (`TraceAnnotation`s
    around the harness's own calls; `bench.window` brackets the traced
    window);
  * `ctx`: the harness's own counts for the traced window (epochs and reads
    with their events and distinct keys, host enqueue time, lags);
  * `peaks`: the device's row of `peaks.json`.

Busy time is the union of device-op intervals inside the window, averaged
over the cell's chips (`ctx["chips"]`, so a chip that ran nothing counts
as idle; without it, over the devices that ran any op); idle share is 1
minus busy over window.
"""
from __future__ import annotations

import pathlib
import re

import numpy as np


def find_xplane(d: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(d).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no trace under {d}")
    return found[-1]


def union_ns(iv: np.ndarray) -> float:
    """Total length of the union of (start, end) intervals."""
    if iv.size == 0:
        return 0.0
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    total, cur_s, cur_e = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    return float(total + cur_e - cur_s)


def gaps_ns(iv: np.ndarray, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle gaps (start, end) of the union of intervals inside [lo, hi]."""
    out = []
    t = lo
    for s, e in iv[np.argsort(iv[:, 0], kind="stable")]:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def matches(name: str, patterns) -> bool:
    return any(re.search(p, name) for p in patterns)


class Trace:
    """Device ops, host spans and the harness's counts of one traced window."""

    def __init__(self, ops, spans, ctx: dict, peaks: dict):
        # ops: (device, start_ns, end_ns, op name, program name, program run)
        self.ops = ops
        self.spans = spans          # list of (name, start_ns, end_ns)
        self.ctx = ctx
        self.peaks = peaks
        win = [s for s in spans if s[0] == "bench.window"]
        if len(win) != 1:
            raise ValueError(f"expected one bench.window span, got {len(win)}")
        self.lo, self.hi = win[0][1], win[0][2]
        self.devices = sorted({o[0] for o in ops})

    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _clip(self, sel) -> np.ndarray:
        iv = np.array([(max(o[1], self.lo), min(o[2], self.hi))
                       for o in sel], dtype=np.float64).reshape(-1, 2)
        return iv[iv[:, 1] > iv[:, 0]]

    def busy_s(self) -> float:
        """Union of device-op time in the window, averaged over the cell's
        chips (or over more devices, where more ran ops)."""
        chips = max(int(self.ctx.get("chips", 0)), len(self.devices))
        if not chips:
            return 0.0
        return self._union_s(self.ops) / chips

    def program_time_s(self, patterns) -> float:
        """Device time of the ops of programs whose name matches."""
        sel = [o for o in self.ops if matches(o[4], patterns)]
        return self._union_s(sel)

    def _union_s(self, sel) -> float:
        # ops can nest (a loop and its body), so time is a union, per device
        return sum(union_ns(self._clip([o for o in sel if o[0] == d]))
                   for d in self.devices) * 1e-9

    def program_runs(self, patterns) -> int:
        """Executions of matching programs that started in the window."""
        runs = {(o[0], o[4], o[5]) for o in self.ops
                if matches(o[4], patterns) and self.lo <= o[1] <= self.hi}
        return len(runs)

    def span_time_s(self, span: str, exclude) -> float:
        """Device time of ops that start inside a `span` host span, leaving
        out programs whose name matches `exclude`."""
        spans = np.array([(s, e) for n, s, e in self.spans if n == span],
                         dtype=np.float64).reshape(-1, 2)
        if spans.size == 0:
            return 0.0
        spans = spans[np.argsort(spans[:, 0])]
        sel = []
        for o in self.ops:
            if matches(o[4], exclude):
                continue
            i = np.searchsorted(spans[:, 0], o[1], side="right") - 1
            if i >= 0 and o[1] <= spans[i, 1]:
                sel.append(o)
        return self._union_s(sel)

    def breakdown(self) -> dict:
        """Top device programs by time, and the longest idle gaps by the
        harness span they fall in."""
        by_prog = {p: self._union_s([o for o in self.ops if o[4] == p])
                   for p in {o[4] for o in self.ops}}
        top = sorted(((p, t) for p, t in by_prog.items() if t > 0),
                     key=lambda kv: -kv[1])[:10]
        idle: dict[str, float] = {}
        for d in self.devices:
            iv = self._clip([o for o in self.ops if o[0] == d])
            for g0, g1 in gaps_ns(iv, self.lo, self.hi):
                name = self.span_at((g0 + g1) / 2)
                idle[name] = idle.get(name, 0.0) + (g1 - g0) * 1e-9
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}

    def span_at(self, t: float) -> str:
        """The innermost harness span (other than the window) covering t."""
        best = None
        for n, s, e in self.spans:
            if n != "bench.window" and s <= t <= e:
                if best is None or e - s < best[2] - best[1]:
                    best = (n, s, e)
        return best[0] if best else "between harness calls"


def load(path, ctx: dict, peaks: dict) -> Trace:
    """Read an `.xplane.pb` into a `Trace`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            mods = []
            raw = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
                elif line.name == "XLA Ops":
                    raw = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
            mods.sort()
            starts = np.array([m[0] for m in mods], dtype=np.float64)
            for s, e, name in raw:
                i = int(np.searchsorted(starts, s, side="right")) - 1
                if i >= 0 and s <= mods[i][1]:
                    prog, run = mods[i][2], i
                else:
                    prog, run = "(no program)", -1
                ops.append((plane.name, s, e, name, prog, run))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return Trace(ops, spans, ctx, peaks)
