"""Host spans of the serving path, on the profiler's clock.

A span is a `jax.profiler.TraceAnnotation` named `cms.<name>`: it lands in
the same `jax.profiler` trace, on the same clock, as the device ops it
causes, so a trace shows where the host spends the time between them.
Keyword arguments are the span's counts (event stats in the trace);
counts known only at close go through `set_metadata`, which callers
compute only while `recording()` says a profiler session records.  A span
opened with `cpu=True` also reports `cpu_ns`, the calling thread's CPU
time inside it: wall time less what the thread spent blocked, on the
device or otherwise.

A span never blocks: it adds no wait on the device, no device-to-host
read and no dispatch.  With no session running it costs one
`TraceAnnotation.is_enabled()` check and records nothing; the profiler
keeps recorded spans in memory and writes them out when its session ends
(`jax.profiler.trace(dir)` / `start_trace` .. `stop_trace`).
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

PREFIX = "cms."

recording = TraceAnnotation.is_enabled


class _Off:
    """The span handed out while no profiler session records: inert."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **counts) -> None:
        return None


_OFF = _Off()


class _CpuSpan(TraceAnnotation):
    """A recording span that adds `cpu_ns` at close."""

    def __enter__(self) -> "_CpuSpan":
        super().__enter__()
        self._cpu0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.set_metadata(cpu_ns=time.thread_time_ns() - self._cpu0)
        return super().__exit__(*exc)


def span(name: str, cpu: bool = False, **counts):
    """Context manager: the host span `cms.<name>` with `counts` as args,
    and `cpu_ns` at close if `cpu` (a shared inert span while no profiler
    session records)."""
    if not recording():
        return _OFF
    return (_CpuSpan if cpu else TraceAnnotation)(PREFIX + name, **counts)
