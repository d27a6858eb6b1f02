"""Share of the HBM roofline that the read path reaches, in %.

The least bytes of a read of P probes with U distinct keys (per tenant,
summed) are its keys in (4 P), one cell per row per distinct key
(c * d * U) and its float32 answers out (4 P).  Time is the device time of
the ops that start inside the harness's `bench.read` spans, leaving out the
flush epoch's programs (a read flushes its plane first) and the queue
appends still running from the enqueue before it: the read path's eager
programs carry no stable names yet.
"""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "flush_device_ms", pathlib.Path(__file__).with_name("flush_device_ms.py"))
_fd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fd)

EXCLUDE = _fd.PATTERNS + (r"queue_append",)


def least_bytes(read: dict) -> float:
    return (8 * read["probes"]
            + read["cell_bytes"] * read["depth"] * read["distinct"])


def read(tr):
    reads = tr.ctx.get("reads")
    if not reads:
        return None
    t = tr.span_time_s("bench.read", EXCLUDE)
    if t <= 0:
        return None
    least = sum(least_bytes(r) for r in reads)
    return least / tr.peaks["hbm_bytes_per_s"] / t * 100
