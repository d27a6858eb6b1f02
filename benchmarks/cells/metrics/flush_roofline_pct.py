"""Share of the HBM roofline that the flush epochs reach, in %.

The least bytes an epoch must move are those of the work itself, whatever
the implementation: 4 bytes per event landed (the key, read once), and for
each distinct key of each tenant one cell per row read and written
(2 * c * d * U, c bytes per stored cell, d rows).  Deduplication cannot go
below U, so a share over 100% means U was miscounted.  Time is the device
time of the epoch programs (the patterns of `flush_device_ms`).
"""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "flush_device_ms", pathlib.Path(__file__).with_name("flush_device_ms.py"))
_fd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fd)


def least_bytes(epoch: dict) -> float:
    return (4 * epoch["events"]
            + 2 * epoch["cell_bytes"] * epoch["depth"] * epoch["distinct"])


def read(tr):
    epochs = tr.ctx.get("epochs")
    if not epochs:
        return None
    t = tr.program_time_s(_fd.PATTERNS)
    if t <= 0:
        return None
    least = sum(least_bytes(e) for e in epochs)
    return least / tr.peaks["hbm_bytes_per_s"] / t * 100
