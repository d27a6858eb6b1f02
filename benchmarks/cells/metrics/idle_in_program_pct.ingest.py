"""Of the device's idle time in the window, the share during which the
host was inside one of the program's outermost `cms.*` spans, in %.

Idle time is the gaps between device ops (`tracefile.gaps_ns`), summed
over the devices that ran any op.  A low share means the chip waits on the
caller, not on the service's own host path.  Time the host spends blocked
on the device falls in the device's busy time, not in these gaps; a gap
inside a span is host work, or a host-side wait the device trace does not
show (a transfer, a lock)."""
import numpy as np

import program_spans
import tracefile


def _union(iv: list) -> list:
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a: list, b: list) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(tr):
    host = _union([(max(s.start, tr.lo), min(s.end, tr.hi))
                   for s in program_spans.spans(tr) if s.depth == 0])
    if not host or not tr.devices:
        return None
    idle = inside = 0.0
    for d in tr.devices:
        iv = np.array([(max(o[1], tr.lo), min(o[2], tr.hi))
                       for o in tr.ops if o[0] == d],
                      dtype=np.float64).reshape(-1, 2)
        gaps = tracefile.gaps_ns(iv[iv[:, 1] > iv[:, 0]], tr.lo, tr.hi)
        idle += sum(g1 - g0 for g0, g1 in gaps)
        inside += _overlap(gaps, host)
    if idle <= 0:
        return None
    return inside / idle * 100
