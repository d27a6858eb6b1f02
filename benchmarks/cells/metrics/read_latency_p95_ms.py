"""A read's latency in the traced span, in ms: the 95th percentile of answer
time minus due time, on the harness's clock.  Where reads are offered
faster than the service answers them, this is the backlog's age, which
grows through the span: a rate of answers is the cell's end-to-end
number, and this its tail."""
import numpy as np


def read(tr):
    lat = tr.ctx.get("lat_s")
    if not lat:
        return None
    return float(np.percentile(lat, 95)) * 1e3
