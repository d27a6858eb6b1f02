"""How late the load generator issued its reads, in ms: the 95th percentile
of issue time minus due time, on the harness's clock.  A starved generator
shows here and not as a fast service."""
import numpy as np


def read(tr):
    lag = tr.ctx.get("lag_s")
    if not lag:
        return None
    return float(np.percentile(lag, 95)) * 1e3
