"""Streaming counting plane: time-scoped sketches + multi-tenant serving.

  * `window`  — ring of B bucket sketches (sliding-window counts) and an
    exponential-decay variant (recency-weighted counts), both built from
    the paper's CML counters without changing their semantics.
  * `service` — multi-tenant registry bucketed into spec-sharing planes:
    each plane stacks its tenants' tables into one (T, d, w) array,
    buffers events in a device-resident ring (scatter-append kernel), and
    ingests/serves the whole plane with single fused Pallas launches.
  * `tiering` — hot/cold plane storage: `TierSpec(max_hot_tenants=N)`
    keeps each plane's top-N active tenants device-resident and parks the
    rest in a host-side cold store with buffered spill, 10-100x more
    tenants than device memory holds with bit-identical answers.
"""
from repro.stream.window import (DecayedSketch, WindowSpec, WindowedSketch,
                                 decay, decayed_init, decayed_query,
                                 decayed_rotate, decayed_update,
                                 interval_epoch, interval_lag,
                                 window_advance_steps, window_advance_to,
                                 window_init, window_query,
                                 window_query_many, window_rotate,
                                 window_update, window_weights,
                                 window_weights_stacked)
from repro.stream.service import (CountService, RoutedPlane, TenantPlane,
                                  WindowPlane)
from repro.stream.tiering import TierSpec, tier_memory_bytes

__all__ = [
    "WindowSpec", "WindowedSketch", "window_init", "window_update",
    "window_rotate", "window_advance_steps", "window_advance_to",
    "window_query", "window_query_many", "window_weights",
    "window_weights_stacked", "interval_epoch", "interval_lag",
    "DecayedSketch", "decay", "decayed_init", "decayed_rotate",
    "decayed_update", "decayed_query",
    "CountService", "RoutedPlane", "TenantPlane", "WindowPlane",
    "TierSpec", "tier_memory_bytes",
]
