"""Count-based embedding admission, gated by a sketch (recsys integration).

Production embedding tables cannot afford a row per raw id; ids are admitted
to the trainable table only once "hot enough".  The classic implementation
needs an exact id->count map (unbounded memory); here the CMLS sketch
provides the counts in constant memory — precisely the paper's
memory/error trade at the point where it matters most, since admission
decisions are all about *low-frequency* ids, where CMLS's relative error is
2-12x better than linear CMS at equal bytes (paper Fig. 1).

Cold ids fall back to a small shared bucket space (hash trick), so the model
stays total: every id maps to some row.

Two decision sources share one row-mapping policy (`rows_of`):

  * `admit` / `observe_and_admit` — threshold the sketch estimate
    directly.  `observe_and_admit` counts through the chunk-sequential
    XLA engine `ops.update_xla` by default (bit-identical to the fused
    Pallas wrappers, which `engine="kernel"` selects) and validates ids
    at the API boundary exactly like `CountService.enqueue`
    (floats/negatives/>32-bit raise).
  * `admit_tracked` — decide from a heavy-hitter tracker heap instead of
    re-querying the sketch: an id is admitted iff it is a tracked
    candidate whose stored estimate clears the threshold.  This is the
    service's tracker-fed admission plane
    (`CountService.add_tenant(admission=...)`): the tracker is refreshed
    by every flush epoch, so hot keys acquire private rows automatically
    and decisions stay O(K) per lookup with no extra sketch launch.
    The heap bounds the admitted set to the top `track_top` candidates —
    size K comfortably above the expected hot-set size.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sketch as sk
from repro.core.hashing import mix32


@dataclasses.dataclass(frozen=True)
class AdmissionSpec:
    threshold: float = 8.0      # min estimated count before a private row
    n_fallback: int = 1024      # shared rows for cold ids
    table_rows: int = 1 << 20   # private rows (admitted ids hash here)


def _validated(ids):
    """API-boundary key validation; traced ids pass through (their
    producer — e.g. the service ring — already validated them), and
    concrete uint32 device arrays stay on device (every uint32 is a valid
    key, so there is nothing to check and no reason to force a
    device->host sync on the hot path — callers under a
    transfer_guard_device_to_host would otherwise raise)."""
    if isinstance(ids, jax.core.Tracer):
        return ids
    if isinstance(ids, jax.Array) and ids.dtype == jnp.uint32:
        return ids
    return jnp.asarray(sk.as_uint32_keys(ids).reshape(np.shape(ids)))


def rows_of(ids: jnp.ndarray, admitted: jnp.ndarray, spec: AdmissionSpec
            ) -> jnp.ndarray:
    """Map ids -> embedding rows given their admission mask.

    Admitted ids occupy [n_fallback, n_fallback + table_rows); cold ids
    share [0, n_fallback).  The row policy is independent of how the mask
    was decided, so sketch-thresholded and tracker-fed admission agree on
    layout.
    """
    hot_row = (mix32(ids.astype(jnp.uint32)) % jnp.uint32(spec.table_rows)
               ).astype(jnp.int32) + spec.n_fallback
    cold_row = (mix32(ids.astype(jnp.uint32) ^ jnp.uint32(0xC01D))
                % jnp.uint32(spec.n_fallback)).astype(jnp.int32)
    return jnp.where(admitted, hot_row, cold_row)


def admit(sketch: sk.Sketch, ids: jnp.ndarray, spec: AdmissionSpec
          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Map raw ids -> table rows under the admission policy.

    Returns (rows, admitted_mask).  Admitted ids occupy
    [n_fallback, n_fallback + table_rows); cold ids share [0, n_fallback).
    """
    est = sk.query(sketch, ids)
    admitted = est >= spec.threshold
    return rows_of(ids, admitted, spec), admitted


def admit_tracked(keys: jnp.ndarray, estimates: jnp.ndarray,
                  filled: jnp.ndarray, ids: jnp.ndarray, spec: AdmissionSpec
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Admission decisions from a heavy-hitter tracker heap.

    keys/estimates/filled: one tenant's (K,) tracker row (e.g.
    `CountService` tracker state, or the all-gathered candidate merge of
    `sharded.routed_admit`).  An id is admitted iff it matches a filled
    candidate whose stored estimate >= spec.threshold — the tracker is
    refreshed per flush epoch, so this needs no sketch query at decision
    time and costs O(N * K) lane compares.  Returns (rows, admitted_mask)
    aligned with ids.
    """
    ids = _validated(ids)
    if ids.ndim != 1:
        raise ValueError(f"ids must be 1D, got shape {ids.shape}")
    hot = filled & (estimates >= spec.threshold)
    eq = ids.astype(jnp.uint32)[:, None] == keys.astype(jnp.uint32)[None, :]
    admitted = jnp.any(eq & hot[None, :], axis=1)
    return rows_of(ids, admitted, spec), admitted


def observe_and_admit(sketch: sk.Sketch, ids: jnp.ndarray, rng: jax.Array,
                      spec: AdmissionSpec, engine: str = "auto"
                      ) -> tuple[sk.Sketch, jnp.ndarray, jnp.ndarray]:
    """Streaming form: count this batch, then admit against the new state.

    ids are validated like `CountService.enqueue` (floats, negatives, and
    >32-bit values raise — no silent uint32 truncation).  engine:
    "kernel" counts/queries through the fused Pallas wrappers
    (`kernels.ops.update`/`query` — the table stays VMEM-resident across
    the update sweep); "xla" the jitted chunk-sequential reference
    (`ops.update_xla` — NOT the one-shot `sk.update_batched`, whose
    min-reads diverge from the kernel grid on cross-chunk cell
    collisions); "auto" takes the XLA engine on every platform (no
    Pallas kernel lowers for TPU yet, and off-TPU the interpreter would
    only add cost — the two engines are bit-identical, so the choice is
    purely a dispatch-cost call).
    """
    if engine not in ("auto", "kernel", "xla"):
        raise ValueError(f"unknown admission engine {engine!r}")
    from repro.kernels import ops  # lazy: keep core import-light
    ids = _validated(ids)
    if engine == "auto":
        engine = "xla"
    elif engine == "kernel" and not ops.fits_vmem(sketch.spec):
        # an explicit kernel request past VMEM raises (as in
        # ops.update_score_rows) instead of silently downgrading
        raise ValueError("table exceeds the VMEM budget; use engine='xla'")
    if engine == "kernel":
        sketch = ops.update(sketch, ids, rng)
        est = ops.query(sketch, ids)
    else:
        sketch = ops.update_xla(sketch, ids, rng)
        est = sk.query(sketch, ids)
    admitted = est >= spec.threshold
    return sketch, rows_of(ids, admitted, spec), admitted
