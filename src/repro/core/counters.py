"""Counter cell semantics: linear (classic CMS) and logarithmic (Morris).

The paper (Alg. 1/2) defines, for log base b > 1:

  IncreaseDecision(c) = True w.p. b^-c
  PointValue(c)       = 0 if c == 0 else b^(c-1)
  Value(c)            = PointValue(c) if c <= 1 else (1 - b^(c+1-1)) / (1 - b)

which collapses to the standard unbiased Morris estimator

  Value(c) = (b^c - 1) / (b - 1)        (equals 0 at c=0 and 1 at c=1)

since Value(c+1) - Value(c) = b^c = 1 / P(increment at state c).

`nfold` generalizes a single IncreaseDecision step to adding n events at
once: move n units in estimate space, then stochastically round back to a
counter state.  For n == 1 this reduces *exactly* to the paper's update
(increment w.p. b^-c), so the batched TPU path is an unbiased
generalization, not an approximation of a different estimator.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

_DTYPES = {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32}


@dataclasses.dataclass(frozen=True)
class CounterSpec:
    """Static description of one sketch cell.

    kind: "linear" (classic CMS cell) or "log" (Morris counter).
    base: log base b > 1 (ignored for linear).
    bits: cell width in bits (8, 16, or 32).
    """

    kind: str = "log"
    base: float = 1.00025
    bits: int = 16

    def __post_init__(self):
        if self.kind not in ("linear", "log"):
            raise ValueError(f"unknown counter kind {self.kind!r}")
        if self.kind == "log" and not self.base > 1.0:
            raise ValueError("log counter needs base > 1")
        if self.bits not in _DTYPES:
            raise ValueError(f"bits must be one of {sorted(_DTYPES)}")

    @property
    def dtype(self):
        return _DTYPES[self.bits]

    @property
    def cells_per_lane(self) -> int:
        """How many cells fit in one packed uint32 storage lane."""
        return 32 // self.bits

    @property
    def max_state(self) -> int:
        return (1 << self.bits) - 1

    @property
    def max_value(self) -> float:
        """Largest representable estimate (saturation point)."""
        if self.kind == "linear":
            return float(self.max_state)
        return float(math.expm1(self.max_state * math.log(self.base)) / (self.base - 1.0))

    # ---- estimate-space transforms (all float32, vectorized) ----

    def decode(self, state: jnp.ndarray) -> jnp.ndarray:
        """Counter state -> unbiased event-count estimate (paper's VALUE)."""
        s = state.astype(jnp.float32)
        if self.kind == "linear":
            return s
        logb = jnp.float32(math.log(self.base))
        # multiply by the float32 reciprocal rather than divide: XLA
        # rewrites division by a constant into exactly this inside a jit,
        # so eager and fused decodes (the read path vs the flush epoch's
        # re-score) agree bit for bit
        inv = np.float32(1.0) / np.float32(self.base - 1.0)
        return jnp.expm1(s * logb) * inv

    def point_mass(self, state: jnp.ndarray) -> jnp.ndarray:
        """Value(c+1) - Value(c) = b^c: estimate mass of one state step."""
        s = state.astype(jnp.float32)
        if self.kind == "linear":
            return jnp.ones_like(s)
        logb = jnp.float32(math.log(self.base))
        return jnp.exp(s * logb)

    def increase_prob(self, state: jnp.ndarray) -> jnp.ndarray:
        """P(IncreaseDecision(c)) = b^-c (paper Alg. 1); 1 for linear."""
        s = state.astype(jnp.float32)
        if self.kind == "linear":
            return jnp.ones_like(s)
        logb = jnp.float32(math.log(self.base))
        return jnp.exp(-s * logb)

    def encode_floor(self, value: jnp.ndarray) -> jnp.ndarray:
        """Largest state c with Value(c) <= value (float32 in, float32 out)."""
        v = value.astype(jnp.float32)
        if self.kind == "linear":
            return jnp.floor(v)
        logb = jnp.float32(math.log(self.base))
        c = jnp.floor(jnp.log1p(v * jnp.float32(self.base - 1.0)) / logb)
        # guard float roundoff both ways: never let Value(c) exceed v by a
        # full step, and never stop one state short when Value(c + 1) <= v
        # (log1p rounds some exact decodes, e.g. of CMLS8 state 246, down)
        too_high = self.decode(c) > v + 1e-6 * jnp.maximum(v, 1.0)
        c = c - too_high.astype(jnp.float32)
        too_low = self.decode(c + 1.0) <= v
        return jnp.maximum(c + too_low.astype(jnp.float32), 0.0)

    def reencode_stochastic(self, value: jnp.ndarray,
                            rng: "jax.Array | None" = None) -> jnp.ndarray:
        """Estimate-space value -> counter state, unbiased when rng given.

        Floor state plus a Bernoulli bump with probability equal to the
        residual in units of the local point mass, so
        E[decode(reencode_stochastic(v))] == v (clipped at max_state).
        With rng None the floor state is returned (deterministic
        under-estimate by < one point mass).  Shared by
        `sketch.merge(mode="estimate_sum")` and `stream.window.decay`.
        Returns float32 states; callers cast to the cell dtype.
        """
        v = value.astype(jnp.float32)
        s = self.encode_floor(v)
        if rng is not None:
            frac = (v - self.decode(s)) / self.point_mass(s)
            s = s + (jax.random.uniform(rng, s.shape) < frac)
        return jnp.clip(s, 0.0, float(self.max_state))

    def nfold(self, state: jnp.ndarray, n: jnp.ndarray, uniform: jnp.ndarray) -> jnp.ndarray:
        """Add n >= 0 events to counter `state` in one step.

        Unbiased in estimate space; for n == 1 this is exactly the paper's
        probabilistic increment.  `uniform` ~ U[0,1) drives the stochastic
        rounding (one uniform per counter).
        Returns the new state with the same dtype as `state`, saturating at
        max_state (the residual-error floor discussed in the paper's §4).
        """
        n = n.astype(jnp.float32)
        if self.kind == "linear":
            # Integer-space path: float32 rounds past 2^24, so a uint32
            # linear cell computed in estimate space would drift from its
            # own state.  Split n into whole + fractional parts (exact in
            # float32 for the whole part below 2^24, and any float32 above
            # 2^24 is already whole), bump stochastically on the fraction,
            # and add with room-clamped uint32 saturation.  Matches the
            # old float path bit-for-bit wherever that path was exact.
            s_u = state.astype(jnp.uint32)
            n_int = jnp.floor(n)
            frac = n - n_int
            bump = (uniform < frac).astype(jnp.uint32)
            room = jnp.uint32(self.max_state) - s_u
            add_f = jnp.minimum(n_int, jnp.float32(2147483648.0))
            add_u = jnp.minimum(add_f.astype(jnp.uint32) + bump, room)
            return (s_u + add_u).astype(state.dtype)
        s = state.astype(jnp.float32)
        v2 = self.decode(state) + n
        c2 = jnp.maximum(self.encode_floor(v2), s)  # monotone: never decrease
        frac = (v2 - self.decode(c2)) / self.point_mass(c2)
        inc = (uniform < frac).astype(jnp.float32)
        new = jnp.where(n > 0, c2 + inc, s)
        new = jnp.clip(new, 0.0, float(self.max_state))
        return new.astype(state.dtype)


def pack_table(table: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Pack a (..., w) table of `bits`-wide cell states into uint32 lanes.

    Cell j of a row lands in lane j // cpl at bit offset (j % cpl) * bits
    (little-endian within the lane), so the returned array has shape
    (..., w // cpl) where cpl = 32 // bits.  bits == 32 is the identity
    layout (one cell per lane).
    """
    cpl = 32 // bits
    if cpl == 1:
        return table.astype(jnp.uint32)
    *lead, w = table.shape
    if w % cpl:
        raise ValueError(f"width {w} not a multiple of cells_per_lane {cpl}")
    grouped = table.astype(jnp.uint32).reshape(*lead, w // cpl, cpl)
    out = jnp.zeros((*lead, w // cpl), jnp.uint32)
    for s in range(cpl):
        out = out | (grouped[..., s] << jnp.uint32(s * bits))
    return out


def unpack_table(lanes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Inverse of `pack_table`: (..., w/cpl) uint32 lanes -> (..., w) states.

    Returns uint32 values (each < 2**bits); callers cast to the cell dtype.
    """
    cpl = 32 // bits
    if cpl == 1:
        return lanes.astype(jnp.uint32)
    mask = jnp.uint32((1 << bits) - 1)
    parts = [(lanes >> jnp.uint32(s * bits)) & mask for s in range(cpl)]
    return jnp.stack(parts, axis=-1).reshape(*lanes.shape[:-1],
                                             lanes.shape[-1] * cpl)


# The paper's three evaluated variants (§3.2), importable by name.
CMS32 = CounterSpec(kind="linear", base=1.0 + 1e-9, bits=32)
CMLS16 = CounterSpec(kind="log", base=1.00025, bits=16)
CMLS8 = CounterSpec(kind="log", base=1.08, bits=8)
