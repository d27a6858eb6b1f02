"""Mixture-of-Experts FFN: top-k routing, capacity-based sort dispatch.

Dispatch is the sorted/grouped form (not the GShard one-hot einsum): tokens
are ranked within their expert by a stable sort, dropped past the capacity,
scattered into (E, C, D) slots, batch-matmul'd per expert, and combined with
their router gates.  This keeps dispatch memory at O(T * k * D) instead of
O(T * E * C) and lowers to gather/scatter + one batched GEMM, which XLA SPMD
partitions cleanly over the "experts" axis (expert parallelism).

Covers: DeepSeek-V2 (64 routed top-6 + 2 shared, normalized top-k gates)
and Llama-4 Scout (16 routed top-1 + 1 shared).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import mlp_apply, mlp_specs
from repro.models.params import P
from repro.sharding import constrain


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    d_ff_shared: Optional[int] = None   # default n_shared * d_ff_expert
    capacity_factor: float = 1.25
    norm_topk: bool = False             # DeepSeek renormalizes top-k gates
    aux_weight: float = 1e-2
    impl: str = "gspmd"                 # "gspmd" (sort+scatter, auto-sharded)
                                        # | "a2a" (manual expert parallelism)
    wire_capacity_factor: float = 1.5   # a2a: per-destination-shard slack

    @property
    def shared_ff(self) -> int:
        if self.n_shared == 0:
            return 0
        return self.d_ff_shared or self.n_shared * self.d_ff_expert


def moe_specs(c: MoEConfig) -> dict:
    # expert weights: EP over "experts" (-> model axis); the per-expert ff
    # dim uses its own logical axis ("expert_mlp" -> unsharded) so one spec
    # never maps the model axis twice
    specs = {
        "router": P((c.d_model, c.n_experts), ("embed", None), "normal:0.02"),
        "gate": P((c.n_experts, c.d_model, c.d_ff_expert),
                  ("experts", "embed", "expert_mlp")),
        "up": P((c.n_experts, c.d_model, c.d_ff_expert),
                ("experts", "embed", "expert_mlp")),
        "down": P((c.n_experts, c.d_ff_expert, c.d_model),
                  ("experts", "expert_mlp", "embed")),
    }
    if c.n_shared:
        specs["shared"] = mlp_specs(c.d_model, c.shared_ff, gated=True)
    return specs


def capacity(c: MoEConfig, n_tokens: int) -> int:
    cap = int(math.ceil(n_tokens * c.top_k / c.n_experts * c.capacity_factor))
    return max(8, cap + (-cap) % 8)  # sublane-aligned


def moe_apply(params, x, c: MoEConfig):
    """x: (T, D) flattened tokens -> (y: (T, D), aux_loss: scalar)."""
    t, d = x.shape
    cap = capacity(c, t)
    logits = (x @ params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                       # (T, E)
    gates, idx = jax.lax.top_k(probs, c.top_k)                    # (T, k)
    if c.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e fraction_e * router_prob_e
    one_hot = jax.nn.one_hot(idx[:, 0], c.n_experts, dtype=jnp.float32)
    aux = c.n_experts * jnp.mean(one_hot.mean(0) * probs.mean(0)) * c.n_experts

    flat_e = idx.reshape(-1)                                      # (T*k,)
    sort_idx = jnp.argsort(flat_e)
    sorted_e = flat_e[sort_idx]
    counts = jnp.bincount(flat_e, length=c.n_experts)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * c.top_k) - offsets[sorted_e]
    keep = rank < cap
    dest = jnp.where(keep, sorted_e * cap + rank, c.n_experts * cap)

    tok = sort_idx // c.top_k
    slots = jnp.zeros((c.n_experts * cap, d), x.dtype)
    slots = slots.at[dest].set(x[tok] * keep[:, None].astype(x.dtype), mode="drop")
    h = slots.reshape(c.n_experts, cap, d)
    h = constrain(h, "experts", None, None)
    up = jnp.einsum("ecd,edf->ecf", h, params["up"].astype(h.dtype))
    gate = jnp.einsum("ecd,edf->ecf", h, params["gate"].astype(h.dtype))
    out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up,
                     params["down"].astype(h.dtype))
    out = constrain(out, "experts", None, None)

    padded = jnp.concatenate([out.reshape(-1, d),
                              jnp.zeros((1, d), out.dtype)], axis=0)
    y_sorted = padded[jnp.minimum(dest, c.n_experts * cap)]
    y_flat = jnp.zeros((t * c.top_k, d), x.dtype).at[sort_idx].set(y_sorted)
    y = (y_flat.reshape(t, c.top_k, d)
         * gates[..., None].astype(x.dtype)).sum(axis=1)
    if c.n_shared:
        y = y + mlp_apply(params["shared"], x)
    return y, aux.astype(jnp.float32)


# --------------------------------------------------------------------------
# manual expert parallelism: all-to-all token routing inside shard_map
# --------------------------------------------------------------------------

def moe_apply_a2a(params_loc, x, c: MoEConfig, *, axis_name: str = "model",
                  mean_axes=("model",)):
    """Expert-parallel MoE for shard_map bodies (DESIGN.md §Perf).

    The GSPMD sort-dispatch path sorts the GLOBAL token axis, which the
    partitioner can only realize by replicating tokens (all-gathers of the
    full batch per layer).  Here tokens stay local: each shard routes its
    (token, k) rows to the shard owning the chosen expert with one
    capacity-bounded all_to_all (repro.routing — the paper's key-routed
    sketch dispatch generalized), computes its local experts' GEMMs, and
    returns results with the inverse all_to_all.

    params_loc: expert leaves already sharded to this shard (E_loc, ...);
    x: (T_loc, d) local tokens.  Returns (y (T_loc, d), aux replicated).
    """
    from repro.routing import local_group_by, route, send_back, ungroup

    n_shards = jax.lax.axis_size(axis_name)
    e_loc = c.n_experts // n_shards
    t, d = x.shape
    logits = (x @ params_loc["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, c.top_k)
    if c.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    one_hot = jax.nn.one_hot(idx[:, 0], c.n_experts, dtype=jnp.float32)
    aux = c.n_experts * jnp.mean(one_hot.mean(0) * probs.mean(0)) * c.n_experts
    aux = jax.lax.pmean(aux, mean_axes)

    flat_e = idx.reshape(-1)                               # (T*k,)
    x_rep = jnp.repeat(x, c.top_k, axis=0)                 # (T*k, d)
    dest = (flat_e // e_loc).astype(jnp.int32)
    cap_wire = max(8, int(t * c.top_k / n_shards * c.wire_capacity_factor))
    recv, routing = route({"x": x_rep, "e": flat_e}, dest, axis_name, cap_wire)

    rows = recv["x"]                                       # (R, d), zeros if invalid
    group = (recv["e"] % e_loc).astype(jnp.int32)          # local expert id
    r_total = rows.shape[0]
    cap_loc = max(8, int(r_total / e_loc * c.capacity_factor))
    grouped, slot2, _ = local_group_by({"x": rows}, group, e_loc, cap_loc)
    h = grouped["x"]                                       # (E_loc, C, d)
    up = jnp.einsum("ecd,edf->ecf", h, params_loc["up"].astype(h.dtype))
    gate = jnp.einsum("ecd,edf->ecf", h, params_loc["gate"].astype(h.dtype))
    out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up,
                     params_loc["down"].astype(h.dtype))
    rows_out = ungroup(out, slot2, e_loc, cap_loc)         # (R, d)
    y_flat = send_back(rows_out, routing, axis_name)       # (T*k, d)
    y = (y_flat.reshape(t, c.top_k, d)
         * gates[..., None].astype(x.dtype)).sum(axis=1)
    if c.n_shared:
        y = y + mlp_apply(params_loc["shared"], x)
    return y, aux.astype(jnp.float32)
