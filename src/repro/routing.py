"""Generic capacity-bounded all-to-all routing (shard_map building block).

One abstraction, three users:
  * the paper's key-routed distributed sketch (core/sharded.py pattern),
  * all-to-all expert parallelism for MoE FFNs (models/moe.py a2a impl),
  * row-sharded embedding-table lookup (models/recsys.py a2a impl).

`route` packs arbitrary pytree payloads into fixed (n_shards, capacity, ...)
buffers keyed by a destination-shard id per row, exchanges them with
lax.all_to_all, and returns enough routing state to send per-row results
back to their origin (`send_back`).  Everything is statically shaped and
differentiable w.r.t. payloads (index plumbing is integer-valued), so the
same machinery runs in training steps.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class Routing:
    """Routing state: how local rows were packed into the send buffer."""
    slot_of_row: jnp.ndarray   # (N,) flat slot in the send buffer, or n*cap
    kept: jnp.ndarray          # (N,) bool — False if dropped by capacity
    recv_valid: jnp.ndarray    # (n_shards * capacity,) bool at the receiver
    n_shards: int
    capacity: int


def _pack(payload, dest: jnp.ndarray, n_shards: int, capacity: int):
    n = dest.shape[0]
    order = jnp.argsort(dest)
    sorted_dest = dest[order]
    counts = jnp.bincount(dest, length=n_shards)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(n) - offsets[sorted_dest]
    keep = rank < capacity
    slot = jnp.where(keep, sorted_dest * capacity + rank, n_shards * capacity)

    def pack_leaf(x):
        buf = jnp.zeros((n_shards * capacity,) + x.shape[1:], x.dtype)
        return buf.at[slot].set(x[order], mode="drop") \
                  .reshape((n_shards, capacity) + x.shape[1:])

    packed = jax.tree_util.tree_map(pack_leaf, payload)
    valid = jnp.zeros((n_shards * capacity,), bool).at[slot].set(keep, mode="drop")
    slot_of_row = jnp.full((n,), n_shards * capacity, jnp.int32) \
                     .at[order].set(jnp.where(keep, slot, n_shards * capacity))
    kept = jnp.zeros((n,), bool).at[order].set(keep)
    return packed, valid, slot_of_row, kept


def route(payload: Any, dest: jnp.ndarray, axis_name: str, capacity: int):
    """Send payload rows to `dest` shards over `axis_name` (inside shard_map).

    Returns (recv_payload, routing).  recv leaves have shape
    (n_shards * capacity, ...): row blocks [j*cap:(j+1)*cap] came from shard
    j; invalid rows are zero-filled (mask with routing.recv_valid).
    """
    n_shards = jax.lax.axis_size(axis_name)
    packed, valid, slot_of_row, kept = _pack(payload, dest, n_shards, capacity)

    def xchg(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0) \
                  .reshape((n_shards * capacity,) + x.shape[2:])

    recv = jax.tree_util.tree_map(xchg, packed)
    recv_valid = xchg(valid.reshape(n_shards, capacity))
    return recv, Routing(slot_of_row=slot_of_row, kept=kept,
                         recv_valid=recv_valid, n_shards=n_shards,
                         capacity=capacity)


def send_back(results: Any, routing: Routing, axis_name: str):
    """Inverse exchange: receiver-aligned results -> origin rows.

    results leaves: (n_shards * capacity, ...) aligned with recv layout.
    Returns leaves of shape (N, ...) aligned with the original rows; rows
    dropped by capacity come back as zeros (mask with routing.kept).
    """
    cap, n_shards = routing.capacity, routing.n_shards

    def xchg(x):
        return jax.lax.all_to_all(x.reshape((n_shards, cap) + x.shape[1:]),
                                  axis_name, split_axis=0, concat_axis=0) \
                  .reshape((n_shards * cap,) + x.shape[1:])

    returned = jax.tree_util.tree_map(xchg, results)

    def unpack(x):
        padded = jnp.concatenate(
            [x, jnp.zeros((1,) + x.shape[1:], x.dtype)], axis=0)
        return padded[jnp.minimum(routing.slot_of_row, n_shards * cap)]

    return jax.tree_util.tree_map(unpack, returned)


def local_group_by(values: Any, group: jnp.ndarray, n_groups: int,
                   capacity: int):
    """Shard-local grouped layout: rows -> (n_groups, capacity, ...) slots.

    Same packing as `route` but without the exchange — used to arrange
    received MoE rows per local expert for the batched GEMM.
    Returns (grouped, slot_of_row, kept).
    """
    packed, _, slot_of_row, kept = _pack(values, group, n_groups, capacity)
    return packed, slot_of_row, kept


def ungroup(grouped: Any, slot_of_row: jnp.ndarray, n_groups: int,
            capacity: int):
    """Inverse of local_group_by for result rows."""
    def unpack(x):
        flat = x.reshape((n_groups * capacity,) + x.shape[2:])
        padded = jnp.concatenate(
            [flat, jnp.zeros((1,) + flat.shape[1:], flat.dtype)], axis=0)
        return padded[jnp.minimum(slot_of_row, n_groups * capacity)]
    return jax.tree_util.tree_map(unpack, grouped)
