"""DimeNet (directional message passing) — arXiv:2003.03123.

Faithful structure: Bessel radial basis, spherical basis j_l(z_ln r/c) *
P_l(cos angle) over edge triplets (k->j, j->i), low-rank (n_bilinear)
bilinear interaction, 6 interaction blocks, per-block output heads.

TPU/JAX adaptations (documented in DESIGN.md §2.2):
  * message passing = gather over edge/triplet index lists + segment_sum —
    JAX's sparse support is BCOO-only, so the scatter IS the implementation;
  * triplets are a *sampled, fixed-shape* list (n_edges * max_angular) —
    enumerating sum(deg^2) triplets is infeasible on ogb-scale graphs;
  * spherical Bessel roots are found by bisection on the closed-form j_l at
    import time (no scipy in the image);
  * non-molecular graphs (cora/reddit/ogb shapes) carry synthetic 3D
    positions; node features enter through the embedding block.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from repro.models.layers import dense
from repro.models.params import P
from repro.sharding import constrain


@dataclasses.dataclass(frozen=True)
class DimeNetConfig:
    n_blocks: int = 6
    d_hidden: int = 128
    n_bilinear: int = 8
    n_spherical: int = 7
    n_radial: int = 6
    cutoff: float = 5.0
    envelope_p: int = 6
    d_feat: Optional[int] = None   # feature graphs: input feature dim
    n_atom_types: int = 95         # molecules: atomic-number embedding
    n_targets: int = 1             # regression targets / classes
    readout: str = "graph"         # "graph" (molecules) | "node"
    # distributed mode: edges+triplets are PARTITIONED (triplet lists local
    # to the shard owning their target edge — a data-pipeline contract), so
    # the edge<->edge aggregation needs NO collectives; only the final
    # node_out reduction crosses shards (§Perf, dimenet/ogb_products)
    local_triplets: bool = False


# --------------------------------------------------------------------------
# bases
# --------------------------------------------------------------------------

def _j_l_np(l: int, x: np.ndarray) -> np.ndarray:
    """Closed-form spherical Bessel j_l via upward recurrence (numpy)."""
    x = np.asarray(x, dtype=np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        j0 = np.where(x == 0, 1.0, np.sin(x) / x)
        if l == 0:
            return j0
        j1 = np.where(x == 0, 0.0, np.sin(x) / x**2 - np.cos(x) / x)
        jm, jc = j0, j1
        for n in range(1, l):
            jm, jc = jc, (2 * n + 1) / x * jc - jm
        return jc


@functools.lru_cache(maxsize=None)
def bessel_roots(n_spherical: int, n_radial: int) -> tuple:
    """First n_radial positive roots of j_l for l = 0..n_spherical-1."""
    out = []
    for l in range(n_spherical):
        xs = np.linspace(1e-3, (n_radial + l + 4) * np.pi, 20_000)
        ys = _j_l_np(l, xs)
        sign = np.sign(ys)
        idx = np.nonzero(sign[1:] * sign[:-1] < 0)[0][:n_radial]
        roots = []
        for i in idx:
            lo, hi = xs[i], xs[i + 1]
            for _ in range(60):
                mid = 0.5 * (lo + hi)
                if _j_l_np(l, np.array([lo]))[0] * _j_l_np(l, np.array([mid]))[0] <= 0:
                    hi = mid
                else:
                    lo = mid
            roots.append(0.5 * (lo + hi))
        out.append(tuple(roots))
    return tuple(out)


def _envelope(r, cutoff: float, p: int):
    """DimeNet smooth cutoff envelope u(d) (polynomial, C^2 at the cutoff)."""
    d = r / cutoff
    a = -(p + 1) * (p + 2) / 2.0
    b = p * (p + 2.0)
    c = -p * (p + 1) / 2.0
    d = jnp.maximum(d, 1e-6)
    env = 1.0 / d + a * d ** (p - 1) + b * d**p + c * d ** (p + 1)
    return jnp.where(d < 1.0, env, 0.0)


def radial_basis(r, cfg: DimeNetConfig):
    """(E,) distances -> (E, n_radial) Bessel RBF with envelope."""
    n = jnp.arange(1, cfg.n_radial + 1, dtype=jnp.float32)
    env = _envelope(r, cfg.cutoff, cfg.envelope_p)
    return (env[:, None] * jnp.sqrt(2.0 / cfg.cutoff)
            * jnp.sin(n[None, :] * jnp.pi * r[:, None] / cfg.cutoff))


def _j_l_jnp(l: int, x):
    """Spherical Bessel j_l, float32-stable.

    The upward recurrence cancels catastrophically for x << l in float32
    (sin(x)/x^2 - cos(x)/x is a difference of ~1/x terms), so small
    arguments use the ascending series j_l(x) ~ x^l/(2l+1)!! (1 - ...).
    """
    x = jnp.maximum(x, 1e-6)
    safe = jnp.maximum(x, 1.0)  # recurrence evaluated away from the bad zone
    j0 = jnp.sin(safe) / safe
    if l == 0:
        return jnp.where(x < 1.0, jnp.sin(x) / x, j0)
    jm, jc = j0, jnp.sin(safe) / safe**2 - jnp.cos(safe) / safe
    for n in range(1, l):
        jm, jc = jc, (2 * n + 1) / safe * jc - jm
    dfact = 1.0
    for k in range(1, 2 * l + 2, 2):
        dfact *= k
    series = (x**l / dfact) * (1.0 - x**2 / (2.0 * (2 * l + 3))
                               + x**4 / (8.0 * (2 * l + 3) * (2 * l + 5)))
    return jnp.where(x < 1.0, series, jc)


def _legendre(l: int, c):
    if l == 0:
        return jnp.ones_like(c)
    pm, pc = jnp.ones_like(c), c
    for n in range(1, l):
        pm, pc = pc, ((2 * n + 1) * c * pc - n * pm) / (n + 1)
    return pc


def spherical_basis(r_kj, angle_cos, cfg: DimeNetConfig):
    """(T,) dist & cos(angle) -> (T, n_spherical * n_radial) SBF."""
    roots = bessel_roots(cfg.n_spherical, cfg.n_radial)
    env = _envelope(r_kj, cfg.cutoff, cfg.envelope_p)
    feats = []
    for l in range(cfg.n_spherical):
        ang = _legendre(l, angle_cos)
        for z in roots[l]:
            feats.append(env * _j_l_jnp(l, jnp.float32(z) * r_kj / cfg.cutoff) * ang)
    return jnp.stack(feats, axis=-1)


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------

def param_specs(cfg: DimeNetConfig) -> dict:
    d, nb = cfg.d_hidden, cfg.n_bilinear
    n_sbf = cfg.n_spherical * cfg.n_radial
    block = {
        "w_rbf": P((cfg.n_radial, d), (None, "mlp")),
        "w_sbf": P((n_sbf, nb), (None, None)),
        "w_down": P((d, nb), ("mlp", None)),
        "w_up": P((nb, d), (None, "mlp")),
        "w_msg1": P((d, d), ("mlp", "mlp")),
        "w_msg2": P((d, d), ("mlp", "mlp")),
        "out_rbf": P((cfg.n_radial, d), (None, "mlp")),
        "out_w1": P((d, d), ("mlp", "mlp")),
        "out_w2": P((d, cfg.n_targets), ("mlp", None), "zeros"),
    }
    specs = {
        "emb_rbf": P((cfg.n_radial, d), (None, "mlp")),
        "emb_edge": P((3 * d, d), ("mlp", "mlp")),
        "blocks": jax.tree_util.tree_map(
            lambda p: P((cfg.n_blocks,) + p.shape, ("layers",) + p.axes,
                        p.init, p.dtype),
            block, is_leaf=lambda x: isinstance(x, P)),
    }
    if cfg.d_feat is not None:
        specs["emb_node"] = P((cfg.d_feat, d), (None, "mlp"))
    else:
        specs["emb_atom"] = P((cfg.n_atom_types, d), (None, "mlp"), "embed")
    return specs


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def apply(params, inputs, cfg: DimeNetConfig, psum_axes=None):
    """inputs: pos (N,3), node features (x_feat (N,F) or atom_z (N,)),
    edge_src/edge_dst (E,), t_kj/t_ji (T,) triplet edge indices, t_mask (T,),
    optional graph_id (N,) + n_graphs for graph readout.
    Returns per-node (N, n_targets) or per-graph (G, n_targets) outputs.

    With `psum_axes` (inside shard_map): edge/triplet arrays are this
    shard's partition (triplets indexing local edges); node-level inputs are
    replicated; the single cross-shard reduction is the node_out psum.
    """
    pos = inputs["pos"]
    src, dst = inputs["edge_src"], inputs["edge_dst"]
    n_nodes = pos.shape[0]

    if cfg.d_feat is not None:
        h = dense(inputs["x_feat"], params["emb_node"])
    else:
        h = jnp.take(params["emb_atom"], inputs["atom_z"], axis=0)
    h = jax.nn.silu(h)

    # edge geometry
    vec = pos[dst] - pos[src]                           # (E, 3)
    r = jnp.sqrt(jnp.maximum((vec**2).sum(-1), 1e-12))  # (E,)
    rbf = radial_basis(r, cfg)                          # (E, n_radial)

    # triplet geometry: angle between edge kj and ji at shared node j
    kj, ji, t_mask = inputs["t_kj"], inputs["t_ji"], inputs["t_mask"]
    v1 = -vec[kj]                                       # j -> k
    v2 = vec[ji]                                        # j -> i
    cos_a = (v1 * v2).sum(-1) / jnp.maximum(
        jnp.sqrt((v1**2).sum(-1) * (v2**2).sum(-1)), 1e-9)
    sbf = spherical_basis(r[kj], jnp.clip(cos_a, -1.0, 1.0), cfg)  # (T, n_sbf)
    sbf = sbf * t_mask[:, None]

    # edge embedding m_ji = MLP([h_j, h_i, rbf]); padded edges masked out
    # (edge lists are padded to shard-divisible lengths, DESIGN.md §4)
    e_mask = inputs.get("edge_mask")
    m = jax.nn.silu(dense(
        jnp.concatenate([h[src], h[dst], rbf @ params["emb_rbf"]], axis=-1),
        params["emb_edge"]))                            # (E, d)
    if e_mask is not None:
        m = m * e_mask[:, None]
    m = constrain(m, "edges", None)

    node_out = jnp.zeros((n_nodes, cfg.n_targets), jnp.float32)

    def block_fwd(carry, bp):
        m, node_out = carry
        # directional interaction: gather messages of edges (k->j), gate by
        # rbf, low-rank bilinear with the angular basis, scatter to (j->i)
        gate = rbf @ bp["w_rbf"]                        # (E, d)
        x_kj = (m * gate)[kj]                           # (T, d)
        p_t = x_kj @ bp["w_down"]                       # (T, nb)
        q_t = sbf @ bp["w_sbf"]                         # (T, nb)
        t_msg = (p_t * q_t) @ bp["w_up"]                # (T, d)
        agg = jax.ops.segment_sum(t_msg, ji, num_segments=m.shape[0])
        m_new = jax.nn.silu(m @ bp["w_msg1"] + agg @ bp["w_msg2"]) + m
        if e_mask is not None:
            m_new = m_new * e_mask[:, None]
        m_new = constrain(m_new, "edges", None)
        # output block: edges -> nodes
        contrib = jax.ops.segment_sum(m_new * (rbf @ bp["out_rbf"]), dst,
                                      num_segments=n_nodes)
        node_out = node_out + dense(jax.nn.silu(contrib @ bp["out_w1"]),
                                    bp["out_w2"]).astype(node_out.dtype)
        return (m_new, node_out), None

    # checkpoint: each block's node-level intermediates (contrib/silu are
    # O(n_nodes * d) fp32) are recomputed in backward instead of stacked
    # across the 6-block scan
    (m, node_out), _ = jax.lax.scan(jax.checkpoint(block_fwd), (m, node_out),
                                    params["blocks"])

    if psum_axes is not None:
        # one reduction for all 6 blocks (sum of block contribs commutes
        # with psum); everything edge<->edge stayed shard-local
        node_out = jax.lax.psum(node_out, psum_axes)
    if cfg.readout == "graph":
        return jax.ops.segment_sum(node_out, inputs["graph_id"],
                                   num_segments=inputs["n_graphs"])
    return node_out


def loss_fn_sharded(params, batch, cfg: DimeNetConfig, rules, mesh):
    """shard_map-wrapped loss for the local-triplets distributed mode.

    Edge/triplet inputs are partitioned over every mesh axis; node-level
    inputs and all params are replicated.  The loss is computed from the
    psum'd node_out, so it is replicated — out_specs P().
    """
    from jax.sharding import PartitionSpec as PS
    from repro.sharding import spec_for

    edge_keys = ("edge_src", "edge_dst", "edge_mask", "t_kj", "t_ji", "t_mask")
    b_specs = {k: (spec_for(("edges",), {"edges": mesh.axis_names}, mesh)
                   if k in edge_keys else PS())
               for k in batch}
    p_specs = jax.tree_util.tree_map(lambda _: PS(), params)

    def body(p, b):
        loss, metrics = loss_fn(p, b, cfg, psum_axes=mesh.axis_names)
        return loss

    loss = shard_map(body, mesh=mesh, in_specs=(p_specs, b_specs),
                     out_specs=PS(), check_vma=False)(params, batch)
    return loss, {}


def loss_fn(params, batch, cfg: DimeNetConfig, psum_axes=None):
    out = apply(params, batch, cfg, psum_axes=psum_axes)
    if cfg.readout == "graph":
        err = out[:, 0] - batch["target"]
        loss = jnp.mean(err**2)
        return loss, {"mse": loss}
    # node classification
    logits = out
    labels = batch["label"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = batch.get("label_mask", jnp.ones_like(labels, jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss, {"nll": loss}
