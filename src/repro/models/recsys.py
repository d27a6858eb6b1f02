"""RecSys model family: DLRM, SASRec, BERT4Rec, two-tower retrieval.

The counting plane (the paper's CMLS sketch) enters here in three places
(DESIGN.md §2.1):
  * `admission` — ids pass through a sketch-gated admission map before the
    embedding lookup (core/admission.py);
  * two-tower in-batch softmax applies logQ correction with sampling
    probabilities *estimated from the sketch* (`item_logq` input);
  * the event stream uses sketch estimates for frequency-capped negatives.

Embedding tables are the scale citizens: rows are sharded over the "model"
mesh axis (RECSYS_RULES.table_rows) and looked up with jnp.take +
segment-reduce (JAX has no native EmbeddingBag — layers.embedding_bag IS
the implementation).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from jax import shard_map
from repro.models import attention as attn
from repro.models.layers import (dense, embedding_bag, layer_norm)
from repro.models.params import P
from repro.sharding import constrain

# Criteo-1TB per-field cardinalities (MLPerf DLRM reference, day_fea_count),
# capped at max_ind_range = 40M per the MLPerf benchmark convention.
CRITEO_TABLE_SIZES = [
    227_605_432, 39_060, 17_295, 7_424, 20_265, 3, 7_122, 1_543, 63,
    130_229_467, 3_067_956, 405_282, 10, 2_209, 11_938, 155, 4, 976, 14,
    292_775_614, 40_790_948, 187_188_510, 590_152, 12_973, 108, 36,
]
MAX_IND_RANGE = 40_000_000


def criteo_tables(cap: int = MAX_IND_RANGE) -> list[int]:
    return [min(v, cap) for v in CRITEO_TABLE_SIZES]


# tables at/above this row count shard over the model axis; rows are padded
# to a 512 multiple so both production meshes divide evenly (pad rows are
# unreachable: lookups are bounded by the true cardinality)
SHARD_ROWS_MIN = 16_384


def round_rows(n: int, mult: int = 512) -> int:
    return n + (-n) % mult


def table_spec(rows: int, dim: int, init="normal:0.01") -> P:
    if rows >= SHARD_ROWS_MIN:
        return P((round_rows(rows), dim), ("table_rows", None), init)
    return P((rows, dim), (None, None), init)


def _mlp_stack_specs(dims: tuple, prefix_axes=(None, "mlp")) -> dict:
    specs = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        specs[f"w{i}"] = P((a, b), prefix_axes)
        specs[f"b{i}"] = P((b,), (None,), "zeros")  # biases replicate
    return specs


def _mlp_stack(params, x, n: int, final_act: bool = False):
    for i in range(n):
        x = dense(x, params[f"w{i}"], params[f"b{i}"])
        if i < n - 1 or final_act:
            x = jax.nn.relu(x)
    return x


# --------------------------------------------------------------------------
# DLRM (arXiv:1906.00091, MLPerf config)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    n_dense: int = 13
    embed_dim: int = 128
    bot_mlp: tuple = (13, 512, 256, 128)
    top_mlp: tuple = (1024, 1024, 512, 256, 1)
    table_sizes: tuple = tuple(criteo_tables())
    # §Perf knobs (dlrm-mlperf/train_batch hillclimb):
    sparse_update: bool = False   # manual row-wise updates, no dense grads
    lookup: str = "gspmd"         # "gspmd" | "a2a" (routed shard_map lookup)

    @property
    def n_sparse(self) -> int:
        return len(self.table_sizes)

    @property
    def interact_dim(self) -> int:
        n = self.n_sparse + 1
        return n * (n - 1) // 2 + self.embed_dim


def dlrm_specs(c: DLRMConfig) -> dict:
    return {
        "tables": {f"t{i}": table_spec(v, c.embed_dim)
                   for i, v in enumerate(c.table_sizes)},
        "bot": _mlp_stack_specs(c.bot_mlp),
        "top": _mlp_stack_specs((c.interact_dim,) + c.top_mlp),
    }


def dlrm_lookup(tables, sparse, c: DLRMConfig) -> jnp.ndarray:
    """(B, n_sparse) ids -> (B, n_sparse, D) embeddings (take per field)."""
    return jnp.stack([jnp.take(tables[f"t{i}"], sparse[:, i], axis=0)
                      for i in range(c.n_sparse)], axis=1)


def dlrm_lookup_a2a(tables, sparse, c: DLRMConfig, rules, mesh) -> jnp.ndarray:
    """Routed lookup: ids travel to the owner shard, rows travel back.

    Tables use interleaved row placement (global row r -> shard r % S,
    slot r // S — a data-plane contract) so the Zipf head round-robins
    across shards instead of flooding shard 0.  One capacity-bounded
    all_to_all pair per sharded field replaces GSPMD's masked-psum gather
    (§Perf, dlrm-mlperf/train_batch).
    """
    from jax.sharding import PartitionSpec as PS
    from repro.routing import route, send_back
    from repro.sharding import spec_for

    ids_spec = spec_for(("batch", None), rules, mesh, sparse.shape)
    out_spec = spec_for(("batch", None, None), rules, mesh,
                        (sparse.shape[0], c.n_sparse, c.embed_dim))
    t_specs = {}
    sharded_field = {}
    for i in range(c.n_sparse):
        rows = tables[f"t{i}"].shape[0]
        sharded_field[i] = rows >= SHARD_ROWS_MIN and rows % 512 == 0
        t_specs[f"t{i}"] = PS("model", None) if sharded_field[i] else PS(None, None)

    n_model = mesh.shape["model"]

    def body(tbls_loc, ids_loc):
        b_loc = ids_loc.shape[0]
        cap = max(8, int(b_loc / n_model * 2.0))
        outs = []
        for i in range(c.n_sparse):
            ids_i = ids_loc[:, i]
            if not sharded_field[i]:
                outs.append(jnp.take(tbls_loc[f"t{i}"], ids_i, axis=0))
                continue
            dest = (ids_i % n_model).astype(jnp.int32)   # interleaved placement
            slot = ids_i // n_model
            recv, routing = route({"idx": slot}, dest, "model", cap)
            rows = jnp.take(tbls_loc[f"t{i}"], recv["idx"], axis=0)
            rows = rows * routing.recv_valid[:, None].astype(rows.dtype)
            outs.append(send_back(rows, routing, "model"))
        return jnp.stack(outs, axis=1)

    return shard_map(body, mesh=mesh, in_specs=(t_specs, ids_spec),
                     out_specs=out_spec, check_vma=False)(tables, sparse)


def dlrm_apply_from_emb(params, dense, embs, c: DLRMConfig):
    """Interaction + MLPs given pre-looked-up embeddings (B, n_sparse, D)."""
    x = _mlp_stack(params["bot"], dense, len(c.bot_mlp) - 1,
                   final_act=True)                       # (B, 128)
    x = constrain(x, "batch", None)
    z = jnp.concatenate([x[:, None, :], embs], axis=1)   # (B, 27, D)
    inter = jnp.einsum("bnd,bmd->bnm", z, z)             # pairwise dots
    iu, ju = jnp.triu_indices(z.shape[1], k=1)
    feats = jnp.concatenate([x, inter[:, iu, ju]], axis=-1)
    logit = _mlp_stack(params["top"], feats, len(c.top_mlp))
    return logit[:, 0]


def dlrm_apply(params, batch, c: DLRMConfig):
    """batch: dense (B, 13), sparse (B, 26) int32 -> logits (B,)."""
    embs = dlrm_lookup(params["tables"], batch["sparse"], c)
    return dlrm_apply_from_emb(params, batch["dense"], embs, c)


def dlrm_score_candidates(params, batch, cand_ids, c: DLRMConfig,
                          cand_field: int = 0):
    """Score ONE context row against C candidate values of `cand_field`.

    DLRM is a ranking model; the retrieval_cand shape asks it to bulk-score
    10^6 candidates for one context.  Everything except the candidate
    field's embedding is computed once and broadcast; interaction + top MLP
    run per candidate (sharded over the "candidates" axis).
    """
    x = _mlp_stack(params["bot"], batch["dense"], len(c.bot_mlp) - 1,
                   final_act=True)[0]                     # (128,)
    fixed = [jnp.take(params["tables"][f"t{i}"], batch["sparse"][0, i], axis=0)
             for i in range(c.n_sparse) if i != cand_field]
    cand = jnp.take(params["tables"][f"t{cand_field}"],
                    cand_ids % c.table_sizes[cand_field], axis=0)  # (C, D)
    cand = constrain(cand, "candidates", None)
    zf = jnp.stack([x] + fixed, axis=0)                  # (26, D)
    inter_ff = jnp.einsum("nd,md->nm", zf, zf)           # fixed x fixed
    inter_fc = jnp.einsum("nd,cd->cn", zf, cand)         # fixed x cand
    iu, ju = jnp.triu_indices(zf.shape[0], k=1)
    base = jnp.concatenate([x, inter_ff[iu, ju]])        # shared features
    feats = jnp.concatenate(
        [jnp.broadcast_to(base, (cand.shape[0], base.shape[0])), inter_fc],
        axis=-1)                                          # (C, interact_dim)
    logit = _mlp_stack(params["top"], feats, len(c.top_mlp))
    return logit[:, 0]


def _bce(logit, y):
    return jnp.mean(jnp.maximum(logit, 0) - logit * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def dlrm_loss(params, batch, c: DLRMConfig):
    loss = _bce(dlrm_apply(params, batch, c), batch["label"])
    return loss, {"bce": loss}


def dlrm_sparse_update_sharded(tables, accs, sparse_ids, g_emb, c: DLRMConfig,
                               opt_cfg, rules, mesh):
    """Row-wise Adagrad applied shard-locally (interleaved row placement).

    XLA's scatter into a model-sharded table moves the full update set
    through a masked-psum pattern.  Manually: all_gather the (ids, grad)
    updates over the batch axes once (the irreducible DP volume), then each
    model shard applies exactly its own rows — no further collectives.
    """
    from jax.sharding import PartitionSpec as PS
    from repro.sharding import spec_for

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_model = mesh.shape["model"]
    ids_spec = spec_for(("batch", None), rules, mesh, sparse_ids.shape)
    g_spec = spec_for(("batch", None, None), rules, mesh, g_emb.shape)
    t_specs, a_specs, sharded_field = {}, {}, {}
    for i in range(c.n_sparse):
        rows = tables[f"t{i}"].shape[0]
        sharded_field[i] = rows >= SHARD_ROWS_MIN and rows % 512 == 0
        t_specs[f"t{i}"] = PS("model", None) if sharded_field[i] else PS(None, None)
        a_specs[f"t{i}"] = {"acc": PS("model") if sharded_field[i] else PS(None)}

    def body(t_loc, a_loc, ids_loc, g_loc):
        ids_g = jax.lax.all_gather(ids_loc, batch_axes, tiled=True)
        # bf16 on the wire + in the gathered buffer: embedding grads tolerate
        # it (production TBE ships fp16 grads); math upcasts to f32 below
        g_g = jax.lax.all_gather(g_loc.astype(jnp.bfloat16), batch_axes,
                                 tiled=True).astype(jnp.float32)
        col = jax.lax.axis_index("model")
        new_t, new_a = {}, {}
        for i in range(c.n_sparse):
            key = f"t{i}"
            t, acc = t_loc[key], a_loc[key]["acc"]
            ids_i, g_i = ids_g[:, i], g_g[:, i]
            ms = jnp.mean(jnp.square(g_i), axis=-1)
            if sharded_field[i]:
                mine = (ids_i % n_model) == col
                slot = jnp.where(mine, ids_i // n_model, t.shape[0])
            else:
                mine = jnp.ones_like(ids_i, bool)
                slot = ids_i
            acc = acc.at[slot].add(jnp.where(mine, ms, 0.0), mode="drop")
            got = acc[jnp.minimum(slot, t.shape[0] - 1)]
            scale = (opt_cfg.table_lr
                     / jnp.sqrt(jnp.maximum(got + opt_cfg.table_eps, 1e-30))
                     * mine.astype(jnp.float32))
            new_t[key] = t.at[slot].add(-(scale[:, None] * g_i).astype(t.dtype),
                                        mode="drop")
            new_a[key] = {"acc": acc}
        return new_t, new_a

    return shard_map(body, mesh=mesh,
                     in_specs=(t_specs, a_specs, ids_spec, g_spec),
                     out_specs=(t_specs, a_specs),
                     check_vma=False)(tables, accs, sparse_ids, g_emb)


def dlrm_train_step_sparse(params, opt_state, batch, opt_step, seed,
                           c: DLRMConfig, opt_cfg, dense_update,
                           rules_mesh=None):
    """Sparse-table train step: embedding grads never densify.

    Autodiff of `take` materializes a (rows, D) zeros+scatter gradient per
    table — 104 GB for the Criteo set.  Here tables are looked up under
    stop_gradient; the loss is differentiated w.r.t. the GATHERED rows
    (B, 26, D), and row-wise Adagrad applies scatter updates to exactly the
    touched rows (the production TBE pattern).  Memory traffic scales with
    B*26*D instead of sum(rows)*D (§Perf, dlrm-mlperf/train_batch).
    """
    tables = params["tables"]
    dense_p = {"bot": params["bot"], "top": params["top"]}
    if c.lookup == "a2a" and rules_mesh is not None:
        embs = dlrm_lookup_a2a(tables, batch["sparse"], c, *rules_mesh)
    else:
        embs = dlrm_lookup(tables, batch["sparse"], c)
    embs = jax.lax.stop_gradient(embs)

    def loss_of(dp, e):
        return _bce(dlrm_apply_from_emb(dp, batch["dense"], e, c),
                    batch["label"])

    loss, (g_dense, g_emb) = jax.value_and_grad(loss_of, argnums=(0, 1))(
        dense_p, embs)
    new_dense, new_dense_state, stats = dense_update(
        g_dense, opt_state["dense"], dense_p, opt_step)

    if c.lookup == "a2a" and rules_mesh is not None:
        new_tables, new_acc = dlrm_sparse_update_sharded(
            tables, opt_state["tables"], batch["sparse"], g_emb, c, opt_cfg,
            *rules_mesh)
        return ({"tables": new_tables, **new_dense},
                {"dense": new_dense_state, "tables": new_acc},
                {"loss": loss, **stats})

    new_tables, new_acc = {}, {}
    for i in range(c.n_sparse):
        key = f"t{i}"
        t, acc = tables[key], opt_state["tables"][key]["acc"]
        ids = batch["sparse"][:, i]
        g = g_emb[:, i].astype(jnp.float32)              # (B, D)
        row_ms = jnp.mean(jnp.square(g), axis=-1)        # (B,)
        acc = acc.at[ids].add(row_ms)
        scale = opt_cfg.table_lr / jnp.sqrt(
            jnp.maximum(acc[ids] + opt_cfg.table_eps, 1e-30))
        new_tables[key] = t.at[ids].add(-(scale[:, None] * g).astype(t.dtype))
        new_acc[key] = {"acc": acc}
    new_params = {"tables": new_tables, **new_dense}
    new_state = {"dense": new_dense_state, "tables": new_acc}
    return new_params, new_state, {"loss": loss, **stats}


# --------------------------------------------------------------------------
# shared transformer encoder block (SASRec / BERT4Rec)
# --------------------------------------------------------------------------

def _enc_block_specs(d: int, n_heads: int, d_ff: int) -> dict:
    return {
        "attn": attn.gqa_specs(attn.GQAConfig(d_model=d, n_heads=n_heads,
                                              n_kv_heads=n_heads,
                                              d_head=d // n_heads)),
        "ln1_s": P((d,), (None,), "ones"), "ln1_b": P((d,), (None,), "zeros"),
        "ln2_s": P((d,), (None,), "ones"), "ln2_b": P((d,), (None,), "zeros"),
        "ff1": P((d, d_ff), (None, "mlp")), "ff1b": P((d_ff,), ("mlp",), "zeros"),
        "ff2": P((d_ff, d), ("mlp", None)), "ff2b": P((d,), (None,), "zeros"),
    }


def _enc_block(p, x, d: int, n_heads: int, causal: bool):
    cfg = attn.GQAConfig(d_model=d, n_heads=n_heads, n_kv_heads=n_heads,
                         d_head=d // n_heads)
    h = layer_norm(x, p["ln1_s"], p["ln1_b"])
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    a, _ = attn.gqa_apply(p["attn"], h, positions, cfg,
                          kind="global" if causal else "bidir", use_rope=False)
    x = x + a
    h = layer_norm(x, p["ln2_s"], p["ln2_b"])
    f = dense(jax.nn.relu(dense(h, p["ff1"], p["ff1b"])), p["ff2"], p["ff2b"])
    return x + f


# --------------------------------------------------------------------------
# SASRec (arXiv:1808.09781)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SASRecConfig:
    n_items: int = 1_000_000
    embed_dim: int = 50
    n_blocks: int = 2
    n_heads: int = 1
    seq_len: int = 50
    n_neg: int = 128          # sampled-softmax negatives (adaptation for 1M items)
    causal: bool = True
    mask_frac: float = 0.0    # BERT4Rec sets > 0

    @property
    def pad_id(self) -> int:
        return self.n_items       # one extra row: PAD (SASRec) / MASK (BERT4Rec)


def sasrec_specs(c: SASRecConfig) -> dict:
    return {
        "items": table_spec(c.n_items + 1, c.embed_dim),
        "pos": P((c.seq_len, c.embed_dim), (None, None), "normal:0.01"),
        "blocks": {f"b{i}": _enc_block_specs(c.embed_dim, c.n_heads,
                                             c.embed_dim)
                   for i in range(c.n_blocks)},
        "ln_s": P((c.embed_dim,), (None,), "ones"),
        "ln_b": P((c.embed_dim,), (None,), "zeros"),
    }


def sasrec_encode(params, history, c: SASRecConfig):
    """history (B, S) item ids -> (B, S, D) contextual item states."""
    x = jnp.take(params["items"], history, axis=0)
    x = x + params["pos"][None, :, :].astype(x.dtype)
    x = constrain(x, "batch", None, None)
    for i in range(c.n_blocks):
        x = _enc_block(params["blocks"][f"b{i}"], x, c.embed_dim, c.n_heads,
                       causal=c.causal)
    return layer_norm(x, params["ln_s"], params["ln_b"])


def _sampled_softmax(params, h, target, rng, c: SASRecConfig,
                     logq: jnp.ndarray | None = None):
    """h (B, D) vs target (B,) + n_neg uniform negatives -> CE loss."""
    b = h.shape[0]
    negs = jax.random.randint(rng, (c.n_neg,), 0, c.n_items)
    cand = jnp.concatenate([target, negs])               # (B + n_neg,)
    e = jnp.take(params["items"], cand, axis=0)          # (B+n, D)
    logits = (h @ e.T).astype(jnp.float32)               # (B, B+n)
    if logq is not None:
        logits = logits - logq[None, :]
    labels = jnp.arange(b)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def sasrec_loss(params, batch, c: SASRecConfig, rng):
    h = sasrec_encode(params, batch["history"], c)[:, -1]  # next-item state
    loss = _sampled_softmax(params, h, batch["target"], rng, c)
    return loss, {"ce": loss}


def bert4rec_loss(params, batch, c: SASRecConfig, rng):
    """Masked-item modeling: mask ~mask_frac of positions, predict originals."""
    hist = batch["history"]
    b, s = hist.shape
    r_mask, r_neg = jax.random.split(rng)
    m = jax.random.uniform(r_mask, (b, s)) < c.mask_frac
    m = m.at[:, -1].set(True)  # always learn the last position
    masked = jnp.where(m, c.pad_id, hist)
    hseq = sasrec_encode(params, masked, c)              # bidirectional
    # loss on the final masked position (fixed-shape; other masks act as noise)
    loss = _sampled_softmax(params, hseq[:, -1], hist[:, -1], r_neg, c)
    return loss, {"ce": loss}


def score_candidates(params, h, cand_ids):
    """h (B, D) x candidate ids (C,) -> (B, C) scores (retrieval_cand cell)."""
    e = jnp.take(params["items"], cand_ids, axis=0)
    e = constrain(e, "candidates", None)
    return (h @ e.T).astype(jnp.float32)


def topk_over_catalog(params, h, c: SASRecConfig, k: int = 100,
                      chunk: int = 65_536):
    """Top-k items for each user state without materializing (B, n_items).

    lax.map over candidate chunks keeps peak memory at B*chunk scores;
    chunk winners are re-ranked at the end (exact top-k).
    """
    n_chunks = -(-c.n_items // chunk)

    def one(i):
        ids = jnp.minimum(i * chunk + jnp.arange(chunk), c.n_items - 1)
        s = score_candidates(params, h, ids)             # (B, chunk)
        v, j = jax.lax.top_k(s, k)
        return v, ids[j]

    vals, idx = jax.lax.map(one, jnp.arange(n_chunks))   # (n_chunks, B, k)
    vals = jnp.moveaxis(vals, 0, 1).reshape(h.shape[0], -1)
    idx = jnp.moveaxis(idx, 0, 1).reshape(h.shape[0], -1)
    v, j = jax.lax.top_k(vals, k)
    return v, jnp.take_along_axis(idx, j, axis=1)


# --------------------------------------------------------------------------
# Two-tower retrieval (Yi et al., RecSys'19) with sketch logQ correction
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TwoTowerConfig:
    n_users: int = 5_000_000
    n_items: int = 1_000_000
    embed_dim: int = 256
    tower: tuple = (1024, 512, 256)
    n_user_feats: int = 8
    n_item_feats: int = 8
    temperature: float = 0.05


def twotower_specs(c: TwoTowerConfig) -> dict:
    dims = (c.embed_dim,) + c.tower
    return {
        "user_table": table_spec(c.n_users, c.embed_dim),
        "item_table": table_spec(c.n_items, c.embed_dim),
        "user_tower": _mlp_stack_specs(dims),
        "item_tower": _mlp_stack_specs(dims),
    }


def _tower(params, table, feats, tower_dims):
    x = embedding_bag(table, feats, mode="mean")
    x = _mlp_stack(params, x, len(tower_dims))
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)


def twotower_embed(params, batch, c: TwoTowerConfig):
    u = _tower(params["user_tower"], params["user_table"], batch["user_feats"], c.tower)
    v = _tower(params["item_tower"], params["item_table"], batch["item_feats"], c.tower)
    return u, v


def twotower_loss(params, batch, c: TwoTowerConfig):
    """In-batch softmax with logQ correction.

    batch["item_logq"]: log sampling probability of each in-batch item,
    estimated from the CMLS sketch (count / total) by the data pipeline —
    the paper's estimator in the exact role exact counters can't scale to.
    """
    u, v = twotower_embed(params, batch, c)
    logits = (u @ v.T).astype(jnp.float32) / c.temperature
    logq = batch.get("item_logq")
    if logq is not None:
        logits = logits - logq[None, :]
    labels = jnp.arange(u.shape[0])
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
    return loss, {"ce": loss}


def twotower_score_candidates(params, batch, cand_feats, c: TwoTowerConfig):
    """One query against C candidate items (C = 10^6 in retrieval_cand)."""
    u = _tower(params["user_tower"], params["user_table"], batch["user_feats"], c.tower)
    v = _tower(params["item_tower"], params["item_table"], cand_feats, c.tower)
    v = constrain(v, "candidates", None)
    return (u @ v.T).astype(jnp.float32) / c.temperature
