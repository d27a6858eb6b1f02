"""Decoder-only LM family covering the five assigned architectures.

One config describes them all (DESIGN.md §2):
  * layer `pattern` — repeating kinds, e.g. ("local", "global") for Gemma-2,
    ("chunked",)*3 + ("global",) for Llama-4 iRoPE, ("global",) for the rest;
  * attention = GQA (optional qkv bias / softcap / per-arch query scale) or
    MLA (DeepSeek latent attention, absorbed decode path);
  * FFN = gated MLP or MoE (sort-dispatch expert parallelism), with an
    optional dense prefix (DeepSeek-V2's first layer);
  * layers are *scanned* in groups of one pattern period — compile time and
    HLO size stay flat in depth, which is what makes 2x46-layer x 40-cell
    dry-runs tractable;
  * remat: each scan body is jax.checkpoint'ed (policy configurable — this
    is a §Perf hillclimb knob).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from jax import shard_map
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models.layers import (cross_entropy, dense, embed_lookup,
                                 mlp_apply, mlp_specs, rms_norm, softcap)
from repro.models.params import P
from repro.sharding import constrain

_POLICIES = {
    "full": None,  # jax.checkpoint default: save nothing, recompute all
    "dots": "dots_with_no_batch_dims_saveable",
    "none": "everything_saveable",
}


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    # attention
    attn_kind: str = "gqa"                    # "gqa" | "mla"
    mla: Optional[attn.MLAConfig] = None
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    attn_softcap: Optional[float] = None
    query_scale: Optional[float] = None
    pattern: tuple = ("global",)
    window: Optional[int] = None              # for "local" layers
    attn_chunk: Optional[int] = None          # for "chunked" layers
    rope_on_global: bool = True               # Llama-4 iRoPE: False
    # ffn
    activation: str = "silu"
    moe: Optional[moe_lib.MoEConfig] = None
    n_dense_prefix: int = 0                   # leading dense-FFN layers
    d_ff_prefix: Optional[int] = None
    # output / norms
    post_norms: bool = False                  # Gemma-2 extra norms
    norm_unit_offset: bool = False            # Gemma (1 + scale) RMSNorm
    final_softcap: Optional[float] = None
    embed_scale: bool = False                 # Gemma sqrt(d) embed scaling
    tie_embeddings: bool = False
    # numerics / scheduling
    dtype: object = jnp.bfloat16
    chunk_q: Optional[int] = None             # query-chunked attention
    kv_chunk: Optional[int] = None            # flash-style online softmax
    remat: str = "full"
    z_loss: float = 1e-4

    @property
    def n_groups(self) -> int:
        n = self.n_layers - self.n_dense_prefix
        assert n % len(self.pattern) == 0, (self.name, n, self.pattern)
        return n // len(self.pattern)

    def gqa(self) -> attn.GQAConfig:
        return attn.GQAConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, d_head=self.d_head,
            rope_theta=self.rope_theta, qkv_bias=self.qkv_bias,
            attn_softcap=self.attn_softcap, query_scale=self.query_scale)

    def cache_len(self, kind: str, max_len: int) -> int:
        if kind == "local":
            return min(self.window, max_len)
        if kind == "chunked":
            return min(self.attn_chunk, max_len)
        return max_len


# --------------------------------------------------------------------------
# parameter specs
# --------------------------------------------------------------------------

def _norm_spec(cfg: LMConfig) -> P:
    init = "zeros" if cfg.norm_unit_offset else "ones"
    return P((cfg.d_model,), (None,), init)


def _layer_specs(cfg: LMConfig, use_moe: bool, d_ff: int) -> dict:
    if cfg.attn_kind == "mla":
        a = attn.mla_specs(cfg.mla)
    else:
        a = attn.gqa_specs(cfg.gqa())
    specs = {"attn": a, "ln_attn": _norm_spec(cfg), "ln_mlp": _norm_spec(cfg)}
    if cfg.post_norms:
        specs["ln_attn_post"] = _norm_spec(cfg)
        specs["ln_mlp_post"] = _norm_spec(cfg)
    if use_moe:
        specs["moe"] = moe_lib.moe_specs(cfg.moe)
    else:
        specs["mlp"] = mlp_specs(cfg.d_model, d_ff, gated=True)
    return specs


def _stack_specs(specs, n: int):
    return jax.tree_util.tree_map(
        lambda p: P((n,) + p.shape, ("layers",) + (p.axes or (None,) * len(p.shape)),
                    p.init, p.dtype),
        specs, is_leaf=lambda x: isinstance(x, P))


def param_specs(cfg: LMConfig) -> dict:
    use_moe = cfg.moe is not None
    group = {f"l{j}": _layer_specs(cfg, use_moe, cfg.d_ff)
             for j in range(len(cfg.pattern))}
    specs = {
        "embed": P((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), "normal:0.02"),
        "blocks": _stack_specs(group, cfg.n_groups),
        "ln_final": _norm_spec(cfg),
    }
    for i in range(cfg.n_dense_prefix):
        specs[f"prefix{i}"] = _layer_specs(cfg, False,
                                           cfg.d_ff_prefix or cfg.d_ff)
    if not cfg.tie_embeddings:
        specs["head"] = P((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                          "normal:0.02")
    return specs


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _attend_layer(p, x, positions, cfg: LMConfig, kind: str, cache,
                  mode: str):
    use_rope = cfg.rope_on_global if kind == "global" else True
    if cfg.attn_kind == "mla":
        if mode == "decode":
            return attn.mla_decode(p, x, positions, cfg.mla, cache)
        y, c = attn.mla_prefill(p, x, positions, cfg.mla,
                                chunk_q=cfg.chunk_q, kv_chunk=cfg.kv_chunk,
                                want_cache=(mode == "prefill"))
        return y, c
    y, c = attn.gqa_apply(p, x, positions, cfg.gqa(), kind=kind,
                          window=cfg.window, attn_chunk=cfg.attn_chunk,
                          use_rope=use_rope, cache=cache,
                          chunk_q=cfg.chunk_q if mode != "decode" else None,
                          kv_chunk=cfg.kv_chunk if mode != "decode" else None,
                          want_cache=(mode == "prefill"))
    return y, c


def _layer(p, x, positions, cfg: LMConfig, kind: str, cache=None,
           mode: str = "train"):
    h = rms_norm(x, p["ln_attn"], unit_offset=cfg.norm_unit_offset)
    a, new_cache = _attend_layer(p["attn"], h, positions, cfg, kind, cache, mode)
    if cfg.post_norms:
        a = rms_norm(a, p["ln_attn_post"], unit_offset=cfg.norm_unit_offset)
    x = x + a
    h = rms_norm(x, p["ln_mlp"], unit_offset=cfg.norm_unit_offset)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        if cfg.moe.impl == "a2a":
            f, aux = _moe_shardmap(p["moe"], h, cfg)
        else:
            t, d = h.shape[0] * h.shape[1], h.shape[2]
            f, aux = moe_lib.moe_apply(p["moe"], h.reshape(t, d), cfg.moe)
            f = f.reshape(x.shape)
    else:
        f = mlp_apply(p["mlp"], h, cfg.activation)
    if cfg.post_norms:
        f = rms_norm(f, p["ln_mlp_post"], unit_offset=cfg.norm_unit_offset)
    return x + f, new_cache, aux


def _moe_shardmap(params, h, cfg: LMConfig):
    """Manual expert parallelism: shard_map around the MoE FFN.

    Tokens stay sharded (batch over data/pod, seq over model); experts are
    sharded over model.  Inside the body, routing is a single pair of
    capacity-bounded all_to_alls over the model axis (moe_apply_a2a).
    Falls back to the auto (GSPMD) path when no mesh context is active
    (e.g. single-host smoke tests without use_rules).
    """
    from repro.sharding import current_ctx, spec_for
    from jax.sharding import PartitionSpec as PS

    ctx = current_ctx()
    if ctx is None or "model" not in ctx[1].axis_names:
        t, d = h.shape[0] * h.shape[1], h.shape[2]
        f, aux = moe_lib.moe_apply(params, h.reshape(t, d), cfg.moe)
        return f.reshape(h.shape), aux
    rules, mesh = ctx
    h_spec = spec_for(("batch", "act_seq", "act_embed"), rules, mesh, h.shape)

    def leaf_spec(path_leaf):
        key, leaf = path_leaf
        if key in ("gate", "up", "down"):
            return PS("model", *([None] * (leaf.ndim - 1)))
        return PS(*([None] * leaf.ndim))

    p_specs = {k: jax.tree_util.tree_map(
        lambda leaf, k=k: leaf_spec((k, leaf)), v)
        for k, v in params.items()}

    def body(p_loc, h_loc):
        t = h_loc.reshape(-1, h_loc.shape[-1])
        y, aux = moe_lib.moe_apply_a2a(p_loc, t, cfg.moe, axis_name="model",
                                       mean_axes=mesh.axis_names)
        return y.reshape(h_loc.shape), aux

    return shard_map(body, mesh=mesh, in_specs=(p_specs, h_spec),
                     out_specs=(h_spec, PS()), check_vma=False)(params, h)


def _group_fwd(block, x, positions, cfg: LMConfig, caches=None,
               mode: str = "train"):
    new_caches = {}
    aux_total = jnp.zeros((), jnp.float32)
    for j, kind in enumerate(cfg.pattern):
        cache_j = caches[f"l{j}"] if caches is not None else None
        x, nc, aux = _layer(block[f"l{j}"], x, positions, cfg, kind,
                            cache_j, mode)
        if nc is not None:
            new_caches[f"l{j}"] = nc
        aux_total = aux_total + aux
    x = constrain(x, "batch", "act_seq", "act_embed")
    return x, new_caches, aux_total


def _embed(params, tokens, cfg: LMConfig):
    x = embed_lookup(params["embed"], tokens).astype(cfg.dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
    return constrain(x, "batch", "act_seq", "act_embed")


def _head(params, x, cfg: LMConfig):
    x = rms_norm(x, params["ln_final"], unit_offset=cfg.norm_unit_offset)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].astype(x.dtype).T
    else:
        logits = dense(x, params["head"])
    return softcap(logits.astype(jnp.float32), cfg.final_softcap)


def apply(params, tokens, cfg: LMConfig):
    """Training/eval forward: tokens (B, S) -> logits (B, S, V) fp32."""
    b, s = tokens.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    x = _embed(params, tokens, cfg)
    aux = jnp.zeros((), jnp.float32)
    for i in range(cfg.n_dense_prefix):
        x, _, _ = _layer(params[f"prefix{i}"], x, positions, cfg, "global")

    policy = _POLICIES[cfg.remat]

    def body(carry, block):
        x, aux = carry
        x, _, a = _group_fwd(block, x, positions, cfg)
        return (x, aux + a), None

    if policy == "everything_saveable":
        body_fn = body
    elif policy is None:
        body_fn = jax.checkpoint(body)
    else:
        body_fn = jax.checkpoint(body, policy=getattr(jax.checkpoint_policies, policy))
    (x, aux), _ = jax.lax.scan(body_fn, (x, aux), params["blocks"])
    return _head(params, x, cfg), aux


def loss_fn(params, batch, cfg: LMConfig):
    logits, aux = apply(params, batch["tokens"], cfg)
    ce = cross_entropy(logits, batch["targets"], z_loss=cfg.z_loss)
    total = ce + (cfg.moe.aux_weight * aux / cfg.n_layers if cfg.moe else 0.0)
    return total, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------------
# serving: cache init / prefill / decode
# --------------------------------------------------------------------------

def cache_specs(cfg: LMConfig, batch: int, max_len: int) -> dict:
    """P-spec tree for the KV cache (abstract for dry-run, zeros for real)."""
    def one(kind: str) -> dict:
        L = cfg.cache_len(kind, max_len)
        if cfg.attn_kind == "mla":
            return {
                "ckv": P((batch, L, cfg.mla.kv_lora), ("batch", "kv_seq", None),
                         "zeros", cfg.dtype),
                "kr": P((batch, L, cfg.mla.qk_rope), ("batch", "kv_seq", None),
                        "zeros", cfg.dtype),
                "pos": P((L,), ("kv_seq",), "neg_ones", jnp.int32),
            }
        return {
            "k": P((batch, L, cfg.n_kv_heads, cfg.d_head),
                   ("batch", "kv_seq", "cache_heads", None), "zeros", cfg.dtype),
            "v": P((batch, L, cfg.n_kv_heads, cfg.d_head),
                   ("batch", "kv_seq", "cache_heads", None), "zeros", cfg.dtype),
            "pos": P((L,), ("kv_seq",), "neg_ones", jnp.int32),
        }

    group = {f"l{j}": one(kind) for j, kind in enumerate(cfg.pattern)}
    specs = {"blocks": _stack_specs(group, cfg.n_groups)}
    for i in range(cfg.n_dense_prefix):
        specs[f"prefix{i}"] = one("global")
    return specs


def init_cache(cfg: LMConfig, batch: int, max_len: int):
    def mk(p: P):
        if p.init == "neg_ones":
            return -jnp.ones(p.shape, p.dtype)
        return jnp.zeros(p.shape, p.dtype)
    return jax.tree_util.tree_map(mk, cache_specs(cfg, batch, max_len),
                                  is_leaf=lambda x: isinstance(x, P))


def decode_step(params, cache, tokens, pos, cfg: LMConfig):
    """One token step. tokens (B, 1); pos () int32 -> (logits (B, V), cache)."""
    positions = pos[None].astype(jnp.int32)
    x = _embed(params, tokens, cfg)
    new_cache = {}
    for i in range(cfg.n_dense_prefix):
        x, nc, _ = _layer(params[f"prefix{i}"], x, positions, cfg, "global",
                          cache[f"prefix{i}"], mode="decode")
        new_cache[f"prefix{i}"] = nc

    def body(x, inp):
        block, cache_g = inp
        x, ncs, _ = _group_fwd(block, x, positions, cfg, cache_g, mode="decode")
        return x, ncs

    x, new_blocks = jax.lax.scan(body, x, (params["blocks"], cache["blocks"]))
    new_cache["blocks"] = new_blocks
    logits = _head(params, x, cfg)
    return logits[:, 0], new_cache


def prefill(params, tokens, cfg: LMConfig, max_len: int):
    """Prefill a prompt; returns (last-token logits (B, V), cache)."""
    b, s = tokens.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    x = _embed(params, tokens, cfg)
    out_cache = {}
    for i in range(cfg.n_dense_prefix):
        x, nc, _ = _layer(params[f"prefix{i}"], x, positions, cfg, "global",
                          mode="prefill")
        out_cache[f"prefix{i}"] = _pack_cache(nc, "global", cfg, s, max_len)

    def body(x, block):
        x, ncs, _ = _group_fwd(block, x, positions, cfg, mode="prefill")
        packed = {f"l{j}": _pack_cache(ncs[f"l{j}"], kind, cfg, s, max_len)
                  for j, kind in enumerate(cfg.pattern)}
        return x, packed

    x, blocks_cache = jax.lax.scan(body, x, params["blocks"])
    out_cache["blocks"] = blocks_cache
    logits = _head(params, x[:, -1:], cfg)
    return logits[:, 0], out_cache


def _pack_cache(raw, kind: str, cfg: LMConfig, s: int, max_len: int):
    """Convert prefill K/V (length s) into the fixed decode cache layout."""
    L = cfg.cache_len(kind, max_len)
    lo = max(0, s - L)
    positions = jnp.arange(lo, s, dtype=jnp.int32)
    slots = positions % L if kind in ("local", "chunked") else positions

    def place(x, fill):
        out = jnp.full((x.shape[0], L) + x.shape[2:], fill, x.dtype)
        return out.at[:, slots].set(x[:, lo:s])

    if cfg.attn_kind == "mla":
        ckv, kr = raw["ckv"], raw["kr"]
        pos = jnp.full((L,), -1, jnp.int32).at[slots].set(positions)
        return {"ckv": place(ckv, 0), "kr": place(kr, 0), "pos": pos}
    k, v = raw["k"], raw["v"]
    pos = jnp.full((L,), -1, jnp.int32).at[slots].set(positions)
    return {"k": place(k, 0), "v": place(v, 0), "pos": pos}


# --------------------------------------------------------------------------
# accounting
# --------------------------------------------------------------------------

def active_param_count(cfg: LMConfig) -> int:
    """Parameters touched per token (MoE counts top_k + shared experts)."""
    d, h = cfg.d_model, cfg.n_heads * cfg.d_head
    kvh = cfg.n_kv_heads * cfg.d_head
    if cfg.attn_kind == "mla":
        m = cfg.mla
        a = (d * m.n_heads * (m.qk_nope + m.qk_rope) + d * m.kv_lora
             + d * m.qk_rope + m.kv_lora * m.n_heads * (m.qk_nope + m.v_dim)
             + m.n_heads * m.v_dim * d)
    else:
        a = d * h * 2 + d * kvh * 2
    dense_ffn = 3 * d * cfg.d_ff
    if cfg.moe is not None:
        c = cfg.moe
        ffn = 3 * d * c.d_ff_expert * c.top_k + 3 * d * c.shared_ff + d * c.n_experts
    else:
        ffn = dense_ffn
    n_moe = cfg.n_layers - cfg.n_dense_prefix
    prefix_ffn = 3 * d * (cfg.d_ff_prefix or cfg.d_ff)
    return (cfg.n_layers * a + n_moe * ffn
            + cfg.n_dense_prefix * prefix_ffn)


def model_flops(cfg: LMConfig, n_tokens: int, seq_len: int) -> float:
    """6*N_active*D + attention score FLOPs (12*L*S*d_head*H per token)."""
    base = 6.0 * active_param_count(cfg) * n_tokens
    attn_f = 12.0 * cfg.n_layers * seq_len * cfg.d_head * cfg.n_heads * n_tokens
    return base + attn_f
