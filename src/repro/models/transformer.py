"""Decoder-only transformer assembly: uniform-pattern models run layers under
``jax.lax.scan`` over stacked params (small HLO, fast compile at 94 layers);
hybrid patterns (recurrentgemma) unroll; a MoE stack with leading dense
layers (``first_k_dense``, DeepSeek-V3) runs those alone, then scans the
expert layers.  Every block kind (attn / local / rglru / rwkv) exposes the
same (x, state) -> (x, state, aux) interface; full-attention layers of a
config with ``kv_lora_rank`` are latent attention (MLA).

``aux`` is the auxiliary loss, a scalar; in a model with experts it is
``{"aux": loss, "counts": (2,) int32}``, the counts being ``held_counts``
summed over the expert layers (``aux_loss`` reads the loss of either).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import ffn as ffn_lib
from repro.models import rglru as rglru_lib
from repro.models import rwkv as rwkv_lib
from repro.models.common import (
    dense_init,
    dtype_of,
    embed_init,
    positional,
    rmsnorm,
    rmsnorm_init,
)

# decode-state leaves that are paged pools shared by all slots (no slot
# axis): per-head K and V, or one latent entry per token (MLA)
POOL_LEAVES = ("pk", "pv", "pl")


def _is_pool(path) -> bool:
    """Whether a decode-state leaf at ``path`` is a paged pool."""
    return getattr(path[-1], "key", None) in POOL_LEAVES


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------


def _attn_init(key, cfg: ModelConfig, dtype):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    if cfg.is_mla:
        h, r = cfg.n_heads, cfg.kv_lora_rank
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return {
            "wq": dense_init(ks[0], cfg.d_model, (h, qk), dtype),
            "wkv_a": dense_init(ks[1], cfg.d_model, (cfg.latent_width,), dtype),
            "kv_norm": rmsnorm_init(r, jnp.float32),
            "wkv_b": dense_init(ks[2], r, (h, cfg.qk_nope_head_dim
                                           + cfg.v_head_dim), dtype),
            "wo": dense_init(ks[3], h * cfg.v_head_dim, (cfg.d_model,), dtype),
        }
    return {
        "wq": dense_init(ks[0], cfg.d_model, (cfg.n_heads, hd), dtype),
        "wk": dense_init(ks[1], cfg.d_model, (cfg.n_kv_heads, hd), dtype),
        "wv": dense_init(ks[2], cfg.d_model, (cfg.n_kv_heads, hd), dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, (cfg.d_model,), dtype),
    }


def _ffn_init(key, cfg: ModelConfig, dtype, dense: bool = False):
    if cfg.is_moe and not dense:
        return ffn_lib.moe_init(
            key, cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.activation, dtype,
            n_held=cfg.n_held_experts,
            shared_ff=cfg.moe_shared_experts * cfg.d_ff,
            scoring=cfg.moe_scoring)
    d_ff = cfg.dense_d_ff if dense else cfg.d_ff
    return ffn_lib.ffn_init(key, cfg.d_model, d_ff, cfg.activation, dtype)


def layer_init(key, cfg: ModelConfig, kind: str, dtype,
               dense_ffn: bool = False):
    """``dense_ffn``: a leading dense layer of a MoE stack."""
    k1, k2 = jax.random.split(key)
    p: Dict[str, Any] = {
        "ln1": rmsnorm_init(cfg.d_model, jnp.float32),
        "ln2": rmsnorm_init(cfg.d_model, jnp.float32),
    }
    if kind in ("attn", "local"):
        p["attn"] = _attn_init(k1, cfg, dtype)
        p["ffn"] = _ffn_init(k2, cfg, dtype, dense_ffn)
    elif kind == "rglru":
        p["rglru"] = rglru_lib.rglru_init(k1, cfg.d_model, cfg.lru_width or cfg.d_model, dtype)
        p["ffn"] = _ffn_init(k2, cfg, dtype)
    elif kind == "rwkv":
        p["time"] = rwkv_lib.rwkv_time_mix_init(k1, cfg.d_model, cfg.rnn_head_dim, dtype)
        p["channel"] = rwkv_lib.rwkv_channel_mix_init(k2, cfg.d_model, cfg.d_ff, dtype)
    else:
        raise ValueError(kind)
    return p


# ---------------------------------------------------------------------------
# Per-layer apply
# ---------------------------------------------------------------------------


def _attn_apply(
    params, cfg: ModelConfig, x, positions, *, window: int,
    cache=None, cache_pos=None, ctx=None, causal: bool = True,
    block_tables=None, layer=None,
):
    """Returns (out, new_cache).  ``layer``: the index of this layer in a
    paged pool stacked over layers (None when the pool is its own)."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ params["wq"].reshape(d, -1)).reshape(b, s, cfg.n_heads, hd)
    k = (x @ params["wk"].reshape(d, -1)).reshape(b, s, cfg.n_kv_heads, hd)
    v = (x @ params["wv"].reshape(d, -1)).reshape(b, s, cfg.n_kv_heads, hd)
    q = positional(q, positions, cfg.pos_type, cfg.rope_theta)
    k = positional(k, positions, cfg.pos_type, cfg.rope_theta)
    # NOTE: no explicit head-sharding constraint here.  With the residual
    # stream sequence-sharded, forcing heads onto the model axis makes GSPMD
    # resolve conflicting shardings through "involuntary full
    # rematerialization" copies (measured: >10x compile time and huge
    # resharding traffic).  Letting sharding propagate from x keeps q
    # S-sharded through the online-softmax scan — flash-style sequence
    # parallelism with one kv all-gather per chunk.  (§Perf iteration 0.)

    if cache is not None:
        # decode: insert new kv, attend against cache
        if window:
            slot = cache_pos % cache["k"].shape[1]  # ring buffer (size >= window)
            kc, vc = attn_lib.update_kv_cache(cache["k"], cache["v"], k, v, slot)
            n_valid = jnp.minimum(cache_pos + s, kc.shape[1])
            out = attn_lib.decode_attention(q, kc, vc, n_valid, window=0)
            new_cache = {"k": kc, "v": vc}
        elif "pk" in cache:
            # paged: scatter into the shared page pool by block table, then
            # gather this batch's logical view; the length limit inside
            # decode_attention masks every unwritten/garbage position
            bs = cache["pk"].shape[-3]
            pk, pv = attn_lib.paged_update_kv_cache(
                cache["pk"], cache["pv"], k, v, cache_pos, block_tables, bs,
                layer)
            kc, vc = attn_lib.paged_gather_kv(pk, pv, block_tables, bs, layer)
            out = attn_lib.decode_attention(q, kc, vc, cache_pos + s)
            new_cache = {"pk": pk, "pv": pv}
        else:
            kc, vc = attn_lib.update_kv_cache(cache["k"], cache["v"], k, v, cache_pos)
            out = attn_lib.decode_attention(q, kc, vc, cache_pos + s)
            new_cache = {"k": kc, "v": vc}
        if ctx is not None:
            # Pin the attention output's sharding before the wo contraction.
            # With wo row-sharded, GSPMD otherwise propagates a head-dim
            # partition backward into the grouped-query einsum and the ring
            # buffer update; when heads don't divide the model axis the
            # padded partition miscompiles the windowed decode path (k-cache
            # rows scaled by the GQA group count).  constrain_heads shards
            # heads only when divisible, replicating otherwise.
            out = ctx.constrain_heads(out)
    else:
        chunk = ctx.attn_chunk if ctx is not None else 1024
        out = attn_lib.attention(
            q, k, v, causal=causal, window=window, chunk=chunk,
            unroll=bool(ctx is not None and ctx.unroll_scans),
        )
        new_cache = None
    out = out.reshape(b, s, -1) @ params["wo"].reshape(-1, d)
    return out, new_cache


def _mla_apply(params, cfg: ModelConfig, x, positions, *, cache=None,
               cache_pos=None, ctx=None, block_tables=None, layer=None):
    """Latent attention (scope ``mla``).  Returns (out, new_cache).

    Queries are ``wq`` (d -> H x (nope + rope)); ``wkv_a`` (d -> r + rope)
    gives the latent, RMS-normed by ``kv_norm``, and one rope key shared by
    the heads; RoPE turns only the rope parts; scale (nope + rope)^-0.5,
    causal.  Without a cache (training, full forwards) the plain form
    expands the latent to per-head K and V through ``wkv_b``; with one,
    each token writes one (r + rope) entry per layer (``lat`` per slot, or
    the paged pool ``pl``) and attention runs in the absorbed form against
    the cached latents (``attention.mla_absorbed_scores``).  ``layer`` as
    in ``_attn_apply``."""
    b, s, d = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla"):
        q = (x @ params["wq"].reshape(d, -1)).reshape(b, s, h, nope + rope)
        q_nope = q[..., :nope]
        q_rope = positional(q[..., nope:], positions, cfg.pos_type,
                            cfg.rope_theta)
        kv = x @ params["wkv_a"]
        latent = rmsnorm(params["kv_norm"], kv[..., :r], cfg.norm_eps)
        k_rope = positional(kv[..., None, r:], positions, cfg.pos_type,
                            cfg.rope_theta)[:, :, 0]
        if cache is None:
            k, v = attn_lib.mla_plain_kv(latent, k_rope, params["wkv_b"],
                                         nope)
            out = attn_lib.attention(
                jnp.concatenate([q_nope, q_rope], -1), k, v, causal=True,
                chunk=ctx.attn_chunk if ctx is not None else 1024,
                unroll=bool(ctx is not None and ctx.unroll_scans))
            new_cache = None
        else:
            entry = jnp.concatenate([latent, k_rope], -1)
            if "pl" in cache:
                bs = cache["pl"].shape[-2]
                pool = attn_lib.paged_update_latent(
                    cache["pl"], entry, cache_pos, block_tables, bs, layer)
                view = attn_lib.paged_gather_latent(
                    pool, block_tables, bs, entry.shape[-1], layer)
                new_cache = {"pl": pool}
            else:
                view = attn_lib.update_latent_cache(cache["lat"], entry,
                                                    cache_pos)
                new_cache = {"lat": view}
            out = attn_lib.mla_decode_attention(
                q_nope, q_rope, view, cache_pos + s, params["wkv_b"],
                scale=(nope + rope) ** -0.5)
        out = out.reshape(b, s, -1) @ params["wo"]
    return out, new_cache


def _aux_zero(cfg: ModelConfig):
    if cfg.is_moe:
        return {"aux": jnp.zeros((), jnp.float32),
                "counts": jnp.zeros((2,), jnp.int32)}
    return jnp.zeros((), jnp.float32)


def _aux_add(a, b):
    return jax.tree.map(lambda u, v: u + v, a, b)


def aux_loss(aux):
    """The auxiliary loss of a stack's ``aux``."""
    return aux["aux"] if isinstance(aux, dict) else aux


def _ffn_apply(params, cfg: ModelConfig, x, ctx, dense: bool = False,
               token_mask=None):
    if cfg.is_moe and not dense:
        y, aux, counts = ffn_lib.moe_apply(
            params, x, top_k=cfg.experts_per_token, activation=cfg.activation,
            ctx=ctx, scoring=cfg.moe_scoring, scale=cfg.routed_scaling,
            token_mask=token_mask)
        return y, {"aux": aux, "counts": counts}
    return ffn_lib.ffn_apply(params, x, cfg.activation), _aux_zero(cfg)


def layer_apply(
    params, cfg: ModelConfig, kind: str, x, positions, *,
    state=None, cache_pos=None, ctx=None, block_tables=None,
    dense_ffn: bool = False, token_mask=None, layer=None,
) -> Tuple[jax.Array, Any, Any]:
    """Pre-norm residual block. Returns (x, new_state, aux).
    ``dense_ffn``: a leading dense layer of a MoE stack; ``token_mask``
    (B, S) marks the tokens an expert layer counts; ``layer`` indexes a
    paged pool in ``state`` that is stacked over layers (``_layer_scan``)."""
    aux = _aux_zero(cfg)
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    if kind == "attn" and cfg.is_mla:
        out, new_mix_state = _mla_apply(
            params["attn"], cfg, h, positions, cache=state,
            cache_pos=cache_pos, ctx=ctx, block_tables=block_tables,
            layer=layer)
    elif kind in ("attn", "local"):
        window = cfg.window_size if kind == "local" else 0
        out, new_mix_state = _attn_apply(
            params["attn"], cfg, h, positions, window=window,
            cache=state, cache_pos=cache_pos, ctx=ctx,
            block_tables=block_tables, layer=layer,
        )
    elif kind == "rglru":
        out, new_mix_state = rglru_lib.rglru_apply(params["rglru"], h, state)
    elif kind == "rwkv":
        out, new_mix_state = rwkv_lib.rwkv_time_mix(
            params["time"], h, cfg.rnn_head_dim, state["time"] if state else None,
            chunk=(ctx.rnn_chunk if ctx is not None else 64),
            unroll=bool(ctx is not None and ctx.unroll_scans),
        )
    else:
        raise ValueError(kind)
    x = x + out
    if ctx is not None:
        x = ctx.constrain_act(x)

    h2 = rmsnorm(params["ln2"], x, cfg.norm_eps)
    if kind == "rwkv":
        out2, new_cm_state = rwkv_lib.rwkv_channel_mix(
            params["channel"], h2, state["channel"] if state else None
        )
        new_state = (
            {"time": new_mix_state, "channel": new_cm_state} if state is not None else None
        )
    else:
        out2, aux = _ffn_apply(params["ffn"], cfg, h2, ctx, dense_ffn,
                               token_mask)
        new_state = new_mix_state
    x = x + out2
    if ctx is not None:
        x = ctx.constrain_act(x)
    return x, new_state, aux


# ---------------------------------------------------------------------------
# Decode-state init per layer kind
# ---------------------------------------------------------------------------


def layer_init_state(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype,
                     paging=None):
    """``paging=(n_blocks, block_size)`` switches full-attention layers to a
    slot-shared page pool (``pk``/``pv`` leaves, or one ``pl`` latent leaf
    under MLA; no batch axis) addressed by per-slot block tables;
    windowed/recurrent kinds keep dense per-slot state (their footprint is
    already O(window) / O(1) per slot)."""
    hd = cfg.resolved_head_dim
    if kind == "attn" and cfg.is_mla:
        # one latent entry per token and layer, no head axis; a pool row
        # is padded to whole lane tiles (attention.latent_pool_width)
        if paging is not None:
            n_blocks, block_size = paging
            width = attn_lib.latent_pool_width(cfg.latent_width)
            return {"pl": jnp.zeros((n_blocks, block_size, width), dtype)}
        return {"lat": jnp.zeros((batch, max_len, cfg.latent_width), dtype)}
    if kind == "attn":
        if paging is not None:
            n_blocks, block_size = paging
            return {
                "pk": jnp.zeros((n_blocks, block_size, cfg.n_kv_heads, hd), dtype),
                "pv": jnp.zeros((n_blocks, block_size, cfg.n_kv_heads, hd), dtype),
            }
        return {
            "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        }
    if kind == "local":
        w = cfg.window_size
        return {
            "k": jnp.zeros((batch, w, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, w, cfg.n_kv_heads, hd), dtype),
        }
    if kind == "rglru":
        return rglru_lib.rglru_init_state(batch, cfg.lru_width or cfg.d_model, dtype)
    if kind == "rwkv":
        return rwkv_lib.rwkv_init_state(batch, cfg.d_model, cfg.rnn_head_dim, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Slot-indexed decode-state surgery (continuous-batching serving)
# ---------------------------------------------------------------------------


def stack_state_map(cfg: ModelConfig, fn, *states):
    """Map ``fn(batch_axis, *leaves)`` over decode-state trees from
    ``stack_init_state``, supplying each leaf's slot (batch) axis.

    Scan and period-scan layouts stack layer/group states with a leading
    layer axis, so their slot axis is 1; unrolled layers (and period-scan
    ``rest_*`` tails) keep the slot axis at 0.  The serving slot pool uses
    this to reset/insert a single slot without knowing the layout.

    Paged page pools (``POOL_LEAVES``) have NO slot axis — they are
    shared storage addressed by block tables, and slot semantics (reset,
    insert, freeze) live entirely in the tables and refcounts.  Per-slot
    surgery therefore passes them through from the FIRST state tree
    untouched: reset keeps the pool, insert keeps the destination pool,
    and merge (new-first) takes the freshly-written pool — numerically
    safe because a masked slot's stale pages sit past its length limit,
    where attention zeroes them exactly.
    """
    def mapper(axis, *trees):
        return jax.tree_util.tree_map_with_path(
            lambda path, *ls: ls[0] if _is_pool(path) else fn(axis, *ls),
            *trees)

    if _use_lead_scan(cfg):
        return {key: mapper(1 if key == "scan" else 0, *[s[key] for s in states])
                for key in states[0]}
    if _use_scan(cfg):
        return mapper(1, *states)
    if _use_period_scan(cfg):
        out = {"groups": mapper(1, *[s["groups"] for s in states])}
        for key in states[0]:
            if key != "groups":
                out[key] = mapper(0, *[s[key] for s in states])
        return out
    return mapper(0, *states)


def pages_only(state) -> bool:
    """True when the positions ``pos`` are a decode state's only per-slot
    leaf: every other leaf is a paged page pool (``POOL_LEAVES``), shared
    by all slots.  Only then can a program step a subset of the slots
    without carrying the rest of their state along."""
    leaves = jax.tree_util.tree_flatten_with_path(
        {k: v for k, v in state.items() if k != "pos"})[0]
    return all(_is_pool(path) for path, _ in leaves)


# ---------------------------------------------------------------------------
# Whole decoder stack
# ---------------------------------------------------------------------------


def _use_lead_scan(cfg: ModelConfig) -> bool:
    """Leading dense layers (``layer_<i>``, applied alone), then a scan over
    the expert layers (``scan``): their FFNs differ, so one scan cannot
    hold both."""
    return cfg.is_moe and cfg.first_k_dense > 0


def _use_scan(cfg: ModelConfig) -> bool:
    return cfg.uniform_pattern() and cfg.n_layers >= 4


def _use_period_scan(cfg: ModelConfig) -> bool:
    """Hybrid patterns (e.g. recurrentgemma's rglru,rglru,local) scan over
    PERIOD GROUPS: the scan body applies one full pattern period, xs carries
    p stacked param trees.  8-26x smaller HLO than unrolling; measured >12x
    compile-time win on recurrentgemma train_4k (EXPERIMENTS.md §Perf)."""
    p = len(cfg.block_pattern)
    return (not cfg.uniform_pattern()) and cfg.n_layers // p >= 2


def _period_split(cfg: ModelConfig):
    p = len(cfg.block_pattern)
    return cfg.n_layers // p, cfg.n_layers % p  # (n_groups, remainder)


def _lead_kind(cfg: ModelConfig) -> str:
    if not cfg.uniform_pattern() or cfg.n_layers <= cfg.first_k_dense:
        raise ValueError(f"{cfg.name}: leading dense layers need a uniform "
                         "pattern and expert layers after them")
    return cfg.block_pattern[0]


def stack_init(key, cfg: ModelConfig):
    dtype = dtype_of(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers)
    if _use_lead_scan(cfg):
        kind, k = _lead_kind(cfg), cfg.first_k_dense
        params = {f"layer_{i}": layer_init(keys[i], cfg, kind, dtype,
                                           dense_ffn=True)
                  for i in range(k)}
        params["scan"] = jax.vmap(
            lambda kk: layer_init(kk, cfg, kind, dtype))(keys[k:])
        return params
    if _use_scan(cfg):
        kind = cfg.block_pattern[0]
        return jax.vmap(lambda k: layer_init(k, cfg, kind, dtype))(keys)
    if _use_period_scan(cfg):
        p = len(cfg.block_pattern)
        n_groups, rest = _period_split(cfg)
        grouped = keys[: n_groups * p].reshape(n_groups, p, 2)
        params = {
            "groups": {
                str(pos): jax.vmap(
                    lambda k, pos=pos: layer_init(k, cfg, cfg.block_pattern[pos], dtype)
                )(grouped[:, pos])
                for pos in range(p)
            }
        }
        for j in range(rest):
            i = n_groups * p + j
            params[f"rest_{j}"] = layer_init(keys[i], cfg, cfg.block_kind(i), dtype)
        return params
    return {
        f"layer_{i}": layer_init(keys[i], cfg, cfg.block_kind(i), dtype)
        for i in range(cfg.n_layers)
    }


def stack_init_state(cfg: ModelConfig, batch: int, max_len: int, paging=None):
    dtype = dtype_of(cfg.dtype)
    if _use_lead_scan(cfg):
        kind, k = _lead_kind(cfg), cfg.first_k_dense
        one = layer_init_state(cfg, kind, batch, max_len, dtype, paging)
        state = {f"layer_{i}": one for i in range(k)}
        state["scan"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_layers - k,) + x.shape),
            one)
        return state
    if _use_scan(cfg):
        kind = cfg.block_pattern[0]
        one = layer_init_state(cfg, kind, batch, max_len, dtype, paging)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), one
        )
    if _use_period_scan(cfg):
        p = len(cfg.block_pattern)
        n_groups, rest = _period_split(cfg)
        state = {
            "groups": {
                str(pos): jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape),
                    layer_init_state(cfg, cfg.block_pattern[pos], batch, max_len,
                                     dtype, paging),
                )
                for pos in range(p)
            }
        }
        for j in range(rest):
            i = n_groups * p + j
            state[f"rest_{j}"] = layer_init_state(cfg, cfg.block_kind(i), batch,
                                                  max_len, dtype, paging)
        return state
    return {
        f"layer_{i}": layer_init_state(cfg, cfg.block_kind(i), batch, max_len,
                                       dtype, paging)
        for i in range(cfg.n_layers)
    }


def _split_pools(states):
    """(pool leaves, other leaves, how to join them) of a decode-state
    tree; the pools are its ``POOL_LEAVES``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(states)
    marks = tuple(_is_pool(path) for path, _ in flat)
    pools = [leaf for (_, leaf), m in zip(flat, marks) if m]
    rest = [leaf for (_, leaf), m in zip(flat, marks) if not m]
    return pools, rest, (treedef, marks)


def _join_pools(pools, rest, how):
    treedef, marks = how
    pools, rest = iter(pools), iter(rest)
    return treedef.unflatten([next(pools) if m else next(rest)
                              for m in marks])


def _layer_scan(step, carry, layers, states, remat: bool):
    """``jax.lax.scan`` of ``step(carry, params, state, layer) -> (carry,
    new_state)`` over params stacked over layers (or period groups), and
    over their decode states unless ``states`` is None (a full forward,
    rematerialised under ``remat``; ``state`` and ``layer`` None).
    Returns (carry, new_states or None).

    A paged pool (``POOL_LEAVES``) is stacked whole, with no slot axis.
    Scanned as ``xs``/``ys`` it would be sliced per layer and written back
    into a fresh stacked buffer: a copy of the whole pool per step, and
    another where a loop carries the state around the scan.  So the pools
    ride in the scan's carry, and each layer scatters its new rows into,
    and gathers its pages from, index ``layer`` (the scan's) in place.
    Every other leaf is sliced per layer as ``xs``/``ys``."""
    if states is None:
        def forward(c, params):
            return step(c, params, None, None)[0], None

        if remat:
            forward = jax.checkpoint(forward, prevent_cse=False)
        return jax.lax.scan(forward, carry, layers)[0], None

    pools, rest, how = _split_pools(states)

    def body(c, xs):
        inner, pools, layer = c
        params, rest = xs
        inner, new_state = step(inner, params, _join_pools(pools, rest, how),
                                layer)
        pools, rest, _ = _split_pools(new_state)
        return (inner, pools, layer + 1), rest

    (carry, pools, _), rest = jax.lax.scan(
        body, (carry, pools, jnp.int32(0)), (layers, rest))
    return carry, _join_pools(pools, rest, how)


def _scan_layers(layers, states, cfg: ModelConfig, kind: str, x, aux,
                 positions, *, cache_pos, ctx, remat: bool, block_tables,
                 token_mask):
    """``layer_apply`` over stacked layer params (and decode states), by
    ``_layer_scan``.  Returns (x, new_states or None, aux)."""
    def step(carry, lp, st, layer):
        h, aux = carry
        # block_tables is closed over: a scan constant, identical for
        # every layer (block ids are shared across the stack)
        h, new_st, a = layer_apply(
            lp, cfg, kind, h, positions, state=st, cache_pos=cache_pos,
            ctx=ctx, block_tables=block_tables, token_mask=token_mask,
            layer=layer,
        )
        return (h, _aux_add(aux, a)), new_st

    (x, aux), new_states = _layer_scan(step, (x, aux), layers, states,
                                       remat)
    return x, new_states, aux


def stack_apply(
    layers, cfg: ModelConfig, x, positions, *,
    states=None, cache_pos=None, ctx=None, remat: bool = True,
    block_tables=None, token_mask=None,
):
    """Run all layers. Returns (x, new_states, aux_total).  ``token_mask``
    (B, S) marks the tokens the expert layers count (all if None)."""
    decode = states is not None

    if _use_lead_scan(cfg):
        kind = _lead_kind(cfg)
        aux = _aux_zero(cfg)
        new_states = {} if decode else None
        for i in range(cfg.first_k_dense):
            fn = functools.partial(
                layer_apply, cfg=cfg, kind=kind, cache_pos=cache_pos,
                ctx=ctx, block_tables=block_tables, dense_ffn=True,
                token_mask=token_mask,
            )
            lp = layers[f"layer_{i}"]
            if remat and not decode:
                x, _, a = jax.checkpoint(
                    lambda lp, h, pos, f=fn: f(lp, x=h, positions=pos,
                                               state=None),
                    prevent_cse=False,
                )(lp, x, positions)
            else:
                x, new_st, a = fn(lp, x=x, positions=positions,
                                  state=states[f"layer_{i}"] if decode
                                  else None)
                if decode:
                    new_states[f"layer_{i}"] = new_st
            aux = _aux_add(aux, a)
        x, scan_states, aux = _scan_layers(
            layers["scan"], states["scan"] if decode else None, cfg, kind, x,
            aux, positions, cache_pos=cache_pos, ctx=ctx, remat=remat,
            block_tables=block_tables, token_mask=token_mask)
        if decode:
            new_states["scan"] = scan_states
        return x, new_states, aux

    if _use_scan(cfg):
        return _scan_layers(
            layers, states, cfg, cfg.block_pattern[0], x, _aux_zero(cfg),
            positions, cache_pos=cache_pos, ctx=ctx, remat=remat,
            block_tables=block_tables, token_mask=token_mask)

    if _use_period_scan(cfg):
        p = len(cfg.block_pattern)
        n_groups, rest = _period_split(cfg)

        def period_step(carry, lps, sts, group):
            h, aux = carry
            new_sts = {}
            for pos in range(p):
                h, new_sts[str(pos)], a = layer_apply(
                    lps[str(pos)], cfg, cfg.block_pattern[pos], h, positions,
                    state=sts[str(pos)] if decode else None,
                    cache_pos=cache_pos, ctx=ctx, block_tables=block_tables,
                    token_mask=token_mask, layer=group,
                )
                aux = _aux_add(aux, a)
            return (h, aux), new_sts

        (x, aux), group_states = _layer_scan(
            period_step, (x, _aux_zero(cfg)), layers["groups"],
            states["groups"] if decode else None, remat)
        new_states = {"groups": group_states} if decode else None
        for j in range(rest):
            i = n_groups * p + j
            st = states[f"rest_{j}"] if decode else None
            fn = functools.partial(
                layer_apply, cfg=cfg, kind=cfg.block_kind(i),
                cache_pos=cache_pos, ctx=ctx, block_tables=block_tables,
                token_mask=token_mask,
            )
            if remat and not decode:
                x, _, a = jax.checkpoint(
                    lambda lp, h, pos, f=fn: f(lp, x=h, positions=pos, state=None),
                    prevent_cse=False,
                )(layers[f"rest_{j}"], x, positions)
            else:
                x, new_st, a = fn(layers[f"rest_{j}"], x=x, positions=positions, state=st)
                if decode:
                    new_states[f"rest_{j}"] = new_st
            aux = _aux_add(aux, a)
        return x, new_states, aux

    aux_total = _aux_zero(cfg)
    new_states = {} if decode else None
    for i in range(cfg.n_layers):
        lp = layers[f"layer_{i}"]
        st = states[f"layer_{i}"] if decode else None
        fn = functools.partial(
            layer_apply, cfg=cfg, kind=cfg.block_kind(i),
            cache_pos=cache_pos, ctx=ctx, block_tables=block_tables,
            token_mask=token_mask,
        )
        if remat and not decode:
            fn = jax.checkpoint(
                lambda lp, h, pos, f=fn: f(lp, x=h, positions=pos, state=None),
                prevent_cse=False,
            )
            x, _, a = fn(lp, x, positions)
        else:
            x, new_st, a = fn(lp, x=x, positions=positions, state=st)
            if decode:
                new_states[f"layer_{i}"] = new_st
        aux_total = _aux_add(aux_total, a)
    return x, new_states, aux_total
