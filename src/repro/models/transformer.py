"""Decoder-only transformer assembly: uniform-pattern models run layers under
``jax.lax.scan`` over stacked params (small HLO, fast compile at 94 layers);
hybrid patterns (recurrentgemma) unroll.  Every block kind (attn / local /
rglru / rwkv) exposes the same (x, state) -> (x, state, aux) interface.
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

# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------


def _attn_init(key, cfg: ModelConfig, dtype):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, (cfg.n_heads, hd), dtype),
        "wk": dense_init(ks[1], cfg.d_model, (cfg.n_kv_heads, hd), dtype),
        "wv": dense_init(ks[2], cfg.d_model, (cfg.n_kv_heads, hd), dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, (cfg.d_model,), dtype),
    }


def _ffn_init(key, cfg: ModelConfig, dtype):
    if cfg.is_moe:
        return ffn_lib.moe_init(key, cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.activation, dtype)
    return ffn_lib.ffn_init(key, cfg.d_model, cfg.d_ff, cfg.activation, dtype)


def layer_init(key, cfg: ModelConfig, kind: str, dtype):
    k1, k2 = jax.random.split(key)
    p: Dict[str, Any] = {
        "ln1": rmsnorm_init(cfg.d_model, jnp.float32),
        "ln2": rmsnorm_init(cfg.d_model, jnp.float32),
    }
    if kind in ("attn", "local"):
        p["attn"] = _attn_init(k1, cfg, dtype)
        p["ffn"] = _ffn_init(k2, cfg, dtype)
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
    block_tables=None,
):
    """Returns (out, new_cache)."""
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
            bs = cache["pk"].shape[1]
            pk, pv = attn_lib.paged_update_kv_cache(
                cache["pk"], cache["pv"], k, v, cache_pos, block_tables, bs)
            kc, vc = attn_lib.paged_gather_kv(pk, pv, block_tables, bs)
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


def _ffn_apply(params, cfg: ModelConfig, x, ctx):
    if cfg.is_moe:
        return ffn_lib.moe_apply(
            params, x, top_k=cfg.experts_per_token, activation=cfg.activation, ctx=ctx
        )
    return ffn_lib.ffn_apply(params, x, cfg.activation), jnp.zeros((), jnp.float32)


def layer_apply(
    params, cfg: ModelConfig, kind: str, x, positions, *,
    state=None, cache_pos=None, ctx=None, block_tables=None,
) -> Tuple[jax.Array, Any, jax.Array]:
    """Pre-norm residual block. Returns (x, new_state, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    if kind in ("attn", "local"):
        window = cfg.window_size if kind == "local" else 0
        out, new_mix_state = _attn_apply(
            params["attn"], cfg, h, positions, window=window,
            cache=state, cache_pos=cache_pos, ctx=ctx,
            block_tables=block_tables,
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
        out2, aux = _ffn_apply(params["ffn"], cfg, h2, ctx)
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
    slot-shared page pool (``pk``/``pv`` leaves, no batch axis) addressed by
    per-slot block tables; windowed/recurrent kinds keep dense per-slot
    state (their footprint is already O(window) / O(1) per slot)."""
    hd = cfg.resolved_head_dim
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

    Paged page pools (``pk``/``pv`` leaves) have NO slot axis — they are
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
            lambda path, *ls: (
                ls[0] if getattr(path[-1], "key", None) in ("pk", "pv")
                else fn(axis, *ls)),
            *trees)

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
    leaf: every other leaf is a paged page pool (``pk``/``pv``), shared by
    all slots.  Only then can a program step a subset of the slots without
    carrying the rest of their state along."""
    leaves = jax.tree_util.tree_flatten_with_path(
        {k: v for k, v in state.items() if k != "pos"})[0]
    return all(getattr(path[-1], "key", None) in ("pk", "pv")
               for path, _ in leaves)


# ---------------------------------------------------------------------------
# Whole decoder stack
# ---------------------------------------------------------------------------


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


def stack_init(key, cfg: ModelConfig):
    dtype = dtype_of(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers)
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


def stack_apply(
    layers, cfg: ModelConfig, x, positions, *,
    states=None, cache_pos=None, ctx=None, remat: bool = True,
    block_tables=None,
):
    """Run all layers. Returns (x, new_states, aux_total)."""
    decode = states is not None

    if _use_scan(cfg):
        kind = cfg.block_pattern[0]

        def body(carry, xs):
            h, aux = carry
            if decode:
                lp, st = xs
            else:
                lp, st = xs, None
            # block_tables is closed over: a scan constant, identical for
            # every layer (block ids are shared across the stack)
            h, new_st, a = layer_apply(
                lp, cfg, kind, h, positions, state=st, cache_pos=cache_pos,
                ctx=ctx, block_tables=block_tables
            )
            return (h, aux + a), new_st

        if remat and not decode:
            body = jax.checkpoint(body, prevent_cse=False)
        xs = (layers, states) if decode else layers
        (x, aux), new_states = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
        return x, (new_states if decode else None), aux

    if _use_period_scan(cfg):
        p = len(cfg.block_pattern)
        n_groups, rest = _period_split(cfg)

        def period_body(carry, xs):
            h, aux = carry
            if decode:
                lps, sts = xs
            else:
                lps, sts = xs, None
            new_sts = {}
            for pos in range(p):
                st = sts[str(pos)] if decode else None
                h, new_st, a = layer_apply(
                    lps[str(pos)], cfg, cfg.block_pattern[pos], h, positions,
                    state=st, cache_pos=cache_pos, ctx=ctx,
                    block_tables=block_tables,
                )
                aux = aux + a
                if decode:
                    new_sts[str(pos)] = new_st
            return (h, aux), (new_sts if decode else None)

        body = period_body
        if remat and not decode:
            body = jax.checkpoint(period_body, prevent_cse=False)
        xs = (layers["groups"], states["groups"]) if decode else layers["groups"]
        (x, aux), new_group_states = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), xs
        )
        new_states = {"groups": new_group_states} if decode else None
        for j in range(rest):
            i = n_groups * p + j
            st = states[f"rest_{j}"] if decode else None
            fn = functools.partial(
                layer_apply, cfg=cfg, kind=cfg.block_kind(i),
                cache_pos=cache_pos, ctx=ctx, block_tables=block_tables,
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
            aux = aux + a
        return x, new_states, aux

    aux_total = jnp.zeros((), jnp.float32)
    new_states = {} if decode else None
    for i in range(cfg.n_layers):
        lp = layers[f"layer_{i}"]
        st = states[f"layer_{i}"] if decode else None
        fn = functools.partial(
            layer_apply, cfg=cfg, kind=cfg.block_kind(i),
            cache_pos=cache_pos, ctx=ctx, block_tables=block_tables,
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
        aux_total = aux_total + a
    return x, new_states, aux_total
