"""Attention: GQA/MQA, chunked (flash-style) causal attention, local windows,
and KV-cache decode.

Two execution paths:
  * XLA path (used for training/prefill dry-runs and CPU tests):
    ``chunked_attention`` — lax.scan over KV chunks with an online softmax, so
    peak memory is O(S * chunk) instead of O(S^2).  For causal masking the
    scan computes masked blocks too (~2x FLOP overcount on the strictly-upper
    half); the Pallas flash kernel (kernels/flash_attention.py) is the TPU
    target that skips them.  The ratio shows up honestly in the roofline's
    MODEL_FLOPS / HLO_FLOPs term.
  * Pallas path: kernels/ops.flash_attention (TPU target, validated in
    interpret mode).

Shapes: q (B, S, Hq, hd); k, v (B, Skv, Hkv, hd); Hq % Hkv == 0.  The
dense and chunked paths also take values of another head size (v (B, Skv,
Hkv, dv), latent attention's 128 against its 192-wide keys).

Latent attention (MLA, DeepSeek-V2/V3) keeps one latent per token and
layer in its cache (``mla_*`` below): the plain form expands it to per-head
keys and values and runs the attention above; the absorbed form scores the
queries against the cached latent itself (``mla_absorbed_scores``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _group_query(q: jax.Array, n_kv: int) -> jax.Array:
    """(B,S,Hq,hd) -> (B,S,Hkv,G,hd)."""
    b, s, hq, hd = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, hd)


# ---------------------------------------------------------------------------
# Dense (reference) attention — used at smoke scale and as the oracle.
# ---------------------------------------------------------------------------


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    kv_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-materialization attention. q_offset: absolute position of q[0]
    relative to k[0] (decode: q_offset = cache position)."""
    b, sq, hq, hd = q.shape
    n_kv = k.shape[2]
    qg = _group_query(q, n_kv).astype(jnp.float32)
    scale = hd ** -0.5
    scores = jnp.einsum("bsngd,btnd->bngst", qg * scale, k.astype(jnp.float32))
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    if kv_len is not None:
        mask &= kpos[None, :] < kv_len
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngst,btnd->bsngd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked flash-style attention (XLA path, memory-bounded)
# ---------------------------------------------------------------------------


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 1024,
    unroll: bool = False,
) -> jax.Array:
    """Online-softmax attention scanning over KV chunks.

    Peak live memory O(B*H*S*chunk).  Exact (bit-for-bit a softmax), masked
    like dense_attention with q_offset=0.
    """
    b, sq, hq, hd = q.shape
    skv = k.shape[1]
    n_kv = k.shape[2]
    dv = v.shape[-1]
    if skv % chunk:
        pad = chunk - skv % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    kc = k.reshape(b, n_chunks, chunk, n_kv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, n_kv, dv).transpose(1, 0, 2, 3, 4)

    qg = _group_query(q, n_kv).astype(jnp.float32) * hd ** -0.5
    qpos = jnp.arange(sq)

    # flash-style recompute: save only the (m, l, acc) carries per KV chunk;
    # the (sq x chunk) score/prob tensors are recomputed in the backward pass
    # instead of being stacked across scan steps (4-16x activation saving).
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, xs):
        m, l, acc = carry
        j, kj, vj = xs  # kj/vj: (B, chunk, n_kv, hd)
        kpos = j * chunk + jnp.arange(chunk)
        s = jnp.einsum("bsngd,btnd->bnsgt", qg, kj.astype(jnp.float32))
        mask = kpos[None, :] < skv
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bnsgt,btnd->bnsgd", p, vj.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    m0 = jnp.full((b, n_kv, sq, hq // n_kv), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n_kv, sq, hq // n_kv), jnp.float32)
    acc0 = jnp.zeros((b, n_kv, sq, hq // n_kv, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (jnp.arange(n_chunks), kc, vc),
        unroll=n_chunks if unroll else 1,
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 2, 1, 3, 4).reshape(b, sq, hq, dv)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Local (sliding-window) attention — exact banded form, O(S * W)
# ---------------------------------------------------------------------------


def local_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, window: int) -> jax.Array:
    """Causal sliding-window attention: each token attends to the previous
    ``window`` tokens (inclusive of itself).  Block form: q blocks of size W
    attend to [prev block | own block]."""
    b, s, hq, hd = q.shape
    n_kv = k.shape[2]
    w = window
    pad = (-s) % w
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = q.shape[1]
    nb = sp // w
    qb = _group_query(q, n_kv).reshape(b, nb, w, n_kv, hq // n_kv, hd)
    kb = k.reshape(b, nb, w, n_kv, hd)
    vb = v.reshape(b, nb, w, n_kv, hd)
    # previous block (block 0's "previous" is zeros, fully masked)
    kprev = jnp.pad(kb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    vprev = jnp.pad(vb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    k2 = jnp.concatenate([kprev, kb], axis=2)  # (b, nb, 2w, n_kv, hd)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    scale = hd ** -0.5
    s_ = jnp.einsum(
        "bcsngd,bctnd->bcnsgt", qb.astype(jnp.float32) * scale, k2.astype(jnp.float32)
    )
    qpos = jnp.arange(w)
    kpos = jnp.arange(2 * w) - w  # relative to block start
    mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] > qpos[:, None] - w)
    # mask out the zero "previous" of block 0
    blk = jnp.arange(nb)
    valid_prev = (blk[:, None, None] > 0) | (kpos[None, None, :] >= 0)
    full_mask = mask[None] & valid_prev
    s_ = jnp.where(full_mask[None, :, None, :, None, :], s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bcnsgt,bctnd->bcsngd", p, v2.astype(jnp.float32))
    out = out.reshape(b, sp, hq, hd)[:, :s]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode-step attention against a KV cache
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,  # (B, S_new, Hq, hd) — S_new > 1 during chunked prefill
    k_cache: jax.Array,  # (B, Smax, Hkv, hd)
    v_cache: jax.Array,
    cache_pos: jax.Array,  # () or (B,) int32: valid tokens INCLUDING new
    *,
    window: int = 0,
) -> jax.Array:
    """Attention against a KV cache.

    ``cache_pos`` counts valid cache entries including the ``S_new`` just
    inserted; a (B,) vector gives each slot its own fill level (continuous
    batching).  Queries are causal within the chunk: query ``i`` attends
    to ``kpos < cache_pos - (S_new - 1) + i``, which for S_new = 1 is the
    historical single-token mask.
    """
    b, sq, hq, hd = q.shape
    n_kv = k_cache.shape[2]
    skv = k_cache.shape[1]
    qg = _group_query(q, n_kv).astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bsngd,btnd->bnsgt", qg, k_cache.astype(jnp.float32))
    kpos = jnp.arange(skv)
    limit = (jnp.reshape(jnp.asarray(cache_pos), (-1, 1))
             - (sq - 1) + jnp.arange(sq)[None])  # (1 or B, S_new)
    mask = kpos[None, None, :] < limit[:, :, None]
    if window:
        mask &= kpos[None, None, :] >= limit[:, :, None] - window
    mask = jnp.broadcast_to(mask, (b, sq, skv))
    s = jnp.where(mask[:, None, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnsgt,btnd->bsngd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, sq, hq, hd).astype(q.dtype)


def update_kv_cache(k_cache, v_cache, k_new, v_new, pos):
    """Insert (B, S_new, Hkv, hd) at position ``pos`` along the seq axis.

    ``pos`` may be a scalar (whole batch at one position) or a (B,) vector
    (per-slot insert positions for continuous batching)."""
    pos = jnp.asarray(pos)
    k_new = k_new.astype(k_cache.dtype)
    v_new = v_new.astype(v_cache.dtype)
    if pos.ndim == 0:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k_new, (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v_new, (0, pos, 0, 0))
    else:
        upd = jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (p, 0, 0)))
        k_cache = upd(k_cache, k_new, pos)
        v_cache = upd(v_cache, v_new, pos)
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# Paged KV cache: shared page pool + per-slot block tables
# ---------------------------------------------------------------------------


def _paged_rows(pos, block_tables, b: int, sq: int, block_size: int,
                layer=None):
    """Pool addresses of the logical positions ``pos + i``: a tuple of
    (B * S_new,) page and in-page indices, led by ``layer`` when the pool
    is stacked over layers.  The page is block ``pos // block_size`` of
    each row's table, or block 0 (the null page) past the table's end."""
    max_blocks = block_tables.shape[1]
    pos = jnp.reshape(jnp.asarray(pos), (-1,))  # () or (B,) -> (1,) or (B,)
    pos = jnp.broadcast_to(pos, (b,))
    logical = pos[:, None] + jnp.arange(sq)[None, :]  # (B, S_new)
    valid = logical < max_blocks * block_size
    bidx = jnp.clip(logical // block_size, 0, max_blocks - 1)
    table = jnp.take_along_axis(block_tables, bidx, axis=1)  # (B, S_new)
    table = jnp.where(valid, table, 0)  # out-of-range -> null block
    rows = (table.reshape(-1), (logical % block_size).reshape(-1))
    return rows if layer is None else (layer,) + rows


def _pages(block_tables, layer=None):
    """Index of each slot's pages (B, max_blocks) in a pool, at ``layer``
    of a stacked one."""
    return block_tables if layer is None else (layer, block_tables)


def paged_update_kv_cache(pk, pv, k_new, v_new, pos, block_tables,
                          block_size: int, layer=None):
    """Scatter (B, S_new, Hkv, hd) new KV into the shared page pool.

    ``pk``/``pv`` are (n_blocks, block_size, Hkv, hd) pools shared by all
    slots, or (L, n_blocks, block_size, Hkv, hd) pools stacked over layers,
    of which ``layer`` is written: only the B * S_new new rows, in place,
    never the layer's whole slice.  ``block_tables`` is (B, max_blocks)
    int32 mapping each slot's logical block index to a physical page;
    ``pos`` is () or (B,) logical write offsets.  Writes at logical
    positions past the table (or rows whose table entry is unallocated)
    land in block 0 — the reserved null/garbage page — so masked-off slots
    and clamped indices can never corrupt live pages.  Token identity then
    rests on the attention length limit: garbage is only ever at positions
    >= a slot's valid length, where the mask zeroes it exactly
    (exp(NEG_INF) == 0.0 in f32)."""
    b, sq = k_new.shape[:2]
    at = _paged_rows(pos, block_tables, b, sq, block_size, layer)
    nk = k_new.astype(pk.dtype).reshape((b * sq,) + k_new.shape[2:])
    nv = v_new.astype(pv.dtype).reshape((b * sq,) + v_new.shape[2:])
    return pk.at[at].set(nk), pv.at[at].set(nv)


def paged_gather_kv(pk, pv, block_tables, block_size: int, layer=None):
    """Gather each slot's logical KV view from the page pool (at ``layer``
    of a stacked one): (B, max_blocks) pages -> (B, max_blocks*bs, Hkv, hd).

    The result feeds the existing ``decode_attention`` unchanged — its
    length limit masks every position past the slot's fill level, so
    whatever stale/null data the unwritten page tails hold contributes
    exactly zero probability mass."""
    b, max_blocks = block_tables.shape
    at = _pages(block_tables, layer)
    kc = pk[at]  # (B, max_blocks, bs, Hkv, hd)
    vc = pv[at]
    kc = kc.reshape((b, max_blocks * block_size) + pk.shape[-2:])
    vc = vc.reshape((b, max_blocks * block_size) + pv.shape[-2:])
    return kc, vc


# ---------------------------------------------------------------------------
# Latent attention (MLA)
# ---------------------------------------------------------------------------


def mla_plain_kv(latent, k_rope, wkv_b, nope: int):
    """The plain form's per-head keys and values: ``wkv_b`` (r, H, nope +
    dv) turns the normed latent (B, T, r) into each head's key part
    ``c W_uk`` and value ``c W_uv``; the roped key (B, T, rope) is shared
    by every head.  Returns k (B, T, H, nope + rope), v (B, T, H, dv)."""
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
    k_nope = jnp.einsum("btr,rhd->bthd", latent, w_uk)
    v = jnp.einsum("btr,rhd->bthd", latent, w_uv)
    h = wkv_b.shape[1]
    k_pe = jnp.broadcast_to(k_rope[:, :, None, :],
                            k_rope.shape[:2] + (h, k_rope.shape[-1]))
    return jnp.concatenate([k_nope, k_pe.astype(k_nope.dtype)], -1), v


def mla_absorbed_scores(q_nope, q_rope, latent, k_rope, w_uk):
    """Unscaled float32 scores (B, H, S, T) of queries against cached
    latents, the same numbers the plain form computes.

    The plain form's key of head ``h`` at position ``t`` is ``[c_t W_uk[h],
    k_rope_t]``, so ``q · k = q_nope · (c_t W_uk[h]) + q_rope · k_rope_t =
    (W_uk[h] q_nope) · c_t + q_rope · k_rope_t``: turning each query's
    ``q_nope`` through ``W_uk`` once (into the r-wide latent space) lets
    every score be read from the cached latent, with no per-head key ever
    formed.  The values absorb the same way on the output side
    (``mla_decode_attention``): ``sum_t p_t c_t W_uv = (sum_t p_t c_t)
    W_uv``."""
    f32 = jnp.float32
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope.astype(f32), w_uk.astype(f32))
    s = jnp.einsum("bshr,btr->bhst", q_lat, latent.astype(f32))
    return s + jnp.einsum("bshe,bte->bhst", q_rope.astype(f32),
                          k_rope.astype(f32))


def mla_decode_attention(q_nope, q_rope, cache, cache_pos, wkv_b, *,
                         scale: float):
    """Absorbed latent attention of (B, S_new, H, .) queries against a
    latent cache (B, T, r + rope) of normed latents and roped shared keys,
    masked as ``decode_attention`` masks (``cache_pos`` counts valid
    entries including the S_new just written).  Computes in float32, as
    ``decode_attention`` does.  Returns (B, S_new, H, dv)."""
    b, sq = q_nope.shape[:2]
    r = wkv_b.shape[0]
    nope = q_nope.shape[-1]
    latent, k_rope = cache[..., :r], cache[..., r:]
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
    s = mla_absorbed_scores(q_nope, q_rope, latent, k_rope, w_uk) * scale
    skv = cache.shape[1]
    kpos = jnp.arange(skv)
    limit = (jnp.reshape(jnp.asarray(cache_pos), (-1, 1))
             - (sq - 1) + jnp.arange(sq)[None])  # (1 or B, S_new)
    mask = jnp.broadcast_to(kpos[None, None, :] < limit[:, :, None],
                            (b, sq, skv))
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", p, latent.astype(jnp.float32))
    out = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv.astype(jnp.float32))
    return out.astype(q_nope.dtype)


def update_latent_cache(cache, new, pos):
    """Insert (B, S_new, W) latent entries into a (B, Smax, W) cache at
    ``pos``, a scalar or a (B,) vector of per-slot positions."""
    pos = jnp.asarray(pos)
    new = new.astype(cache.dtype)
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice(cache, new, (0, pos, 0))
    return jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
        c, n, (p, 0)))(cache, new, pos)


LANES = 128


def latent_pool_width(width: int) -> int:
    """Stored width of a latent pool row: ``width`` rounded up to whole
    128-lane tiles.  On a TPU an array whose last axis is no such multiple
    (MLA's 512 + 64 = 576) takes a compact layout at a program's boundary
    and the tiled one inside it, so every program that took the pool would
    copy the whole of it into the other layout on entry and back on exit."""
    return -(-width // LANES) * LANES


def paged_update_latent(pool, new, pos, block_tables, block_size: int,
                        layer=None):
    """Scatter (B, S_new, W) latent entries into a (n_blocks, block_size,
    ``latent_pool_width(W)``) pool, or into ``layer`` of one stacked over
    layers, with the addressing (null page included) of
    ``paged_update_kv_cache``; the pad columns are written with zeros."""
    b, sq, w = new.shape
    at = _paged_rows(pos, block_tables, b, sq, block_size, layer)
    rows = jnp.pad(new.astype(pool.dtype).reshape(b * sq, w),
                   ((0, 0), (0, pool.shape[-1] - w)))
    return pool.at[at].set(rows)


def paged_gather_latent(pool, block_tables, block_size: int, width: int,
                        layer=None):
    """Each slot's logical latent view (B, max_blocks * block_size,
    ``width``), the pool's pad columns left out."""
    b, max_blocks = block_tables.shape
    view = pool[_pages(block_tables, layer)]
    return view.reshape(b, max_blocks * block_size, -1)[..., :width]


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------


def flash_attention(
    q, k, v, *, causal=True, block_q: Optional[int] = None,
    block_kv: Optional[int] = None, interpret: Optional[bool] = None,
) -> jax.Array:
    """Pallas flash-kernel path with tuned tiling.

    Unpinned ``block_q``/``block_kv`` resolve through the kernel autotuner
    (kernels/tuning.py) instead of the kernel's historical hardcoded
    128/128 — the model path sees tuned attention shapes."""
    from repro.kernels import ops

    return ops.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_kv=block_kv, interpret=interpret)


def attention(
    q, k, v, *, causal=True, window=0, chunk=1024, force_dense: bool = False,
    unroll: bool = False, impl: str = "auto",
    block_q: Optional[int] = None, block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Route to the cheapest exact implementation for the shapes at hand.

    This is itself a paper-style fork-join: below the crossover (short
    sequences) the "serial" dense path wins (no scan/launch overhead); above
    it, the chunked path is required for memory.  See core/overhead.py for
    the analytic crossover; the static rule here (S <= 2*chunk) matches it
    for all assigned shapes.

    ``impl="flash"`` forces the Pallas kernel path, threading tuned (or
    explicitly pinned) ``block_q``/``block_kv`` through to the kernel.
    """
    if impl not in ("auto", "flash"):
        raise ValueError(f"impl must be 'auto' or 'flash', got {impl!r}")
    if impl == "flash":
        if window:
            raise ValueError("impl='flash' does not support sliding windows; "
                             "use the local_attention path")
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_kv=block_kv, interpret=interpret)
    s = q.shape[1]
    if window and not force_dense and s > 2 * window:
        return local_attention(q, k, v, window=window)
    if force_dense or s <= 2 * chunk:
        return dense_attention(q, k, v, causal=causal, window=window)
    return chunked_attention(q, k, v, causal=causal, window=window, chunk=chunk,
                             unroll=unroll)
