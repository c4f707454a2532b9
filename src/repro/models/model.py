"""build_model(config) -> Model: a uniform functional API over every assigned
architecture (decoder-only, hybrid, SSM, MoE, enc-dec, VLM backbone).

Batch conventions (all synthetic / stub-frontend per assignment):
  train, decoder-only : {"tokens": (B, S) i32}
  train, vlm          : + {"vision_embeds": (B, P, D), "positions": (B, S, 3)}
  train, audio encdec : {"frames": (B, S, D), "tokens": (B, S) i32}
  decode              : {"tokens": (B, 1)} (+ positions for mrope); state holds caches
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec as encdec_lib
from repro.models import transformer as tfm
from repro.models.common import dtype_of, embed_init, embed_lookup, rmsnorm, rmsnorm_init, unembed

AUX_LOSS_WEIGHT = 0.01
Z_LOSS_WEIGHT = 1e-4
# stub: fraction of the sequence occupied by vision patches for VLM training
VLM_PATCH_FRACTION = 8
# stub: encoder frames per decoder token length in enc-dec decode
ENCDEC_DECODE_ENC_LEN = 4096


def _positions_default(b, s, offset=0):
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32) + offset, (b, s))


def _offset_positions(b: int, s: int, offset) -> jax.Array:
    """(B, S) absolute positions from a scalar or per-slot (B,) offset."""
    off = jnp.asarray(offset, jnp.int32)
    off = off[:, None] if off.ndim else off[None, None]
    return jnp.broadcast_to(off + jnp.arange(s, dtype=jnp.int32)[None], (b, s))


def mrope_positions(b: int, s: int, offset) -> jax.Array:
    """(B, S, 3) text-only mrope positions: the three planes share the
    sequential index.  ``offset`` is a scalar or a per-slot (B,) vector —
    the one helper both the prefill and decode serving paths use instead of
    hand-building position tensors."""
    pos = _offset_positions(b, s, offset)
    return jnp.broadcast_to(pos[:, :, None], (b, s, 3))


def _zero_slots(leaf, mask, axis):
    """Zero ``leaf`` where the slot ``mask`` is True along ``axis``."""
    shape = [1] * leaf.ndim
    shape[axis] = mask.shape[0]
    return jnp.where(mask.reshape(shape), jnp.zeros((), leaf.dtype), leaf)


def _select_slots(mask, axis, new, old):
    """Take ``new`` where the slot ``mask`` is True along ``axis``, else
    keep ``old`` — the per-slot freeze behind masked decode steps."""
    shape = [1] * new.ndim
    shape[axis] = mask.shape[0]
    return jnp.where(mask.reshape(shape), new, old)


# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., Any]
    loss: Callable[..., Any]  # (params, batch, ctx) -> (loss, metrics)
    decode_step: Callable[..., Any]  # (params, state, batch, ctx) -> (logits, state)
    init_decode_state: Callable[..., Any]  # (batch_size, max_len[, per_slot]) -> state
    forward_logits: Callable[..., Any] = None  # (params, batch, ctx) -> (B,S,V)
    prefill: Callable[..., Any] = None  # (params, batch, ctx) -> (B,1,V) last-pos logits
    vlm_patches: Callable[[int], int] = staticmethod(lambda s: 0)
    # slot-indexed decode-state surgery (continuous-batching slot pool);
    # all take/return per-slot (per_slot=True) states
    reset_decode_slots: Callable[..., Any] = None  # (state, slot_mask) -> state
    merge_decode_state: Callable[..., Any] = None  # (new, old, active) -> state
    # models with experts: decode_step that also returns the (2,) int32
    # [held_assignments, experts_hit] of the tokens where the batch's
    # optional ``token_mask`` (B, S) is true, summed over the expert layers
    decode_step_counted: Optional[Callable[..., Any]] = None


def build_model(cfg: ModelConfig) -> Model:
    if cfg.is_encdec:
        return _build_encdec(cfg)
    return _build_decoder_only(cfg)


# ---------------------------------------------------------------------------
# Decoder-only
# ---------------------------------------------------------------------------


def _vlm_patches(cfg: ModelConfig, s: int) -> int:
    if cfg.frontend != "vision" or s <= 8:
        return 0
    return min(1024, s // VLM_PATCH_FRACTION)


def _build_decoder_only(cfg: ModelConfig) -> Model:
    dtype = dtype_of(cfg.dtype)

    def init(key):
        k_embed, k_layers, k_out = jax.random.split(key, 3)
        params = {
            "embed": embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype),
            "layers": tfm.stack_init(k_layers, cfg),
            "final_norm": rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = embed_init(k_out, cfg.vocab_size, cfg.d_model, dtype)
        return params

    def _embed(params, batch, decode_offset=None):
        tokens = batch["tokens"]
        b, s = tokens.shape
        scale = float(cfg.d_model) ** 0.5 if cfg.tie_embeddings else None
        x = embed_lookup(params["embed"], tokens, scale)
        if cfg.frontend == "vision" and "vision_embeds" in batch:
            p = batch["vision_embeds"].shape[1]
            x = jnp.concatenate([batch["vision_embeds"].astype(x.dtype), x[:, p:]], axis=1)
        if cfg.pos_type == "mrope":
            positions = batch["positions"]  # (B, S, 3)
        elif decode_offset is not None:
            positions = _offset_positions(b, s, decode_offset)
        else:
            positions = _positions_default(b, s)
        return x, positions

    def _logits(params, x):
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        return unembed(table, x)

    def forward_logits(params, batch, ctx=None, remat=False):
        x, positions = _embed(params, batch)
        if ctx is not None:
            x = ctx.constrain_act(x)
        x, _, aux = tfm.stack_apply(
            params["layers"], cfg, x, positions, ctx=ctx, remat=remat
        )
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return _logits(params, x), tfm.aux_loss(aux)

    def prefill(params, batch, ctx=None):
        """Inference prefill: full forward, logits only at the last position."""
        x, positions = _embed(params, batch)
        if ctx is not None:
            x = ctx.constrain_act(x)
        x, _, _ = tfm.stack_apply(
            params["layers"], cfg, x, positions, ctx=ctx, remat=False
        )
        x = rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
        return _logits(params, x)

    def loss(params, batch, ctx=None):
        tokens = batch["tokens"]
        b, s = tokens.shape
        x, positions = _embed(params, batch)
        if ctx is not None:
            x = ctx.constrain_act(x)
        x, _, aux = tfm.stack_apply(params["layers"], cfg, x, positions, ctx=ctx)
        aux = tfm.aux_loss(aux)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        targets = tokens[:, 1:]
        p = _vlm_patches(cfg, s) if cfg.frontend == "vision" else 0
        mask = jnp.broadcast_to(
            (jnp.arange(targets.shape[1]) >= p).astype(jnp.float32)[None], targets.shape
        )
        ce, z = xent_auto(table, x[:, :-1], targets, mask, ctx=ctx)
        total = ce + AUX_LOSS_WEIGHT * aux + Z_LOSS_WEIGHT * z
        return total, {"ce": ce, "aux": aux, "z": z}

    def init_decode_state(batch_size: int, max_len: int, per_slot: bool = False,
                          paging=None):
        """``per_slot=True`` gives every batch row its own cache position
        (continuous batching); the default scalar keeps lockstep decode.
        ``paging=(n_blocks, block_size)`` swaps full-attention KV for a
        slot-shared page pool addressed by per-batch block tables (passed
        per step as ``batch["block_tables"]``)."""
        pos_shape = (batch_size,) if per_slot else ()
        return {
            "layers": tfm.stack_init_state(cfg, batch_size, max_len, paging),
            "pos": jnp.zeros(pos_shape, jnp.int32),
        }

    def _decode(params, state, batch, ctx):
        pos = state["pos"]
        x, positions = _embed(params, batch, decode_offset=pos)
        x, new_layers, aux = tfm.stack_apply(
            params["layers"], cfg, x, positions,
            states=state["layers"], cache_pos=pos, ctx=ctx, remat=False,
            block_tables=batch.get("block_tables"),
            token_mask=batch.get("token_mask"),
        )
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = _logits(params, x)
        return (logits, {"layers": new_layers,
                         "pos": pos + batch["tokens"].shape[1]}, aux)

    def decode_step(params, state, batch, ctx=None):
        logits, new_state, _ = _decode(params, state, batch, ctx)
        return logits, new_state

    def decode_step_counted(params, state, batch, ctx=None):
        logits, new_state, aux = _decode(params, state, batch, ctx)
        return logits, new_state, aux["counts"]

    def reset_decode_slots(state, slot_mask):
        """Zero the decode state of every slot where ``slot_mask`` is True
        (per-slot state only)."""
        mask = jnp.asarray(slot_mask, bool)
        layers = tfm.stack_state_map(
            cfg, lambda ax, leaf: _zero_slots(leaf, mask, ax), state["layers"])
        return {"layers": layers, "pos": jnp.where(mask, 0, state["pos"])}

    def merge_decode_state(new_state, old_state, active):
        """Per-slot select: slots where ``active`` is True take the stepped
        state, the rest stay EXACTLY frozen (positions AND layer state —
        recurrent families must not accumulate masked-step updates)."""
        mask = jnp.asarray(active, bool)
        layers = tfm.stack_state_map(
            cfg, functools.partial(_select_slots, mask),
            new_state["layers"], old_state["layers"])
        return {"layers": layers,
                "pos": jnp.where(mask, new_state["pos"], old_state["pos"])}

    return Model(
        cfg=cfg, init=init, loss=loss, decode_step=decode_step,
        init_decode_state=init_decode_state, forward_logits=forward_logits,
        prefill=prefill, vlm_patches=functools.partial(_vlm_patches, cfg),
        reset_decode_slots=reset_decode_slots,
        merge_decode_state=merge_decode_state,
        decode_step_counted=decode_step_counted if cfg.is_moe else None,
    )


# ---------------------------------------------------------------------------
# Encoder-decoder
# ---------------------------------------------------------------------------


def _build_encdec(cfg: ModelConfig) -> Model:
    dtype = dtype_of(cfg.dtype)

    def init(key):
        ks = jax.random.split(key, 4 + cfg.encoder_layers + cfg.n_layers)
        params = {
            "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
            "unembed": embed_init(ks[1], cfg.vocab_size, cfg.d_model, dtype),
            "enc_final_norm": rmsnorm_init(cfg.d_model),
            "final_norm": rmsnorm_init(cfg.d_model),
        }
        for i in range(cfg.encoder_layers):
            params[f"enc_{i}"] = encdec_lib.encdec_layer_init(ks[2 + i], cfg, False, dtype)
        for i in range(cfg.n_layers):
            params[f"dec_{i}"] = encdec_lib.encdec_layer_init(
                ks[2 + cfg.encoder_layers + i], cfg, True, dtype
            )
        return params

    def encode(params, frames, ctx=None):
        x = frames.astype(dtype)
        positions = _positions_default(x.shape[0], x.shape[1])
        for i in range(cfg.encoder_layers):
            f = functools.partial(
                encdec_lib.encoder_layer_apply, cfg=cfg, positions=positions, ctx=ctx
            )
            x = jax.checkpoint(lambda p, h, f=f: f(p, x=h), prevent_cse=False)(
                params[f"enc_{i}"], x
            )
        return rmsnorm(params["enc_final_norm"], x, cfg.norm_eps)

    def loss(params, batch, ctx=None):
        enc_out = encode(params, batch["frames"], ctx)
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_lookup(params["embed"], tokens)
        positions = _positions_default(b, s)
        for i in range(cfg.n_layers):
            lp = params[f"dec_{i}"]
            enc_kv = encdec_lib.cross_kv(lp, cfg, enc_out)

            def body(lp, h, enc_kv, i=i):
                out, _ = encdec_lib.decoder_layer_apply(
                    lp, cfg, h, positions, enc_kv, ctx=ctx
                )
                return out

            x = jax.checkpoint(body, prevent_cse=False)(lp, x, enc_kv)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        ce, z = xent_auto(
            params["unembed"], x[:, :-1], tokens[:, 1:],
            jnp.ones((b, s - 1), jnp.float32), ctx=ctx,
        )
        total = ce + Z_LOSS_WEIGHT * z
        return total, {"ce": ce, "z": z}

    def init_decode_state(batch_size: int, max_len: int, per_slot: bool = False):
        hd = cfg.resolved_head_dim
        enc_len = min(ENCDEC_DECODE_ENC_LEN, max_len)
        pos_shape = (batch_size,) if per_slot else ()
        state: Dict[str, Any] = {"pos": jnp.zeros(pos_shape, jnp.int32)}
        for i in range(cfg.n_layers):
            state[f"dec_{i}"] = {
                "k": jnp.zeros((batch_size, max_len, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((batch_size, max_len, cfg.n_kv_heads, hd), dtype),
            }
            state[f"cross_{i}"] = {
                "k": jnp.zeros((batch_size, enc_len, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((batch_size, enc_len, cfg.n_kv_heads, hd), dtype),
            }
        return state

    def decode_step(params, state, batch, ctx=None):
        pos = state["pos"]
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_lookup(params["embed"], tokens)
        positions = _offset_positions(b, s, pos)
        new_state = {"pos": pos + s}
        for i in range(cfg.n_layers):
            lp = params[f"dec_{i}"]
            enc_kv = (state[f"cross_{i}"]["k"], state[f"cross_{i}"]["v"])
            x, new_cache = encdec_lib.decoder_layer_apply(
                lp, cfg, x, positions, enc_kv,
                self_cache=state[f"dec_{i}"], cache_pos=pos, ctx=ctx,
            )
            new_state[f"dec_{i}"] = new_cache
            new_state[f"cross_{i}"] = state[f"cross_{i}"]
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(params["unembed"], x)
        return logits, new_state

    def prefill(params, batch, ctx=None):
        """Enc-dec prefill: encode frames, run decoder teacher-forced, return
        last-position logits."""
        enc_out = encode(params, batch["frames"], ctx)
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_lookup(params["embed"], tokens)
        positions = _positions_default(b, s)
        for i in range(cfg.n_layers):
            lp = params[f"dec_{i}"]
            enc_kv = encdec_lib.cross_kv(lp, cfg, enc_out)
            x, _ = encdec_lib.decoder_layer_apply(lp, cfg, x, positions, enc_kv, ctx=ctx)
        x = rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
        return unembed(params["unembed"], x)

    def merge_decode_state(new_state, old_state, active):
        """Enc-dec decode state keeps every leaf's slot axis at 0, so one
        uniform per-slot select suffices."""
        mask = jnp.asarray(active, bool)
        return jax.tree.map(
            functools.partial(_select_slots, mask, 0), new_state, old_state)

    return Model(
        cfg=cfg, init=init, loss=loss, decode_step=decode_step,
        init_decode_state=init_decode_state, prefill=prefill,
        merge_decode_state=merge_decode_state,
    )


# ---------------------------------------------------------------------------
# Loss helpers
# ---------------------------------------------------------------------------


def _xent(logits, targets, mask):
    """Cross entropy + z-loss; logits fp32 (B, S, V)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = (logz - gold) * mask
    denom = jnp.maximum(mask.sum() * (targets.shape[0] if mask.shape[0] == 1 else 1), 1.0)
    ce = ce.sum() / denom
    z = (jnp.square(logz) * mask).sum() / denom
    return ce, z


XENT_CHUNK = 1024


def _constrain_logits(logits, ctx):
    """Keep CE logits vocab-sharded over the model axis: the (B, chunk, V)
    buffer is the largest single activation in training."""
    if ctx is None:
        return logits
    from jax.sharding import PartitionSpec as P

    b, _, v = logits.shape
    bspec = ctx.dp_spec if b % ctx.dp == 0 else None
    vspec = ctx.model_axis if v % ctx.tp == 0 else None
    return ctx.constrain(logits, P(bspec, None, vspec))


def _xent_chunked(table, x, targets, mask, chunk: int = XENT_CHUNK, ctx=None):
    """Memory-bounded CE: never materializes the full (B, S, V) logits.

    Scans over sequence chunks; each chunk's logits are produced, consumed
    and (via jax.checkpoint) recomputed in the backward pass, so the peak
    live buffer is (B, chunk, V) instead of (B, S, V) — the difference
    between fitting and not fitting HBM at (4k seq x 256 batch x 150k vocab).

    table: (V, D); x: (B, S, D) final hidden states; targets/mask: (B, S).
    Returns (ce_mean, z_mean).
    """
    b, s, d = x.shape
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nch = x.shape[1] // chunk

    def to_chunks(a):
        return a.reshape((b, nch, chunk) + a.shape[2:]).swapaxes(0, 1)

    xc, tc, mc = to_chunks(x), to_chunks(targets), to_chunks(mask)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, xs):
        ce_sum, z_sum = carry
        xi, ti, mi = xs
        logits = unembed(table, xi)  # (B, chunk, V) fp32 — transient
        logits = _constrain_logits(logits, ctx)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
        ce_sum = ce_sum + ((logz - gold) * mi).sum()
        z_sum = z_sum + (jnp.square(logz) * mi).sum()
        return (ce_sum, z_sum), None

    unroll = nch if (ctx is not None and getattr(ctx, "unroll_scans", False)) else 1
    (ce_sum, z_sum), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(())), (xc, tc, mc), unroll=unroll)
    denom = jnp.maximum(mask.sum(), 1.0)
    return ce_sum / denom, z_sum / denom


def xent_auto(table, x, targets, mask, chunk: int = XENT_CHUNK, ctx=None):
    """Direct CE for short sequences, chunked above (the same fork-join
    size-crossover reasoning as everywhere else in this framework)."""
    if x.shape[1] <= 2 * chunk:
        logits = unembed(table, x)
        logits = _constrain_logits(logits, ctx)
        denom_mask = mask if mask.ndim == 2 else mask[None]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(denom_mask.sum(), 1.0)
        ce = ((logz - gold) * denom_mask).sum() / denom
        z = (jnp.square(logz) * denom_mask).sum() / denom
        return ce, z
    return _xent_chunked(table, x, targets, mask, chunk, ctx)
