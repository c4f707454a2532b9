"""Fused causal attention (flash) Pallas kernel — the TPU target for the
XLA chunked-attention path in models/attention.py.

Grid: (batch*heads, q_blocks, kv_blocks); the kv dimension is sequential
("arbitrary") and carries the online-softmax state (m, l, acc) in VMEM
scratch.  Strictly-upper causal blocks are skipped with pl.when — the FLOP
saving the XLA path cannot express (see roofline notes in EXPERIMENTS.md).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hw import V5E

NEG_INF = -1e30


def flash_working_set_bytes(block_q: int, block_kv: int, hd: int,
                            dtype_bytes: int) -> int:
    """Per-grid-step VMEM the compiler allocates: q/k/v/out blocks, each
    double-buffered by the pipeline, the (m, l, acc) fp32 online-softmax
    scratch, and the fp32 (bq, bkv) logits and probabilities (the tuner's
    VMEM-filter estimate, held to ``HardwareSpec.vmem_limit_bytes``)."""
    io = 2 * (block_q * hd * 2 + block_kv * hd * 2) * dtype_bytes
    scratch = (block_q * 128 * 2 + block_q * hd) * 4
    scores = 2 * block_q * block_kv * 4
    return io + scratch + scores


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  kv_steps: int, block_q: int, block_kv: int, causal: bool,
                  sm_scale: float, kv_len: Optional[int]):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    should_run = True
    if causal:
        # skip strictly-upper blocks: q block i covers rows [i*bq, (i+1)*bq)
        should_run = kj * block_kv < (qi + 1) * block_q

    @pl.when(should_run)
    def _run():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)  # (bkv, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bkv)
        if causal or kv_len is not None:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = jnp.ones(s.shape, bool)
            if causal:
                valid &= cols <= rows
            if kv_len is not None:
                # KV padded to the block multiple: padded columns must not
                # contribute exp(0) mass to the softmax denominator
                valid &= cols < kv_len
            s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...][:, :1]  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = l_ref[...][:, :1] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == kv_steps - 1)
    def _done():
        l = l_ref[...][:, :1]
        o_ref[0, ...] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (BH, S, hd)
    k: jax.Array,  # (BH, Skv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_kv: int = 128,
    sm_scale: Optional[float] = None,
    kv_len: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Heads folded into the leading dim (GQA handled by the ops.py wrapper).
    ``block_q``/``block_kv`` come from the autotuner via ops.py unless the
    caller pins them.  ``kv_len`` is the true (pre-padding) KV length: columns
    at or beyond it are masked out of the softmax."""
    bh, s, hd = q.shape
    skv = k.shape[1]
    assert s % block_q == 0 and skv % block_kv == 0, (s, skv, block_q, block_kv)
    sm_scale = sm_scale if sm_scale is not None else hd ** -0.5
    kv_steps = skv // block_kv
    if kv_len is not None and kv_len >= skv:
        kv_len = None  # no padded columns: skip the mask

    kern = functools.partial(
        _flash_kernel, kv_steps=kv_steps, block_q=block_q, block_kv=block_kv,
        causal=causal, sm_scale=sm_scale, kv_len=kv_len,
    )
    return pl.pallas_call(
        kern,
        grid=(bh, s // block_q, kv_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # m
            pltpu.VMEM((block_q, 128), jnp.float32),  # l
            pltpu.VMEM((block_q, hd), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=V5E.vmem_limit_bytes,
        ),
        interpret=interpret,
    )(q, k, v)
