"""Fused chunked-WKV6 Pallas kernel — the identified §Perf lever for the
rwkv6-3b train cell (EXPERIMENTS.md hillclimb cell 2).

The XLA chunked WKV materializes the (L, L, N) pairwise decay tensor in HBM
every chunk (the cell's dominant memory term).  This kernel keeps the whole
chunk working set — r/k/v/logw blocks, the (L, L) score matrix built one key
position at a time, and the carried (N, N) state — in VMEM: HBM traffic
collapses to the streaming reads of r,k,v,w and the write of o (the
flash-attention treatment, applied to the linear-recurrence chunk).

Grid: (B*H parallel, chunks sequential); the inter-chunk state is VMEM
scratch carried across the sequential grid dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hw import V5E


def wkv_working_set_bytes(chunk: int, n: int, dtype_bytes: int) -> int:
    """Per-grid-step VMEM the compiler allocates: the r/k/v/logw blocks and
    the fp32 out and state blocks, each double-buffered by the pipeline,
    the carried (N, N) state and its update temporaries, the fp32 (L, N)
    copies and per-key-position temporaries, and the (L, L) score matrix
    with its masks (the tuner's VMEM-filter estimate, held to
    ``HardwareSpec.vmem_limit_bytes``)."""
    blocks = 2 * (4 * chunk * n * dtype_bytes + chunk * n * 4 + n * n * 4)
    rows = 10 * chunk * n * 4
    scores = 4 * chunk * chunk * 4
    state = 3 * n * n * 4
    return blocks + rows + scores + state


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sout_ref, state_ref,
                *, chunk: int, n_chunks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)  # (L, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)  # logw <= 0
    u = u_ref[0].astype(jnp.float32)  # (1, N)
    S = state_ref[...]  # (N, N)

    hi = jax.lax.Precision.HIGHEST
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    t_col = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    # logW_t inclusive, summed row by row in sequence order (Mosaic has no
    # cumsum, and a triangular matmul sums in another order)
    cw, run = w, w[0:1]
    for t in range(1, chunk):
        run = run + w[t:t + 1]
        cw = jnp.where(t_col == t, run, cw)
    cwe = cw - w  # exclusive
    # intra-chunk term, one key position s at a time: the pairwise decay
    # exp(cwe[t] - cw[s]) for t > s (all exponents <= 0) never needs the
    # (L, L, N) tensor, whose middle-axis broadcast Mosaic cannot lay out
    # the bonus u on the diagonal
    A = jnp.where(t_idx == s_idx,
                  jnp.sum(r * u * k, axis=-1, keepdims=True), 0.0)
    for s in range(chunk - 1):
        dec = jnp.where(t_col > s, jnp.exp(cwe - cw[s:s + 1]), 0.0)
        score = jnp.sum(r * dec * k[s:s + 1], axis=-1, keepdims=True)
        A = A + jnp.where(s_idx == s, score, 0.0)
    o = jnp.dot(A, v, precision=hi, preferred_element_type=jnp.float32)
    o = o + jnp.dot(r * jnp.exp(cwe), S, precision=hi,
                    preferred_element_type=jnp.float32)
    o_ref[0, ...] = o.astype(o_ref.dtype)

    wl = cw[-1:, :]  # (1, N) logW_L
    k_dec = k * jnp.exp(wl - cw)
    # diag(exp(wl)) @ S scales row n of the state by exp(wl[n])
    n = S.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    decay = eye.astype(jnp.float32) * jnp.exp(wl)
    state_ref[...] = jnp.dot(decay, S, precision=hi,
                             preferred_element_type=jnp.float32) + \
        jax.lax.dot_general(k_dec, v, (((0,), (0,)), ((), ())), precision=hi,
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_chunks - 1)
    def _done():
        sout_ref[0, ...] = state_ref[...]


def wkv_pallas(r, k, v, logw, u, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,logw: (BH, S, N) — heads folded into batch; u: (BH, N).
    Returns (out (BH, S, N) fp32, final state (BH, N, N) fp32).
    S must be a multiple of ``chunk`` (ops.py pads)."""
    bh, s, n = r.shape
    # u goes in as (BH, 1, N): a (1, N) block of it then spans the whole
    # last two dims, which the TPU's (8, 128) block tiling rule requires
    u = u.reshape(bh, 1, n)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    kern = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    blk = pl.BlockSpec((1, chunk, n), lambda b, j: (b, j, 0))
    return pl.pallas_call(
        kern,
        grid=(bh, n_chunks),
        in_specs=[blk, blk, blk, blk,
                  pl.BlockSpec((1, 1, n), lambda b, j: (b, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, n, n), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, n), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=V5E.vmem_limit_bytes,
        ),
        interpret=interpret,
    )(r, k, v, logw, u)
