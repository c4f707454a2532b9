"""Blocked MXU matmul Pallas kernel, with a fused epilogue.

The paper's Matrix Multiplication domain, TPU-adapted (DESIGN.md §2): instead
of distributing row-column products over cores/threads, the kernel tiles
C = A @ B into MXU-aligned (bm, bn, bk) VMEM blocks over a 3D grid.  The K
grid dimension is "arbitrary" (sequential) — the inter-product additions the
paper identifies as the synchronization overhead become a VMEM fp32
accumulator that never leaves the chip; the parallel dimensions are M and N.

The epilogue (bias add + activation + output-dtype cast) runs inside the
kernel on the fp32 accumulator at the last K step, so C is written to HBM
exactly once in its final form — no separate XLA epilogue pass re-reading
and re-writing the (m, n) output.

Block sizes come from the empirical autotuner (kernels/tuning.py), with
``pick_block_shape`` — the analytic largest-that-fits-VMEM rule — demoted to
the tuner's zero-measurement prior.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hw import V5E

EPILOGUE_ACTIVATIONS = ("relu", "gelu", "silu", "tanh")


def matmul_working_set_bytes(bm: int, bn: int, bk: int, dtype_bytes: int,
                             out_bytes: Optional[int] = None) -> int:
    """Per-grid-step VMEM the compiler allocates: the A, B and output
    blocks, each double-buffered by the pipeline, the fp32 accumulator
    scratch, and the fp32 product of one K step (the tuner's VMEM-filter
    estimate, held to ``HardwareSpec.vmem_limit_bytes``)."""
    out_bytes = out_bytes or dtype_bytes
    return (2 * (bm * bk + bk * bn) * dtype_bytes
            + 2 * bm * bn * out_bytes + 2 * bm * bn * 4)


def pick_block_shape(m: int, n: int, k: int, dtype_bytes: int = 4,
                     vmem_budget: Optional[float] = None) -> Tuple[int, int, int]:
    """Largest MXU-aligned (bm, bn, bk) whose working set fits VMEM.

    This is the analytic heuristic, kept as the autotuner's zero-measurement
    PRIOR (kernels/tuning.py validates it against the divisor/VMEM filters
    and measures alternatives around it)."""
    budget = vmem_budget or V5E.vmem_limit_bytes
    bm = min(512, max(128, m))
    bn = min(512, max(128, n))
    bk = min(2048, max(128, k))
    def fits(bm, bn, bk):
        return matmul_working_set_bytes(bm, bn, bk, dtype_bytes) <= budget
    while not fits(bm, bn, bk) and bk > 128:
        bk //= 2
    while not fits(bm, bn, bk) and (bm > 128 or bn > 128):
        bm = max(128, bm // 2)
        bn = max(128, bn // 2)
    return bm, bn, bk


def _apply_epilogue(acc: jax.Array, activation: Optional[str]) -> jax.Array:
    if activation is None:
        return acc
    if activation == "relu":
        return jax.nn.relu(acc)
    if activation == "gelu":
        return jax.nn.gelu(acc)
    if activation == "silu":
        return jax.nn.silu(acc)
    if activation == "tanh":
        return jnp.tanh(acc)
    raise ValueError(f"unknown epilogue activation: {activation!r}")


def _matmul_kernel(*refs, k_steps: int, activation: Optional[str],
                   has_bias: bool):
    if has_bias:
        a_ref, b_ref, bias_ref, o_ref, acc_ref = refs
    else:
        (a_ref, b_ref, o_ref, acc_ref), bias_ref = refs, None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        acc = acc_ref[...]
        if has_bias:
            acc = acc + bias_ref[...].astype(jnp.float32)  # (1, bn) broadcast
        o_ref[...] = _apply_epilogue(acc, activation).astype(o_ref.dtype)


def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    bias: Optional[jax.Array] = None,  # (1, n), added to the fp32 accumulator
    activation: Optional[str] = None,  # one of EPILOGUE_ACTIVATIONS
    block_shape: Optional[Tuple[int, int, int]] = None,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C[m,n] = epilogue(A[m,k] @ B[k,n] + bias) with explicit VMEM tiling.

    Shapes must be multiples of the block shape (ops.py pads).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    if activation is not None and activation not in EPILOGUE_ACTIVATIONS:
        raise ValueError(f"activation must be one of {EPILOGUE_ACTIVATIONS}")
    bm, bn, bk = block_shape or pick_block_shape(m, n, k, a.dtype.itemsize)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    out_dtype = out_dtype or a.dtype
    k_steps = k // bk

    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    args = [a, b]
    if has_bias:
        assert bias.shape == (1, n), (bias.shape, n)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        args.append(bias)

    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps,
                          activation=activation, has_bias=has_bias),
        grid=(m // bm, n // bn, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=V5E.vmem_limit_bytes,
        ),
        interpret=interpret,
    )(*args)
