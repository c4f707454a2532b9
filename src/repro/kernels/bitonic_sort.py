"""Bitonic sort network Pallas kernel — quicksort's TPU replacement.

The paper's quicksort recursion is control-flow-divergent and cannot map to
the TPU's SIMD VPU (DESIGN.md §2).  The TPU-idiomatic equivalent is a sorting
NETWORK: data-independent compare-exchange stages, all lanes active every
step, O(n log^2 n) work.

Layout: a row of n keys lives as n/128 consecutive rows of 128 lanes, so a
(rows, n) input is the dense (rows * n/128, 128) array the kernel sees and
every key uses a full vreg lane.  The i^j partner exchange then has two
forms:

* partners in the same chunk of ``_CHUNK_ROWS`` rows: two ``pltpu.roll``s of
  the chunk (by ±j along lanes for j < 128, by ±j/128 rows otherwise) and a
  select — all of a k's in-chunk stages run on one load of the chunk;
* partners in different chunks (j >= one chunk): a plain min/max of the two
  chunks, no permutation at all.

Every stage loops over chunks (``fori_loop``), so the program stays the
same size whatever n is.  The kernel sorts each row of a (block_rows, n)
block resident in VMEM; the distributed sample sort (core/sort.py) uses it
as the per-shard local sort, and the grid dimension streams row blocks from
HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hw import V5E

LANES = 128
# Shortest row the kernel takes: 8 rows of 128 lanes, one f32 (8, 128)
# tile, so every row block is tile-aligned (ops.py pads up to it).
MIN_N = 8 * LANES
_CHUNK_ROWS = 64  # rows of 128 lanes one compare-exchange pass holds


def sort_working_set_bytes(block_rows: int, n: int, dtype_bytes: int) -> int:
    """Per-grid-step VMEM the compiler allocates: the input and output
    blocks, each double-buffered by the pipeline, plus the temporaries of
    one chunk pass (the tuner's VMEM-filter estimate, held to
    ``HardwareSpec.vmem_limit_bytes``)."""
    return 4 * block_rows * n * dtype_bytes + 16 * _CHUNK_ROWS * LANES * 4


def _exchange(x, g, *, j: int, k: int, n: int):
    """One in-chunk compare-exchange stage of x (c, 128): partner = i ^ j,
    direction ascending iff (i & k) == 0, where ``g`` holds each key's
    flat index in the block (rows start at multiples of n)."""
    if j < LANES:
        axis, shift, size = 1, j, LANES
    else:
        axis, shift, size = 0, j // LANES, x.shape[0]
    a = pltpu.roll(x, shift, axis)
    b = pltpu.roll(x, size - shift, axis)
    # one of the two rolls brought the partner; which one does not depend
    # on roll's direction convention: roll the index the same way and look
    partner = jnp.where(pltpu.roll(g, shift, axis) == (g ^ j), a, b)
    i = g & (n - 1)
    keep_lo = ((i & j) == 0) == ((i & k) == 0)
    return jnp.where(keep_lo, jnp.minimum(x, partner),
                     jnp.maximum(x, partner))


def _bitonic_kernel(x_ref, o_ref, *, n: int):
    t = o_ref.shape[0]  # rows of 128 lanes in this block
    c = min(t, _CHUNK_ROWS)
    n_chunks = t // c
    span = c * LANES  # keys one chunk holds

    def index(row0):
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (c, LANES), 0)
        return rows * LANES + jax.lax.broadcasted_iota(jnp.int32,
                                                       (c, LANES), 1)

    def in_chunk(src, k: int, j_top: int):
        """Stages j_top, j_top/2, ..., 1 of merge size k, chunk by chunk."""
        def body(ci, carry):
            row0 = pl.multiple_of(ci * c, c)
            x = src[pl.ds(row0, c), :]
            g = index(row0)
            j = j_top
            while j >= 1:
                x = _exchange(x, g, j=j, k=k, n=n)
                j //= 2
            o_ref[pl.ds(row0, c), :] = x
            return carry

        jax.lax.fori_loop(0, n_chunks, body, 0)

    def across_chunks(k: int, j: int):
        """Stage j >= span: chunk pairs jr rows apart, min/max in place."""
        jr = j // LANES
        m = jr // c  # chunks between partners

        def body(p, carry):
            row0 = pl.multiple_of(((p // m) * 2 * m + p % m) * c, c)
            lo = o_ref[pl.ds(row0, c), :]
            hi = o_ref[pl.ds(row0 + jr, c), :]
            asc = (index(row0) & (n - 1) & k) == 0
            mn, mx = jnp.minimum(lo, hi), jnp.maximum(lo, hi)
            o_ref[pl.ds(row0, c), :] = jnp.where(asc, mn, mx)
            o_ref[pl.ds(row0 + jr, c), :] = jnp.where(asc, mx, mn)
            return carry

        jax.lax.fori_loop(0, n_chunks // 2, body, 0)

    k = 2
    while k <= n:
        j = k // 2
        while j >= span:
            across_chunks(k, j)
            j //= 2
        # the first pass reads the input block; later ones work in place
        in_chunk(x_ref if k == 2 else o_ref, k, j)
        k *= 2


def bitonic_sort_pallas(
    x: jax.Array,
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Sort each row of x (rows, n) ascending; n must be a power of 2 and
    at least ``MIN_N``, and keys 32-bit (ops.py pads with +inf, widens and
    strips).  ``block_rows`` comes from the autotuner (kernels/tuning.py),
    which VMEM-filters the candidates."""
    rows, n = x.shape
    assert n & (n - 1) == 0 and n >= MIN_N, f"n={n}: power of 2 >= {MIN_N}"
    assert x.dtype.itemsize == 4, x.dtype
    assert rows % block_rows == 0
    t = block_rows * n // LANES
    out = pl.pallas_call(
        functools.partial(_bitonic_kernel, n=n),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((t, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((t, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows * n // LANES, LANES), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=V5E.vmem_limit_bytes),
        interpret=interpret,
    )(x.reshape(rows * n // LANES, LANES))
    return out.reshape(rows, n)
