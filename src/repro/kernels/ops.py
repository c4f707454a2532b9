"""Public wrappers around the Pallas kernels: padding to hardware tile
multiples, GQA head folding, and interpret-mode selection (interpret=True on
CPU — the kernel body executes in Python for validation; TPU is the target).

Block/grid shapes are no longer frozen constants: each entry point resolves
them through the empirical autotuner (kernels/tuning.py + core/costs/
autotune.py) unless the caller pins them explicitly.  Resolution happens in
the plain-Python wrapper — outside the jitted implementation — so measured
search (when enabled) never runs under a trace; the jitted inner functions
take the resolved config as static arguments and stay cached per config.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.bitonic_sort import MIN_N, bitonic_sort_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.wkv import wkv_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_dim(x, dim: int, mult: int, value=0.0):
    r = (-x.shape[dim]) % mult
    if r == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[dim] = (0, r)
    return jnp.pad(x, pads, constant_values=value)


def _pad128(n: int) -> int:
    return n + (-n) % 128


# ---------------------------------------------------------------------------
# matmul (with fused epilogue)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_shape", "activation",
                                             "out_dtype", "interpret"))
def _matmul_impl(a, b, bias, *, block_shape, activation, out_dtype, interpret):
    m, _ = a.shape
    _, n = b.shape
    ap = _pad_dim(_pad_dim(a, 0, 128), 1, 128)
    bp = _pad_dim(_pad_dim(b, 0, 128), 1, 128)
    bs = tuple(min(v, d) for v, d in
               zip(block_shape, (ap.shape[0], bp.shape[1], ap.shape[1])))
    biasp = None if bias is None else _pad_dim(bias.reshape(1, -1), 1, 128)
    out = matmul_pallas(ap, bp, bias=biasp, activation=activation,
                        block_shape=bs, out_dtype=out_dtype,
                        interpret=interpret)
    return out[:m, :n]


def matmul(a, b, *, block_shape: Optional[Tuple[int, int, int]] = None,
           bias=None, activation: Optional[str] = None, out_dtype=None,
           interpret: Optional[bool] = None, tuner=None):
    """Blocked-MXU matmul; pads to 128 multiples and strips.

    ``block_shape=None`` resolves through the autotuner (tuned cache entry
    if one exists for this backend, else the analytic prior).  ``bias``
    ((n,)-shaped) and ``activation`` run as a fused epilogue inside the
    kernel on the fp32 accumulator — no separate XLA epilogue pass.
    """
    interpret = _interpret_default() if interpret is None else interpret
    if block_shape is None:
        block_shape = tuning.matmul_block_shape(
            _pad128(a.shape[0]), _pad128(b.shape[1]), _pad128(a.shape[1]),
            a.dtype, interpret=interpret, tuner=tuner)
    out_dtype = jnp.dtype(out_dtype if out_dtype is not None else a.dtype)
    return _matmul_impl(a, b, bias, block_shape=tuple(block_shape),
                        activation=activation, out_dtype=out_dtype,
                        interpret=interpret)


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------


def _sort_npad(n: int) -> int:
    """Power-of-two padded row length the bitonic kernel executes on — the
    single source the tuner's VMEM filter and the kernel padding share."""
    return max(1 << (n - 1).bit_length(), MIN_N)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _sort_impl(x, *, block_rows, interpret):
    rows, n = x.shape
    n_pad = _sort_npad(n)
    floating = jnp.issubdtype(x.dtype, jnp.floating)
    # the kernel sorts 32-bit keys; narrower ones widen exactly and back
    xw = x.astype(jnp.float32 if floating else jnp.int32) \
        if x.dtype.itemsize < 4 else x
    big = jnp.asarray((jnp.finfo if floating else jnp.iinfo)(xw.dtype).max,
                      xw.dtype)
    xp = (jnp.pad(xw, ((0, 0), (0, n_pad - n)), constant_values=big)
          if n_pad != n else xw)
    out = bitonic_sort_pallas(xp, block_rows=block_rows, interpret=interpret)
    return out[:, :n].astype(x.dtype)


def sort(x, *, block_rows: Optional[int] = None,
         interpret: Optional[bool] = None, tuner=None):
    """Ascending sort of a 1D array or each row of a 2D array.

    ``block_rows=None`` resolves through the autotuner, whose VMEM filter
    rejects row blocks whose working set exceeds budget for large n (the old
    static loop could not)."""
    interpret = _interpret_default() if interpret is None else interpret
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None]
    rows, n = x.shape
    if block_rows is None:
        block_rows = tuning.sort_block_rows(
            rows, _sort_npad(n),
            x.dtype if x.dtype.itemsize >= 4 else jnp.float32,
            interpret=interpret, tuner=tuner)
    out = _sort_impl(x, block_rows=int(block_rows), interpret=interpret)
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def _flash_impl(q, k, v, *, causal, block_q, block_kv, interpret):
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * hq, x.shape[1], hd)
    qf, kf, vf = fold(q), fold(k), fold(v)
    bq = min(block_q, s)
    skv = k.shape[1]
    bkv = min(block_kv, skv)
    qf = _pad_dim(qf, 1, bq)
    kf = _pad_dim(kf, 1, bkv)
    vf = _pad_dim(vf, 1, bkv)
    out = flash_attention_pallas(
        qf, kf, vf, causal=causal, block_q=bq, block_kv=bkv, kv_len=skv,
        interpret=interpret
    )[:, :s]
    return out.reshape(b, hq, s, hd).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None,
                    interpret: Optional[bool] = None, tuner=None):
    """(B, S, Hq, hd) GQA attention via the flash kernel.

    KV heads are repeated to Hq and heads folded into batch.  Unpinned
    ``block_q``/``block_kv`` resolve through the autotuner (the prior is the
    previous hardcoded 128/128)."""
    interpret = _interpret_default() if interpret is None else interpret
    if block_q is None or block_kv is None:
        b, s, hq, hd = q.shape
        tq, tkv = tuning.flash_block_shapes(
            b * hq, s, k.shape[1], hd, q.dtype, causal=causal,
            interpret=interpret, tuner=tuner)
        block_q = block_q if block_q is not None else tq
        block_kv = block_kv if block_kv is not None else tkv
    return _flash_impl(q, k, v, causal=causal, block_q=int(block_q),
                       block_kv=int(block_kv), interpret=interpret)


# ---------------------------------------------------------------------------
# WKV
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _wkv_impl(r, k, v, logw, u, *, chunk, interpret):
    b, s, h, n = r.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], n)
    rf, kf, vf = fold(r), fold(k), fold(v)
    wf = fold(logw)
    pad = (-s) % chunk
    if pad:
        # logw pads with 0 (=> decay 1) and k with 0 => padding is a no-op
        rf = _pad_dim(rf, 1, chunk)
        kf = _pad_dim(kf, 1, chunk)
        vf = _pad_dim(vf, 1, chunk)
        wf = _pad_dim(wf, 1, chunk)
    uf = jnp.broadcast_to(u[None], (b, h, n)).reshape(b * h, n)
    out, state = wkv_pallas(rf, kf, vf, wf, uf, chunk=chunk, interpret=interpret)
    out = out[:, :s].reshape(b, h, s, n).transpose(0, 2, 1, 3)
    return out, state.reshape(b, h, n, n)


def wkv(r, k, v, logw, u, *, chunk: Optional[int] = None,
        interpret: Optional[bool] = None, tuner=None):
    """Fused chunked WKV6: (B, S, H, N) inputs, u (H, N).
    Returns (out (B, S, H, N) fp32, state (B, H, N, N) fp32).

    ``chunk=None`` resolves through the autotuner (prior: 64)."""
    interpret = _interpret_default() if interpret is None else interpret
    if chunk is None:
        b, s, h, n = r.shape
        chunk = tuning.wkv_chunk(b * h, s, n, r.dtype, interpret=interpret,
                                 tuner=tuner)
    return _wkv_impl(r, k, v, logw, u, chunk=int(chunk), interpret=interpret)
