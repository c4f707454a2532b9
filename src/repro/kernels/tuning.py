"""Per-kernel-family tuning: pruned candidate spaces + tuned-config lookup.

One section per kernel family (matmul, flash attention, bitonic sort, WKV).
Each builds the pruned search space the autotuner measures:

  * every candidate is hardware-aligned (MXU/VPU tile multiples) and must
    exactly divide the padded problem dims where the kernel asserts it,
  * every candidate passes the VMEM budget filter using the working-set
    estimate exported by its kernel module,
  * every candidate carries an analytic cost (the prior) used to order the
    search and as the ledger's "predicted" value.

The prior config is the pre-tuner static heuristic, demoted: ``matmul.
pick_block_shape``, flash's (128, 128), sort's largest-of-(8,4,2,1) row
block, WKV's chunk of 64 — each now validated against the same divisor and
VMEM filters as any other candidate, so an out-of-budget heuristic can no
longer reach a kernel.  With measurement disabled (the default) the tuner
answers with exactly these priors; ``ops.py`` therefore behaves identically
to the pre-tuner code until someone measures.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.costs.autotune import Autotuner, Candidate, TuneResult, TuneSpec
from repro.hw import V5E, HardwareSpec

_GRID_STEP_S = 5e-8  # per-grid-step sequencing overhead (analytic prior only)


def vmem_budget(hw: HardwareSpec = V5E) -> int:
    """The scoped VMEM limit each kernel passes to the compiler: a
    candidate whose working-set estimate exceeds it is never admitted."""
    return hw.vmem_limit_bytes


def _resolve(tuner: Optional[Autotuner]) -> Autotuner:
    """Injected tuner wins; else the default Runtime's tuner."""
    if tuner is not None:
        return tuner
    from repro.runtime import default_runtime

    return default_runtime().tuner


def _resolve_hw(hw: Optional[HardwareSpec]) -> HardwareSpec:
    """Default to the default Runtime's engine spec, so a calibrated
    Runtime (RuntimeConfig.calibrate) also calibrates the tuner's priors +
    VMEM budget."""
    if hw is not None:
        return hw
    from repro.runtime import default_runtime

    return default_runtime().engine.hw


def _peak(hw: HardwareSpec, dtype_bytes: int) -> float:
    return hw.peak_flops_bf16 if dtype_bytes == 2 else hw.peak_flops_f32


# ---------------------------------------------------------------------------
# Matmul
# ---------------------------------------------------------------------------

_MATMUL_BMN = (128, 256, 512)
_MATMUL_BK = (128, 256, 512, 1024, 2048)


def _matmul_prior_s(m: int, n: int, k: int, bm: int, bn: int, bk: int,
                    dtype_bytes: int, hw: HardwareSpec) -> float:
    """Analytic per-config cost: compute/memory roofline where the HBM term
    counts A re-streamed per N-block column and B per M-block row (the
    block-shape dependence ``OverheadModel.matmul_cost`` abstracts away)."""
    compute = 2.0 * m * n * k / (_peak(hw, dtype_bytes) * 0.8)
    hbm_bytes = dtype_bytes * (m * k * (n // bn) + k * n * (m // bm)) + 4.0 * m * n
    memory = hbm_bytes / (hw.hbm_bw * 0.8)
    grid = (m // bm) * (n // bn) * (k // bk)
    return max(compute, memory) + grid * _GRID_STEP_S + hw.kernel_launch_s


def matmul_candidates(m: int, n: int, k: int, dtype_bytes: int,
                      *, hw: HardwareSpec = V5E
                      ) -> Tuple[dict, Tuple[Candidate, ...]]:
    """(prior_config, candidates) for PADDED dims (multiples of 128)."""
    from repro.kernels.matmul import matmul_working_set_bytes, pick_block_shape

    budget = vmem_budget(hw)
    cands = {}

    def admit(bm: int, bn: int, bk: int) -> None:
        if m % bm or n % bn or k % bk:
            return
        ws = matmul_working_set_bytes(bm, bn, bk, dtype_bytes)
        if ws > budget:
            return
        cands[(bm, bn, bk)] = Candidate(
            {"bm": bm, "bn": bn, "bk": bk},
            _matmul_prior_s(m, n, k, bm, bn, bk, dtype_bytes, hw), ws)

    for bm in sorted({min(b, m) for b in _MATMUL_BMN}):
        for bn in sorted({min(b, n) for b in _MATMUL_BMN}):
            for bk in sorted({min(b, k) for b in _MATMUL_BK}):
                admit(bm, bn, bk)
    admit(128, 128, 128)  # dims are 128-multiples: never an empty space

    heuristic = tuple(min(v, d) for v, d in
                      zip(pick_block_shape(m, n, k, dtype_bytes), (m, n, k)))
    admit(*heuristic)
    if heuristic in cands:
        prior = cands[heuristic].config
    else:  # heuristic does not divide the dims (e.g. bm=512 on m=640)
        prior = min(cands.values(), key=lambda c: c.prior_s).config
    return dict(prior), tuple(cands.values())


def _matmul_runner(m, n, k, dtype, interpret, config):
    from repro.kernels.matmul import matmul_pallas

    a = jnp.ones((m, k), dtype)
    b = jnp.ones((k, n), dtype)
    f = jax.jit(functools.partial(
        matmul_pallas, block_shape=(config["bm"], config["bn"], config["bk"]),
        interpret=interpret))
    return lambda: f(a, b).block_until_ready()


def tune_matmul(m: int, n: int, k: int, dtype, *, interpret: bool,
                tuner: Optional[Autotuner] = None,
                hw: Optional[HardwareSpec] = None) -> TuneResult:
    dtype = jnp.dtype(dtype)
    t = _resolve(tuner)
    hw = _resolve_hw(hw)
    key = (f"matmul/{m}x{n}x{k}/{dtype.name}/i{int(bool(interpret))}"
           f"/hw-{hw.name}")
    hit = t.peek(key)
    if hit is not None:
        return hit
    prior, cands = matmul_candidates(m, n, k, dtype.itemsize, hw=hw)
    spec = TuneSpec(
        "matmul", key,
        prior, cands,
        make_runner=functools.partial(_matmul_runner, m, n, k, dtype, interpret),
        query=(("shape", f"{m}x{n}x{k}"), ("dtype", dtype.name)))
    return t.tune(spec)


def matmul_block_shape(m: int, n: int, k: int, dtype, *, interpret: bool,
                       tuner: Optional[Autotuner] = None
                       ) -> Tuple[int, int, int]:
    c = tune_matmul(m, n, k, dtype, interpret=interpret, tuner=tuner).config
    return (c["bm"], c["bn"], c["bk"])


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

_FLASH_BLOCKS = (64, 128, 256, 512)


def _flash_prior_s(bh: int, s: int, skv: int, hd: int, bq: int, bkv: int,
                   dtype_bytes: int, causal: bool, hw: HardwareSpec) -> float:
    sp = -(-s // bq) * bq
    skvp = -(-skv // bkv) * bkv
    kv_frac = 0.55 if causal else 1.0  # causal skips strictly-upper blocks
    compute = 4.0 * bh * sp * skvp * hd * kv_frac / (hw.peak_flops_f32 * 0.8)
    # K/V re-streamed once per q block; Q and O streamed once
    hbm = dtype_bytes * bh * (2 * sp * hd + 2 * skvp * hd * (sp // bq) * kv_frac)
    memory = hbm / (hw.hbm_bw * 0.8)
    grid = bh * (sp // bq) * (skvp // bkv) * kv_frac
    return max(compute, memory) + grid * _GRID_STEP_S + hw.kernel_launch_s


def flash_candidates(bh: int, s: int, skv: int, hd: int, dtype_bytes: int,
                     *, causal: bool, hw: HardwareSpec = V5E
                     ) -> Tuple[dict, Tuple[Candidate, ...]]:
    from repro.kernels.flash_attention import flash_working_set_bytes

    budget = vmem_budget(hw)
    cands = {}

    def admit(bq: int, bkv: int) -> None:
        ws = flash_working_set_bytes(bq, bkv, hd, dtype_bytes)
        if ws > budget:
            return
        cands[(bq, bkv)] = Candidate(
            {"block_q": bq, "block_kv": bkv},
            _flash_prior_s(bh, s, skv, hd, bq, bkv, dtype_bytes, causal, hw), ws)

    for bq in sorted({min(b, s) for b in _FLASH_BLOCKS}):
        for bkv in sorted({min(b, skv) for b in _FLASH_BLOCKS}):
            admit(bq, bkv)
    prior = {"block_q": min(128, s), "block_kv": min(128, skv)}
    admit(prior["block_q"], prior["block_kv"])
    if (prior["block_q"], prior["block_kv"]) not in cands:
        prior = min(cands.values(), key=lambda c: c.prior_s).config
    return dict(prior), tuple(cands.values())


def _flash_runner(bh, s, skv, hd, dtype, causal, interpret, config):
    from repro.kernels.flash_attention import flash_attention_pallas

    bq, bkv = config["block_q"], config["block_kv"]
    sp = -(-s // bq) * bq
    skvp = -(-skv // bkv) * bkv
    q = jnp.ones((bh, sp, hd), dtype)
    k = jnp.ones((bh, skvp, hd), dtype)
    v = jnp.ones((bh, skvp, hd), dtype)
    f = jax.jit(functools.partial(
        flash_attention_pallas, causal=causal, block_q=bq, block_kv=bkv,
        interpret=interpret))
    return lambda: f(q, k, v).block_until_ready()


def tune_flash(bh: int, s: int, skv: int, hd: int, dtype, *, causal: bool,
               interpret: bool, tuner: Optional[Autotuner] = None,
               hw: Optional[HardwareSpec] = None) -> TuneResult:
    dtype = jnp.dtype(dtype)
    t = _resolve(tuner)
    hw = _resolve_hw(hw)
    key = (f"flash/{bh}x{s}x{skv}x{hd}/{dtype.name}"
           f"/c{int(causal)}/i{int(bool(interpret))}/hw-{hw.name}")
    hit = t.peek(key)
    if hit is not None:
        return hit
    prior, cands = flash_candidates(bh, s, skv, hd, dtype.itemsize,
                                    causal=causal, hw=hw)
    spec = TuneSpec(
        "flash_attention", key,
        prior, cands,
        make_runner=functools.partial(
            _flash_runner, bh, s, skv, hd, dtype, causal, interpret),
        query=(("shape", f"{bh}x{s}x{skv}x{hd}"), ("dtype", dtype.name),
               ("causal", causal)))
    return t.tune(spec)


def flash_block_shapes(bh: int, s: int, skv: int, hd: int, dtype, *,
                       causal: bool, interpret: bool,
                       tuner: Optional[Autotuner] = None) -> Tuple[int, int]:
    c = tune_flash(bh, s, skv, hd, dtype, causal=causal, interpret=interpret,
                   tuner=tuner).config
    return (c["block_q"], c["block_kv"])


# ---------------------------------------------------------------------------
# Bitonic sort
# ---------------------------------------------------------------------------

_SORT_ROWS = (1, 2, 4, 8, 16, 32)


def _sort_prior_s(rows: int, n: int, block_rows: int, dtype_bytes: int,
                  hw: HardwareSpec) -> float:
    log2n = max(math.log2(max(n, 2)), 1.0)
    ops_total = rows * n * log2n * (log2n + 1) / 2
    compute = ops_total / hw.peak_flops_f32
    memory = 2.0 * rows * n * dtype_bytes / (hw.hbm_bw * 0.8)
    grid = rows // block_rows
    return max(compute, memory) + grid * _GRID_STEP_S + hw.kernel_launch_s


def sort_candidates(rows: int, n: int, dtype_bytes: int,
                    *, hw: HardwareSpec = V5E
                    ) -> Tuple[dict, Tuple[Candidate, ...]]:
    """``n`` is the padded (power-of-two) row length the kernel sees."""
    from repro.kernels.bitonic_sort import sort_working_set_bytes

    budget = vmem_budget(hw)
    cands = {}
    for r in _SORT_ROWS:
        if r > rows or rows % r:
            continue
        ws = sort_working_set_bytes(r, n, dtype_bytes)
        if ws > budget and r > 1:
            continue  # block_rows=1 always admitted: the kernel's floor
        cands[r] = Candidate({"block_rows": r},
                             _sort_prior_s(rows, n, r, dtype_bytes, hw), ws)
    # the old ops.py heuristic (largest of 8,4,2,1 dividing rows), now subject
    # to the VMEM filter instead of reaching the kernel unchecked
    prior_r = max((r for r in cands if r <= 8), default=min(cands))
    return dict(cands[prior_r].config), tuple(cands.values())


def _sort_runner(rows, n, dtype, interpret, config):
    from repro.kernels.bitonic_sort import bitonic_sort_pallas

    x = jnp.ones((rows, n), dtype)
    f = jax.jit(functools.partial(
        bitonic_sort_pallas, block_rows=config["block_rows"],
        interpret=interpret))
    return lambda: f(x).block_until_ready()


def tune_sort(rows: int, n: int, dtype, *, interpret: bool,
              tuner: Optional[Autotuner] = None,
              hw: Optional[HardwareSpec] = None) -> TuneResult:
    dtype = jnp.dtype(dtype)
    t = _resolve(tuner)
    hw = _resolve_hw(hw)
    key = f"sort/{rows}x{n}/{dtype.name}/i{int(bool(interpret))}/hw-{hw.name}"
    hit = t.peek(key)
    if hit is not None:
        return hit
    prior, cands = sort_candidates(rows, n, dtype.itemsize, hw=hw)
    spec = TuneSpec(
        "sort", key,
        prior, cands,
        make_runner=functools.partial(_sort_runner, rows, n, dtype, interpret),
        query=(("shape", f"{rows}x{n}"), ("dtype", dtype.name)))
    return t.tune(spec)


def sort_block_rows(rows: int, n: int, dtype, *, interpret: bool,
                    tuner: Optional[Autotuner] = None) -> int:
    return tune_sort(rows, n, dtype, interpret=interpret,
                     tuner=tuner).config["block_rows"]


# ---------------------------------------------------------------------------
# WKV (chunked linear recurrence)
# ---------------------------------------------------------------------------

_WKV_CHUNKS = (16, 32, 64, 128, 256)


def _wkv_prior_s(bh: int, s: int, n: int, chunk: int, dtype_bytes: int,
                 hw: HardwareSpec) -> float:
    """The scan-chunk analytic model (costs/model.scan_chunk_cost) with the
    head axes folded into the batch dim, per-kernel-grid flavored."""
    n_chunks = -(-s // chunk)
    flops = bh * (2 * chunk * chunk * n * 2 + 2 * chunk * n * n * 2)
    per_chunk = flops / (hw.peak_flops_f32 * 0.8)
    pairwise = bh * chunk * chunk * n * 4
    per_chunk = max(per_chunk, pairwise / (hw.hbm_bw * 0.8))
    return n_chunks * (per_chunk + _GRID_STEP_S * bh) + hw.kernel_launch_s


def wkv_candidates(bh: int, s: int, n: int, dtype_bytes: int,
                   *, hw: HardwareSpec = V5E
                   ) -> Tuple[dict, Tuple[Candidate, ...]]:
    from repro.kernels.wkv import wkv_working_set_bytes

    budget = vmem_budget(hw)
    s_cap = max(64, -(-s // 16) * 16)  # chunks beyond the padded seq waste VMEM
    cands = {}
    for c in _WKV_CHUNKS:
        if c > s_cap:
            continue
        ws = wkv_working_set_bytes(c, n, dtype_bytes)
        if ws > budget and len(cands) > 0:
            continue
        cands[c] = Candidate({"chunk": c},
                             _wkv_prior_s(bh, s, n, c, dtype_bytes, hw), ws)
    prior_c = 64 if 64 in cands else min(cands, key=lambda c: cands[c].prior_s)
    return dict(cands[prior_c].config), tuple(cands.values())


def _wkv_runner(bh, s, n, dtype, interpret, config):
    from repro.kernels.wkv import wkv_pallas

    chunk = config["chunk"]
    sp = -(-s // chunk) * chunk
    r = jnp.ones((bh, sp, n), dtype)
    k = jnp.ones((bh, sp, n), dtype)
    v = jnp.ones((bh, sp, n), dtype)
    logw = jnp.full((bh, sp, n), -0.5, dtype)
    u = jnp.ones((bh, n), dtype)
    f = jax.jit(functools.partial(wkv_pallas, chunk=chunk, interpret=interpret))

    def run():
        out, state = f(r, k, v, logw, u)
        out.block_until_ready()
        return state

    return run


def tune_wkv(bh: int, s: int, n: int, dtype, *, interpret: bool,
             tuner: Optional[Autotuner] = None,
             hw: Optional[HardwareSpec] = None) -> TuneResult:
    dtype = jnp.dtype(dtype)
    t = _resolve(tuner)
    hw = _resolve_hw(hw)
    key = f"wkv/{bh}x{s}x{n}/{dtype.name}/i{int(bool(interpret))}/hw-{hw.name}"
    hit = t.peek(key)
    if hit is not None:
        return hit
    prior, cands = wkv_candidates(bh, s, n, dtype.itemsize, hw=hw)
    spec = TuneSpec(
        "wkv", key,
        prior, cands,
        make_runner=functools.partial(_wkv_runner, bh, s, n, dtype, interpret),
        query=(("shape", f"{bh}x{s}x{n}"), ("dtype", dtype.name)))
    return t.tune(spec)


def wkv_chunk(bh: int, s: int, n: int, dtype, *, interpret: bool,
              tuner: Optional[Autotuner] = None) -> int:
    return tune_wkv(bh, s, n, dtype, interpret=interpret,
                    tuner=tuner).config["chunk"]
