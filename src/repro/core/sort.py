"""Distributed sorting — the paper's quicksort domain, TPU-adapted.

Quicksort's data-dependent recursion has no TPU analogue (DESIGN.md §2), so
the paper's *questions* are answered with the TPU-idiomatic equivalent:

  * per-shard sort: XLA sort / bitonic network Pallas kernel (kernels/)
  * global structure: master-slave SAMPLE SORT under shard_map —
      1. each device sorts its local shard,
      2. splitters are selected by a configurable strategy and agreed on by
         all devices (the paper's "pivot placement by master thread"),
      3. elements are binned by splitter and exchanged with one all-to-all,
      4. each device sorts its received bucket -> device i holds the i-th
         contiguous segment of the global order.

Splitter strategies transplant the paper's pivot strategies (Table 3):
  left / right / mean / random  — one candidate per shard, as in the paper
  sampled                       — regular sampling (beyond-paper baseline;
                                  the classic sample-sort splitter)

Bad splitters do not break correctness here (capacity is worst-case safe);
they surface as BUCKET IMBALANCE -> a bigger all-to-all + a longer tail
bucket sort.  ``SortReport.imbalance`` quantifies the paper's observation
that random pivots perform worst.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.costs import CostEngine, OverheadModel, resolve_engine

PIVOT_STRATEGIES = ("left", "right", "mean", "random", "sampled")
_INF = jnp.inf


@dataclasses.dataclass
class SortReport:
    strategy: str
    pivot: str
    n: int
    chips: int
    counts: Optional[np.ndarray] = None  # elements landing on each device

    @property
    def imbalance(self) -> float:
        """max bucket load / ideal load — 1.0 is perfect."""
        if self.counts is None or self.chips == 1:
            return 1.0
        return float(self.counts.max() * self.chips / max(self.n, 1))


def _select_splitters(xs_local, pivot: str, axis: str, chips: int, n_local: int):
    """Agree on (chips-1) ascending splitters; identical on every device."""
    if pivot == "sampled":
        # regular sampling: chips-1 candidates per shard
        idx = (jnp.arange(1, chips) * n_local) // chips
        cand = xs_local[idx]  # (chips-1,)
        allc = jax.lax.all_gather(cand, axis).reshape(-1)  # (chips*(chips-1),)
        allc = jnp.sort(allc)
        take = (jnp.arange(1, chips) * allc.shape[0]) // chips
        return allc[take]
    if pivot == "left":
        cand = xs_local[0]
    elif pivot == "right":
        cand = xs_local[-1]
    elif pivot == "mean":
        cand = xs_local.mean()
    elif pivot == "random":
        rank = jax.lax.axis_index(axis)
        key = jax.random.fold_in(jax.random.PRNGKey(17), rank)
        cand = xs_local[jax.random.randint(key, (), 0, n_local)]
    else:
        raise ValueError(pivot)
    allc = jnp.sort(jax.lax.all_gather(cand, axis))  # (chips,)
    return allc[:-1]  # chips-1 boundaries


def distributed_sort(
    x: jax.Array,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    pivot: str = "sampled",
    model: Optional[OverheadModel] = None,
    force_parallel: bool = False,
    engine: Optional[CostEngine] = None,
    measure: bool = False,
    local_sort: str = "xla",
) -> Tuple[jax.Array, SortReport]:
    """Sort a 1D array with overhead-managed serial/parallel dispatch.

    Returns (sorted array (n,), report).  The parallel path pads internally
    (worst-case-safe capacity) and compacts before returning.  The
    serial/parallel switch consults the CostEngine; ``measure=True``
    additionally times the executed path (synchronously) and attaches the
    wall time to the engine's ledger entry — the predicted-vs-measured hook.
    ``local_sort="pallas"`` runs the single-chip path through the bitonic
    network kernel with an autotuner-resolved (VMEM-filtered) row block
    instead of the XLA sort.
    """
    eng = resolve_engine(engine, model)
    n = x.shape[0]
    chips = int(mesh.shape[axis]) if mesh is not None else 1

    decision = eng.decide_sort(n, chips=chips, dtype_bytes=x.dtype.itemsize)
    parallel = force_parallel or decision.choice != "serial"
    t0 = time.perf_counter() if measure else 0.0
    if not parallel or chips == 1 or mesh is None:
        if local_sort == "pallas":
            from repro.kernels import ops as kernel_ops

            out = kernel_ops.sort(x)
        else:
            out = jnp.sort(x)
        if measure:
            out.block_until_ready()
            eng.record_measured(decision, time.perf_counter() - t0)
        return out, SortReport("serial", pivot, n, chips)

    pad = (-n) % chips
    xp = jnp.pad(x, (0, pad), constant_values=_INF)
    n_local = xp.shape[0] // chips

    def body(xl):
        xl = xl.reshape(-1)  # (n_local,)
        xs = jnp.sort(xl)
        splitters = _select_splitters(xs, pivot, axis, chips, n_local)
        # bucket id for each local element
        bucket = jnp.searchsorted(splitters, xs, side="right")  # (n_local,) in [0, chips)
        # scatter into fixed (chips, n_local) send buffer, +inf padded
        offs = jnp.cumsum(
            jnp.zeros((chips,), jnp.int32).at[bucket].add(1)
        )  # counts per bucket
        # position within bucket via stable ordering: xs sorted => elements of
        # each bucket are contiguous; start offsets:
        starts = jnp.concatenate([jnp.zeros(1, jnp.int32), offs[:-1]])
        within = jnp.arange(n_local, dtype=jnp.int32) - starts[bucket]
        send = jnp.full((chips, n_local), _INF, xs.dtype)
        send = send.at[bucket, within].set(xs)
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0, tiled=True)
        # recv: (chips, n_local) — all elements of MY bucket
        mine = jnp.sort(recv.reshape(-1))  # (chips*n_local,), +inf padded tail
        count = jnp.sum(mine < _INF).astype(jnp.int32)  # inputs must be finite
        return mine[None], count[None]

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=P(axis), out_specs=(P(axis, None), P(axis)),
    )
    segments, counts = fn(xp)  # (chips, chips*n_local), (chips,)
    counts_np = np.asarray(jax.device_get(counts))
    seg_np = np.asarray(jax.device_get(segments))
    out = np.concatenate([seg_np[i, : counts_np[i]] for i in range(chips)])[:n]
    if measure:
        eng.record_measured(decision, time.perf_counter() - t0)
    report = SortReport("sample_sort", pivot, n, chips, counts=counts_np)
    return jnp.asarray(out), report
