"""Fork-join adaptive dispatch (the paper's central mechanism).

``adaptive_matmul`` decides AT TRACE TIME — from static shapes, the active
mesh and the CostEngine (core/costs) — whether a matmul executes serially
(replicated; the paper's single-core path) or parallel under one of the
sharded strategies, and emits exactly that program.  Below the crossover
order, parallel execution *is* overhead (paper Fig. 2): thread-creation ->
kernel launches, inter-core communication -> collectives.

The decision is static (shapes are static in JAX), which matches the paper:
the problem order is known before execution and the fork-join switch happens
at dispatch, not per element.  Every decision lands in the engine's ledger;
the engine's decision cache makes repeated same-shape dispatches (e.g. the
products of ``matmul_chain``) free.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.costs import CostBreakdown, CostEngine, Decision, OverheadModel
from repro.core.costs import resolve_engine


@dataclasses.dataclass(frozen=True)
class DispatchReport:
    chosen: CostBreakdown
    serial: CostBreakdown
    chips: int
    decision: Optional[Decision] = None

    @property
    def predicted_speedup(self) -> float:
        return self.serial.total / self.chosen.total


def _pad_to(x, dim: int, mult: int):
    r = (-x.shape[dim]) % mult
    if r == 0:
        return x, 0
    pads = [(0, 0)] * x.ndim
    pads[dim] = (0, r)
    return jnp.pad(x, pads), r


def decide_matmul(m: int, n: int, k: int, *, chips: int,
                  model: Optional[OverheadModel] = None,
                  engine: Optional[CostEngine] = None,
                  dtype_bytes: int = 2, io_at_master: bool = True) -> DispatchReport:
    """Standalone dispatch defaults to the paper's setting: inputs live at a
    master and the result must be gathered back (io_at_master=True).  Inside
    a model — operands already distributed on a mesh — pass False."""
    eng = resolve_engine(engine, model)
    dec = eng.decide_matmul(m, n, k, chips=chips, dtype_bytes=dtype_bytes,
                            io_at_master=io_at_master)
    serial = dec.baseline if dec.baseline is not None else dec.predicted
    return DispatchReport(chosen=dec.predicted, serial=serial, chips=chips,
                          decision=dec)


def adaptive_matmul(
    a: jax.Array,
    b: jax.Array,
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    model: Optional[OverheadModel] = None,
    return_report: bool = False,
    force_strategy: Optional[str] = None,
    engine: Optional[CostEngine] = None,
    io_at_master: bool = True,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
):
    """C = A @ B with overhead-managed serial/parallel dispatch.

    A: (m, k); B: (k, n).  With no mesh (or a 1-chip axis) this is the serial
    path.  Strategies follow core/costs/model.matmul_cost.
    ``force_strategy`` bypasses the overhead decision (tests/benchmarks).
    ``io_at_master`` defaults to True — the paper's standalone setting, where
    inputs conceptually live at a master and the result is gathered back.
    In-model callers whose operands are ALREADY distributed on the mesh
    (``matmul_chain`` intermediates, layer code) must pass False: for them
    the "input management" overhead row does not exist, which moves the
    serial/parallel crossover all the way down.
    ``use_kernel=True`` executes the single-chip path through the Pallas
    matmul with autotuner-resolved block shapes instead of the XLA dot, so
    the tiling decision is also a managed, measured one.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    chips = int(mesh.shape[axis]) if mesh is not None else 1
    dtype_bytes = a.dtype.itemsize
    report = decide_matmul(m, n, k, chips=chips, model=model, engine=engine,
                           dtype_bytes=dtype_bytes, io_at_master=io_at_master)
    strategy = force_strategy or report.chosen.strategy

    if strategy == "serial" or mesh is None or chips == 1:
        if use_kernel:
            from repro.kernels import ops as kernel_ops

            out = kernel_ops.matmul(a, b, interpret=interpret)
        else:
            out = a @ b
        return (out, report) if return_report else out

    if strategy == "shard_m":
        ap, pad = _pad_to(a, 0, chips)
        fn = jax.shard_map(
            lambda al, bl: al @ bl, mesh=mesh,
            in_specs=(P(axis, None), P(None, None)), out_specs=P(axis, None),
        )
        out = fn(ap, b)[: m]
    elif strategy == "shard_n":
        bp, pad = _pad_to(b, 1, chips)
        fn = jax.shard_map(
            lambda al, bl: al @ bl, mesh=mesh,
            in_specs=(P(None, None), P(None, axis)), out_specs=P(None, axis),
        )
        out = fn(a, bp)[:, : n]
    elif strategy == "shard_k":
        ap, _ = _pad_to(a, 1, chips)
        bp, _ = _pad_to(b, 0, chips)
        fn = jax.shard_map(
            lambda al, bl: jax.lax.psum(al @ bl, axis), mesh=mesh,
            in_specs=(P(None, axis), P(axis, None)), out_specs=P(None, None),
        )
        out = fn(ap, bp)
    else:  # shard_mn — needs two axes; fall back to shard_m on one axis
        ap, _ = _pad_to(a, 0, chips)
        fn = jax.shard_map(
            lambda al, bl: al @ bl, mesh=mesh,
            in_specs=(P(axis, None), P(None, None)), out_specs=P(axis, None),
        )
        out = fn(ap, b)[: m]
    return (out, report) if return_report else out


def fork_join(
    serial_fn: Callable,
    parallel_fn: Callable,
    *,
    parallel_wins: bool,
):
    """The paper's fork-join switch as a generic combinator: the choice is a
    trace-time constant (problem size is static), so the non-chosen branch
    never appears in the compiled program — zero residual overhead."""
    return parallel_fn if parallel_wins else serial_fn


def matmul_chain(matrices, mesh=None, axis="data", model=None, engine=None):
    """Matrix-chain multiplication with per-product adaptive dispatch
    (the paper's 'matrix chain multiplication' case): association order by
    classic DP on FLOP counts, each product dispatched adaptively.  All
    products share one engine, so repeated shapes hit its decision cache."""
    eng = resolve_engine(engine, model)
    dims = [m.shape[0] for m in matrices] + [matrices[-1].shape[1]]
    nmat = len(matrices)
    # dp over chain order
    import numpy as np

    cost = np.zeros((nmat, nmat))
    split = np.zeros((nmat, nmat), dtype=int)
    for span in range(1, nmat):
        for i in range(nmat - span):
            j = i + span
            best, arg = np.inf, i
            for s in range(i, j):
                c = cost[i, s] + cost[s + 1, j] + dims[i] * dims[s + 1] * dims[j + 1]
                if c < best:
                    best, arg = c, s
            cost[i, j], split[i, j] = best, arg

    def mult(i, j):
        if i == j:
            return matrices[i]
        s = split[i, j]
        # chain intermediates are already distributed: io_at_master=False
        return adaptive_matmul(mult(i, s), mult(s + 1, j), mesh, axis,
                               engine=eng, io_at_master=False)

    return mult(0, nmat - 1)
