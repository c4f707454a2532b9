"""Pipeline parallelism (GPipe-style) over a mesh axis.

Completes the parallelism matrix (DP/TP/SP/EP in sharding.py; PP here): the
layer stack is split into S contiguous stages laid out on the ``pod`` axis;
microbatches stream through with ``jax.lax.ppermute`` stage-to-stage
transfers; the bubble is the standard (S-1)/(M+S-1) fraction.

Under the paper's lens, PP is the *dependency-pattern* case (DESIGN.md §1):
layer k depends on layer k-1, so available parallelism across stages comes
only from pipelining independent microbatches — exactly the paper's "sub
tasks under consideration are not independent enough" scenario, managed by
choosing M via the overhead model (`pipeline_bubble_fraction`).

The schedule runs inside shard_map; each rank applies ONLY its local stage
parameters (stage params pre-sharded on the leading stage axis).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def pipeline_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def best_microbatch_count(n_stages: int, tokens: int, max_micro: int = 64,
                          bubble_budget: float = 0.1) -> int:
    """Smallest M whose bubble is under budget (fewer, fatter microbatches
    amortize per-dispatch overhead — the paper's launch-overhead row)."""
    for m in range(1, max_micro + 1):
        if pipeline_bubble_fraction(n_stages, m) <= bubble_budget:
            return m
    return max_micro


def gpipe(
    stage_fn: Callable,  # (stage_params, x) -> x
    stage_params,  # pytree; leaves (S, ...) — stage-major, sharded P(axis)
    x,  # (M, mb, ...) microbatched input (replicated across the pipe axis)
    mesh: Mesh,
    axis: str = "pod",
):
    """Run x through S pipeline stages.  Returns (M, mb, ...) outputs.

    Schedule: at tick t (0 <= t < M+S-1), rank r processes microbatch
    t - r if 0 <= t - r < M; activations hop r -> r+1 between ticks.
    """
    n_stages = mesh.shape[axis]
    m = x.shape[0]
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(params_local, xs):
        # params_local leaves: (1, ...) — this rank's stage; xs: (M, mb, ...)
        rank = jax.lax.axis_index(axis)
        p_local = jax.tree.map(lambda a: a[0], params_local)
        mb_shape = xs.shape[1:]
        carry_in = jnp.zeros(mb_shape, xs.dtype)  # activation arriving from prev
        outs = jnp.zeros_like(xs)

        def tick(t, state):
            carry, outs = state
            mb_idx = t - rank
            active = (mb_idx >= 0) & (mb_idx < m)
            # stage 0 reads fresh microbatches; others read the carried activation
            safe_idx = jnp.clip(mb_idx, 0, m - 1)
            inp = jnp.where(rank == 0, xs[safe_idx], carry)
            y = stage_fn(p_local, inp)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # the last stage writes its output; earlier stages forward
            outs = jax.lax.cond(
                active & (rank == n_stages - 1),
                lambda o: o.at[safe_idx].set(y),
                lambda o: o,
                outs,
            )
            carry = jax.lax.ppermute(y, axis, fwd_perm)
            return carry, outs

        _, outs = jax.lax.fori_loop(0, m + n_stages - 1, tick, (carry_in, outs))
        # everyone returns; only the last rank's buffer is non-zero -> psum
        # (cheap relative to the stage compute; avoids a broadcast special-case)
        return jax.lax.psum(outs, axis)

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    other_axes = [a for a in mesh.axis_names if a != axis]
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_params, P(*([None] * x.ndim))),
        out_specs=P(*([None] * x.ndim)),
        check_vma=False,
    )
    return fn(stage_params, x)
