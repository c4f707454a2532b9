"""Feed-forward blocks: dense GLU FFNs and Mixture-of-Experts.

MoE has two execution paths:

* ``moe_dense`` — reference/oracle path: every expert computed for every
  token, outputs combined by router weight.  Exact (no token dropping);
  used at smoke scale and as the allclose oracle for the EP path.

* ``moe_ep`` — expert-parallel production path, run under ``shard_map``:
  experts are sharded over the ``model`` mesh axis, tokens are sharded over
  the data axes and replicated across ``model``.  Each model-rank gathers the
  (token, expert) assignments that hit its local experts into a fixed
  ``capacity`` buffer, runs a grouped matmul (``jax.lax.ragged_dot``),
  scatter-adds weighted results, and ``psum``s over ``model``.

  This is a *replication-based* EP dispatch: instead of an all-to-all we pay
  one psum over the model axis.  Rationale (paper lens): the all-to-all's
  inter-core-communication overhead scales with tokens*d_model both ways,
  while the psum costs one output-sized reduce; for top-k >= 6 of the
  assigned MoE archs the psum is cheaper and has no load-imbalance stalls.
  The overhead model (core/overhead.py) makes this trade explicit.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import activation_fn, is_glu


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------


def ffn_init(key, d: int, f: int, activation: str, dtype=jnp.float32):
    from repro.models.common import dense_init

    ks = jax.random.split(key, 3)
    p = {"w_in": dense_init(ks[0], d, (f,), dtype), "w_out": dense_init(ks[1], f, (d,), dtype)}
    if is_glu(activation):
        p["w_gate"] = dense_init(ks[2], d, (f,), dtype)
    return p


def ffn_apply(params, x, activation: str):
    act = activation_fn(activation)
    h = x @ params["w_in"]
    if is_glu(activation):
        h = act(x @ params["w_gate"]) * h
    else:
        h = act(h)
    return h @ params["w_out"]


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def moe_init(key, d: int, f: int, n_experts: int, activation: str, dtype=jnp.float32):
    from repro.models.common import dense_init

    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], d, (n_experts,), jnp.float32),
        "w_in": dense_init(ks[1], d, (n_experts, f), dtype).transpose(1, 0, 2),
        "w_out": dense_init(ks[2], f, (n_experts, d), dtype).transpose(1, 0, 2),
    }
    if is_glu(activation):
        p["w_gate"] = dense_init(ks[3], d, (n_experts, f), dtype).transpose(1, 0, 2)
    return p  # expert tensors: (E, D, F) / (E, F, D)


def _router_topk(logits: jax.Array, k: int):
    """Return (weights, ids): renormalized top-k router weights."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-9)
    return w, ids


def load_balance_loss(logits: jax.Array, ids: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e f_e * p_e."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    p_mean = probs.reshape(-1, n_experts).mean(axis=0)
    f = jnp.zeros(n_experts).at[ids.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    return n_experts * jnp.sum(f * p_mean)


def moe_dense(params, x, *, top_k: int, activation: str):
    """Oracle path: compute every expert for every token."""
    act = activation_fn(activation)
    b, s, d = x.shape
    t = x.reshape(-1, d)
    logits = t.astype(jnp.float32) @ params["router"]
    w, ids = _router_topk(logits, top_k)  # (T,K)
    h = jnp.einsum("td,edf->tef", t, params["w_in"])
    if is_glu(activation):
        h = act(jnp.einsum("td,edf->tef", t, params["w_gate"])) * h
    else:
        h = act(h)
    y_all = jnp.einsum("tef,efd->ted", h, params["w_out"])  # (T,E,D)
    onehot_w = jnp.zeros((t.shape[0], params["router"].shape[1]), y_all.dtype)
    onehot_w = onehot_w.at[jnp.arange(t.shape[0])[:, None], ids].add(w.astype(y_all.dtype))
    y = jnp.einsum("ted,te->td", y_all, onehot_w)
    aux = load_balance_loss(logits, ids, params["router"].shape[1])
    return y.reshape(b, s, d), aux


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _moe_local(t, router, w_in, w_gate, w_out, *, top_k, n_experts, ep_shards,
               capacity, activation, model_axis):
    """Per-device body of the EP path (runs inside shard_map).

    t: (T, D) local tokens (replicated over the model axis);
    w_*: (E_loc, D, F) local expert shards; ``capacity`` is PER EXPERT.

    Dispatch layout: a fixed (E_loc, capacity, D) slot buffer per rank and
    batched einsums.  §Perf iteration 1 (EXPERIMENTS.md): the earlier
    sorted+ragged_dot layout pulled an ~8x dense all-experts einsum into the
    backward pass (ragged_dot has no segment-structured VJP); fixed slots
    make every matmul a plain batched einsum whose VJP is two batched
    einsums — compiled FLOPs drop to capacity_factor x useful.
    """
    act = activation_fn(activation)
    T, d = t.shape
    e_loc = n_experts // ep_shards
    rank = jax.lax.axis_index(model_axis)
    lo = rank * e_loc

    logits = t.astype(jnp.float32) @ router
    w, ids = _router_topk(logits, top_k)  # (T, K)
    flat_ids = ids.reshape(-1)  # (T*K,)
    flat_w = w.reshape(-1).astype(t.dtype)  # keep combine traffic in bf16
    local = (flat_ids >= lo) & (flat_ids < lo + e_loc)
    e_idx = jnp.where(local, flat_ids - lo, e_loc)  # E_loc == overflow bin
    # slot within the expert's capacity buffer, in assignment order
    one_hot = jax.nn.one_hot(e_idx, e_loc + 1, dtype=jnp.int32)  # (T*K, E+1)
    within = jnp.cumsum(one_hot, axis=0)[jnp.arange(e_idx.shape[0]), e_idx] - 1
    keep = local & (within < capacity)
    slot_e = jnp.where(keep, e_idx, e_loc)  # dropped -> overflow row
    slot_c = jnp.where(keep, within, 0)
    tok = jnp.arange(e_idx.shape[0]) // top_k

    # scatter tokens into (E_loc+1, capacity, D); overflow row is garbage
    xs = jnp.zeros((e_loc + 1, capacity, d), t.dtype)
    xs = xs.at[slot_e, slot_c].set(jnp.take(t, tok, axis=0))
    xs = xs[:e_loc]  # (E_loc, C, D)

    h = jnp.einsum("ecd,edf->ecf", xs, w_in)
    if w_gate is not None:
        h = act(jnp.einsum("ecd,edf->ecf", xs, w_gate)) * h
    else:
        h = act(h)
    out = jnp.einsum("ecf,efd->ecd", h.astype(xs.dtype), w_out)  # (E_loc, C, D)

    # combine: gather each kept assignment's row, weight, scatter-add to tokens
    gate_w = jnp.where(keep, flat_w, 0.0).astype(out.dtype)
    safe_e = jnp.where(keep, slot_e, 0)
    rows = out[safe_e, slot_c]  # (T*K, D)
    tok_safe = jnp.where(keep, tok, T)
    y = jnp.zeros((T + 1, d), out.dtype).at[tok_safe].add(rows * gate_w[:, None])[:T]
    return jax.lax.psum(y, model_axis)


def moe_ep(
    params,
    x,
    *,
    top_k: int,
    activation: str,
    mesh,
    data_axes,
    model_axis: str = "model",
    capacity_factor: float = 2.0,
):
    """Expert-parallel MoE over ``mesh``; see module docstring."""

    b, s, d = x.shape
    n_experts = params["router"].shape[1]
    ep = mesh.shape[model_axis]
    dp = 1
    for ax in data_axes:
        dp *= mesh.shape[ax]
    t_local = max(b // dp, 1) * s
    # per-EXPERT slot capacity: cf x the balanced load, MXU-aligned
    raw = int(t_local * top_k / n_experts * capacity_factor)
    capacity = _round_up(max(raw, 8), 128 if raw >= 128 else 8)

    dspec = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    has_gate = "w_gate" in params

    def body(t3, router, w_in, w_gate, w_out):
        t = t3.reshape(-1, d)
        y = _moe_local(
            t, router, w_in, w_gate if has_gate else None, w_out,
            top_k=top_k, n_experts=n_experts, ep_shards=ep, capacity=capacity,
            activation=activation, model_axis=model_axis,
        )
        return y.reshape(t3.shape)

    in_specs = (
        P(dspec, None, None),  # x: tokens sharded over data axes
        P(None, None),  # router replicated
        P(model_axis, None, None),  # experts sharded over model
        P(model_axis, None, None),
        P(model_axis, None, None),
    )
    args = (x, params["router"], params["w_in"],
            params.get("w_gate", params["w_in"]), params["w_out"])
    y = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(dspec, None, None),
        check_vma=False,
    )(*args)
    # aux loss from a (cheap, tokens x E) global router replay
    logits = x.reshape(-1, d).astype(jnp.float32) @ params["router"]
    _, ids = jax.lax.top_k(jax.nn.softmax(logits, -1), top_k)
    aux = load_balance_loss(logits, ids, n_experts)
    return y, aux


def moe_apply(params, x, *, top_k: int, activation: str, ctx=None):
    """Dispatch: EP under a mesh context, dense oracle otherwise.

    The EP collective strategy (replicated-psum vs all-to-all) is a
    CostEngine decision site: the query lands in the engine's ledger at
    trace time.  Only the psum path is implemented, so an all-to-all verdict
    is advisory — the ledger documents the gap instead of hiding it.
    """
    if ctx is not None and ctx.use_ep and ctx.mesh.shape.get(ctx.model_axis, 1) > 1:
        b, s, d = x.shape
        ep = ctx.mesh.shape[ctx.model_axis]
        engine = getattr(ctx, "cost_engine", None)
        if engine is None:
            from repro.runtime import default_runtime

            engine = default_runtime().engine
        dec = engine.decide_moe_dispatch(
            max(b // ctx.dp, 1) * s, d, top_k=top_k, ep_shards=ep,
            dtype_bytes=x.dtype.itemsize)
        if dec.choice != "replicated_psum":
            engine.ledger.record(
                "moe_dispatch", dec.query.as_dict(), "replicated_psum",
                dec.baseline, note=f"engine prefers {dec.choice}; psum is the "
                f"implemented EP path")
        return moe_ep(
            params, x, top_k=top_k, activation=activation, mesh=ctx.mesh,
            data_axes=ctx.data_axes, model_axis=ctx.model_axis,
            capacity_factor=ctx.moe_capacity_factor,
        )
    return moe_dense(params, x, top_k=top_k, activation=activation)
