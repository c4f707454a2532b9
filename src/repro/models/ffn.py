"""Feed-forward blocks: dense GLU FFNs and Mixture-of-Experts.

One routing function (``route``) serves every MoE path: softmax top-k
(renormalised), or DeepSeek-V3's sigmoid scores with a correction bias that
only chooses.  A layer holds the experts ``[first, first + E)`` of the
router's ``n`` outputs (all of them unless told otherwise) and computes
only their part of the result; shared experts (``params["shared"]``, one
MLP) are added for every token.

MoE has two execution paths:

* ``moe_apply`` off a mesh — every held expert computed for every token,
  weighted by the router (zero where a token did not choose it).  Exact (no
  capacity, no token dropping); it is the serving path and the allclose
  oracle for EP.

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


def moe_init(key, d: int, f: int, n_experts: int, activation: str,
             dtype=jnp.float32, *, n_held: Optional[int] = None,
             shared_ff: int = 0, scoring: str = "softmax"):
    """Router over ``n_experts`` (float32), the ``n_held`` experts this
    layer holds (default all), a correction bias for sigmoid scoring, and
    a shared MLP of width ``shared_ff`` (0: none)."""
    from repro.models.common import dense_init

    e = n_experts if n_held is None else n_held
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, (n_experts,), jnp.float32),
        "w_in": dense_init(ks[1], d, (e, f), dtype).transpose(1, 0, 2),
        "w_out": dense_init(ks[2], f, (e, d), dtype).transpose(1, 0, 2),
    }
    if is_glu(activation):
        p["w_gate"] = dense_init(ks[3], d, (e, f), dtype).transpose(1, 0, 2)
    if scoring == "sigmoid":
        p["router_bias"] = jnp.zeros((n_experts,), jnp.float32)
    if shared_ff:
        p["shared"] = ffn_init(ks[4], d, shared_ff, activation, dtype)
    return p  # expert tensors: (E, D, F) / (E, F, D)


def route(logits: jax.Array, top_k: int, *, scoring: str = "softmax",
          bias: Optional[jax.Array] = None, scale: float = 1.0):
    """Return (weights (T, K) float32, ids (T, K)) of each token's experts.

    ``softmax``: the top-k of the softmax, renormalised to sum to one.
    ``sigmoid`` (DeepSeek-V3 ``noaux_tc`` with one group): scores are
    ``sigmoid(logits)``; the choice is the top-k of score + ``bias`` (the
    correction bias steers the choice only); the weights are the chosen
    scores over their sum, times ``scale`` (``routed_scaling_factor``)."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, top_k)
        w = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-9)
        return w, ids
    if scoring != "sigmoid":
        raise ValueError(f"unknown router scoring {scoring!r}")
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    _, ids = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return w * scale, ids


def held_counts(ids: jax.Array, first: int, n_held: int,
                token_mask: Optional[jax.Array] = None) -> jax.Array:
    """(2,) int32: routed (token, expert) pairs that land on the held
    experts, and held experts with at least one routed token, over the
    tokens where ``token_mask`` (T,) is true (every token if None)."""
    hit = (ids[:, :, None] == first + jnp.arange(n_held)).any(axis=1)
    if token_mask is not None:
        hit &= token_mask[:, None]
    return jnp.stack([hit.sum(), hit.any(axis=0).sum()]).astype(jnp.int32)


def load_balance_loss(logits: jax.Array, ids: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e f_e * p_e."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    p_mean = probs.reshape(-1, n_experts).mean(axis=0)
    f = jnp.zeros(n_experts).at[ids.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    return n_experts * jnp.sum(f * p_mean)


def _routing(params, t, top_k, scoring, scale):
    """The router's logits and route() of tokens ``t`` (T, D)."""
    logits = t.astype(jnp.float32) @ params["router"]
    w, ids = route(logits, top_k, scoring=scoring,
                   bias=params.get("router_bias"), scale=scale)
    return logits, w, ids


def _held_part(params, t, w, ids, first: int, activation: str):
    """Held experts' part of the result for tokens ``t`` (T, D): a dense
    product over the held experts, each token's weight zero where it did
    not choose the expert."""
    act = activation_fn(activation)
    n_held = params["w_in"].shape[0]
    hit = ids[:, :, None] == first + jnp.arange(n_held)  # (T, K, E)
    gate = jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)  # (T, E)
    h = jnp.einsum("td,edf->tef", t, params["w_in"])
    if is_glu(activation):
        h = act(jnp.einsum("td,edf->tef", t, params["w_gate"])) * h
    else:
        h = act(h)
    h = (h * gate[..., None]).astype(t.dtype)
    return jnp.einsum("tef,efd->td", h, params["w_out"])


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _moe_local(t, router, w_in, w_gate, w_out, *, top_k, n_experts, ep_shards,
               capacity, activation, model_axis, bias=None,
               scoring="softmax", scale=1.0, first=0):
    """Per-device body of the EP path (runs inside shard_map).

    t: (T, D) local tokens (replicated over the model axis);
    w_*: (E_loc, D, F) local expert shards of the ``n_experts`` held ones,
    which start at global expert ``first``; ``capacity`` is PER EXPERT.

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
    lo = first + rank * e_loc

    logits = t.astype(jnp.float32) @ router
    w, ids = route(logits, top_k, scoring=scoring, bias=bias,
                   scale=scale)  # (T, K)
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
    scoring: str = "softmax",
    scale: float = 1.0,
    first: int = 0,
):
    """Expert-parallel MoE over ``mesh`` (routed part); see module
    docstring.  Returns (y, aux)."""

    b, s, d = x.shape
    n_experts = params["w_in"].shape[0]  # held: split over the model axis
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

    bias = params.get("router_bias")
    has_bias = bias is not None

    def body(t3, router, rbias, w_in, w_gate, w_out):
        t = t3.reshape(-1, d)
        y = _moe_local(
            t, router, w_in, w_gate if has_gate else None, w_out,
            top_k=top_k, n_experts=n_experts, ep_shards=ep, capacity=capacity,
            activation=activation, model_axis=model_axis,
            bias=rbias if has_bias else None, scoring=scoring, scale=scale,
            first=first,
        )
        return y.reshape(t3.shape)

    in_specs = (
        P(dspec, None, None),  # x: tokens sharded over data axes
        P(None, None),  # router replicated
        P(None),  # correction bias replicated
        P(model_axis, None, None),  # experts sharded over model
        P(model_axis, None, None),
        P(model_axis, None, None),
    )
    args = (x, params["router"],
            bias if has_bias else jnp.zeros((1,), jnp.float32),
            params["w_in"], params.get("w_gate", params["w_in"]),
            params["w_out"])
    y = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(dspec, None, None),
        check_vma=False,
    )(*args)
    # aux loss from a (cheap, tokens x E) global router replay
    logits, _, ids = _routing(params, x.reshape(-1, d), top_k, scoring, scale)
    aux = load_balance_loss(logits, ids, params["router"].shape[1])
    return y, aux


def moe_apply(params, x, *, top_k: int, activation: str, ctx=None,
              scoring: str = "softmax", scale: float = 1.0, first: int = 0,
              token_mask: Optional[jax.Array] = None):
    """The expert layer: EP under a mesh context, the dense product over
    the held experts otherwise, plus the shared MLP.  Returns (y, aux,
    counts), ``counts`` the ``held_counts`` of the tokens where
    ``token_mask`` (B, S) is true.  Scopes ``moe_router``, ``moe_experts``
    and ``moe_shared`` name its parts in the compiled program.  Sigmoid
    scoring balances by its correction bias: aux is 0.

    The EP collective strategy (replicated-psum vs all-to-all) is a
    CostEngine decision site: the query lands in the engine's ledger at
    trace time.  Only the psum path is implemented, so an all-to-all verdict
    is advisory — the ledger documents the gap instead of hiding it.
    """
    b, s, d = x.shape
    t = x.reshape(-1, d)
    with jax.named_scope("moe_router"):
        logits, w, ids = _routing(params, t, top_k, scoring, scale)
        counts = held_counts(
            ids, first, params["w_in"].shape[0],
            None if token_mask is None else token_mask.reshape(-1))
    if ctx is not None and ctx.use_ep and ctx.mesh.shape.get(ctx.model_axis, 1) > 1:
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
        with jax.named_scope("moe_experts"):
            y, aux = moe_ep(
                params, x, top_k=top_k, activation=activation, mesh=ctx.mesh,
                data_axes=ctx.data_axes, model_axis=ctx.model_axis,
                capacity_factor=ctx.moe_capacity_factor, scoring=scoring,
                scale=scale, first=first,
            )
    else:
        aux = load_balance_loss(logits, ids, params["router"].shape[1])
        with jax.named_scope("moe_experts"):
            y = _held_part(params, t, w, ids, first,
                           activation).reshape(b, s, d)
    if scoring == "sigmoid":
        aux = jnp.zeros((), jnp.float32)
    if "shared" in params:
        with jax.named_scope("moe_shared"):
            y = y + ffn_apply(params["shared"], x, activation)
    return y, aux, counts
