"""train_step / serve_step factories.

``make_train_step`` builds the jit-able pure step the launcher and the
dry-run both lower: loss -> grad (remat inside the model) -> optional
gradient compression -> AdamW -> new (params, opt_state).  Microbatch
accumulation runs as a lax.scan over microbatches (grad accumulation in
fp32), which also gives XLA a window to overlap the per-microbatch gradient
reduce-scatter with the next microbatch's compute.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.model import Model, mrope_positions
from repro.models.transformer import pages_only
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.compression import compress_gradients, init_compression
from repro.optim.schedule import warmup_cosine


@dataclasses.dataclass(frozen=True)
class TrainLoopConfig:
    optimizer: AdamWConfig = AdamWConfig()
    warmup_steps: int = 100
    total_steps: int = 10000
    microbatches: int = 1  # grad-accumulation factor
    compression: bool = False
    compression_keep_frac: float = 0.1


def init_train_state(model: Model, key, loop: TrainLoopConfig):
    params = model.init(key)
    state = {
        "params": params,
        "opt": adamw_init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if loop.compression:
        state["compress"] = init_compression(params)
    return state


def make_train_step(model: Model, loop: TrainLoopConfig, ctx=None) -> Callable:
    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch, ctx)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step_fn(state, batch):
        params = state["params"]
        if loop.microbatches > 1:
            def micro(carry, mb):
                acc, loss_acc = carry
                (loss, _), grads = grad_fn(params, mb)
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / loop.microbatches,
                    acc, grads,
                )
                return (acc, loss_acc + loss / loop.microbatches), None

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            mbs = jax.tree.map(
                lambda x: x.reshape((loop.microbatches, -1) + x.shape[1:]), batch
            )
            (grads, loss), _ = jax.lax.scan(micro, (zero, 0.0), mbs)
            metrics: Dict[str, Any] = {}
        else:
            (loss, metrics), grads = grad_fn(params, batch)

        new_compress = None
        if loop.compression:
            grads, new_compress, cmetrics = compress_gradients(
                grads, state.get("compress"), keep_frac=loop.compression_keep_frac
            )
            metrics = {**metrics, **cmetrics}

        lr = warmup_cosine(
            state["step"], peak_lr=loop.optimizer.lr,
            warmup_steps=loop.warmup_steps, total_steps=loop.total_steps,
        )
        new_params, new_opt, ometrics = adamw_update(
            params, grads, state["opt"], loop.optimizer, lr=lr
        )
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        if new_compress is not None:
            new_state["compress"] = new_compress
        return new_state, {"loss": loss, "lr": lr, **metrics, **ometrics}

    return step_fn


def make_serve_step(model: Model, ctx=None) -> Callable:
    """One decode step: greedy next token + updated caches.

    When ``batch`` carries an ``active`` (B,) bool mask (continuous
    batching), inactive slots keep their WHOLE decode state frozen
    (``model.merge_decode_state``): positions, caches and recurrent states
    see no trace of the masked dummy step, so free/retired slots can ride
    along in the fixed-shape step without re-jitting.
    """

    def serve_fn(params, decode_state, batch):
        active = batch.get("active")
        model_batch = {k: v for k, v in batch.items() if k != "active"}
        logits, new_state = model.decode_step(params, decode_state, model_batch, ctx)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        if active is not None:
            new_state = model.merge_decode_state(new_state, decode_state, active)
        return next_tok, new_state

    return serve_fn


def make_decode_macro_step(model: Model, horizon: int, *, eos_id: int,
                           pad_id: int, ctx=None) -> Callable:
    """K lockstep greedy decode steps inside ONE device program — the host
    is consulted once per macro-step, not once per token.

    ``lax.scan`` over ``horizon`` single-token decode steps with on-device
    EOS masking and per-slot budget countdown: a slot that emits ``eos_id``
    or exhausts its budget mid-macro-step is masked for the rest of the
    scan (state fully frozen via ``merge_decode_state``, emissions padded
    with ``pad_id``).  Positions are per-slot device state, so mrope
    families need no host-built position tensors.

    Returns ``macro_fn(params, state, tok, active, budget) ->
    (emitted (B, K), new_state)`` where ``tok`` is each slot's last token,
    ``active`` the live-slot mask and ``budget`` the per-slot remaining
    token allowance.  Emission semantics match the per-token host loop
    exactly: an active slot's EOS is emitted, then the slot goes quiet.
    """
    mrope = model.cfg.pos_type == "mrope"
    k_steps = max(int(horizon), 1)

    def macro_fn(params, state, tok, active, budget, block_tables=None):
        def body(carry, _):
            st, tk, act, bud = carry
            feed = jnp.where(act, tk, jnp.int32(pad_id))[:, None]
            batch = {"tokens": feed}
            if block_tables is not None:
                # zero inactive rows' tables so their masked writes land in
                # the null block — a released slot's pages may already belong
                # to someone else, and ``act`` can flip mid-macro-step
                batch["block_tables"] = jnp.where(
                    act[:, None], block_tables, 0)
            if mrope:
                batch["positions"] = mrope_positions(feed.shape[0], 1, st["pos"])
            logits, new_st = model.decode_step(params, st, batch, ctx)
            new_st = model.merge_decode_state(new_st, st, act)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            emit = jnp.where(act, nxt, jnp.int32(pad_id))
            bud = bud - act.astype(jnp.int32)
            new_act = act & (nxt != eos_id) & (bud > 0)
            return (new_st, jnp.where(act, nxt, tk), new_act, bud), emit

        (state, _, _, _), emitted = jax.lax.scan(
            body, (state, tok, active, budget), None, length=k_steps)
        return emitted.T, state  # (B, K)

    return macro_fn


def make_batched_prefill(model: Model, ctx=None) -> Callable:
    """One jitted program that lowers a whole (padded) prompt group into a
    per-slot decode state: ``lax.scan`` over fixed-width chunks through the
    same ``decode_step`` forward the decode path runs, with per-slot
    activity masks (slots not being prefilled stay fully frozen), per-row
    TRUE-length position advancement for ragged groups, and on-device
    capture of each row's first generated token at its own last prompt
    position.  Pad garbage lands only at cache positions beyond each row's
    advance limit, where the causal ``decode_attention`` mask never reads
    it before a real decode write overwrites it.

    ``prefill_fn(params, state, chunks, lengths, starts=None,
    block_tables=None) -> (first_tok (B,), state)`` with ``chunks``
    (n_chunks, B, c) int32 padded prompt chunks and ``lengths`` (B,) true
    prompt lengths (0 marks a slot not prefilled).  Chunk width and count
    are static shapes; the chunk width is the scheduler's ``prefill_chunk``
    decision (1 pins the exact per-token replay for families without a
    chunked decode form).

    With a radix prefix-cache hit, ``chunks``/``lengths`` carry only the
    SUFFIX tokens and ``starts`` (B,) gives each prefilled row's first
    logical position (its prefix hit length): positions, cache writes and
    the length limit all continue from the reused prefix.  ``block_tables``
    routes paged cache writes; rows not being prefilled get their table
    zeroed so masked writes land in the null block.

    ``chunks`` may hold fewer rows ``R`` than there are slots where every
    leaf of ``state`` but ``pos`` is a shared page pool (``pages_only``):
    row ``j`` is then the ``j``-th slot, in ascending order, whose
    ``lengths`` entry is positive, and pad rows take the index
    ``n_slots``.  The program gathers those slots' positions, lengths,
    starts and block tables, runs the same scan on ``R`` rows, scatters
    the new positions back (pad rows dropped) and returns ``first_tok``
    as (R,).
    """
    mrope = model.cfg.pos_type == "mrope"

    def prefill_fn(params, state, chunks, lengths, starts=None,
                   block_tables=None):
        n_chunks, b, c = chunks.shape
        n_slots = lengths.shape[0]
        rows = None
        if b < n_slots:
            if not pages_only(state):
                raise ValueError(
                    f"a prefill of {b} rows over {n_slots} slots needs a "
                    "state whose only per-slot leaf is 'pos'")
            rows = jnp.nonzero(lengths > 0, size=b, fill_value=n_slots)[0]

            def take(v):
                return None if v is None else v.at[rows].get(
                    mode="fill", fill_value=0)

            pool_pos = state["pos"]
            lengths, starts, block_tables = (
                take(lengths), take(starts), take(block_tables))
            state = {**state, "pos": take(pool_pos)}
        if starts is not None:
            state = dict(state)
            state["pos"] = jnp.where(
                lengths > 0, jnp.asarray(starts, jnp.int32), state["pos"])

        def body(carry, xs):
            st, first = carry
            i, tok = xs  # tok: (B, c)
            off = i * c
            valid = jnp.clip(lengths - off, 0, c)  # true tokens this chunk
            act = valid > 0
            batch = {"tokens": tok}
            if block_tables is not None:
                batch["block_tables"] = jnp.where(
                    act[:, None], block_tables, 0)
            if mrope:
                batch["positions"] = mrope_positions(b, c, st["pos"])
            logits, new_st = model.decode_step(params, st, batch, ctx)
            new_st = model.merge_decode_state(new_st, st, act)
            # decode_step advanced active rows by the full chunk width;
            # ragged rows only actually consumed ``valid`` prompt tokens
            new_st = dict(new_st)
            new_st["pos"] = jnp.where(act, st["pos"] + valid, st["pos"])
            done_now = act & (lengths <= off + c)
            last = jnp.take_along_axis(
                logits, jnp.maximum(valid - 1, 0)[:, None, None], axis=1)[:, 0]
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
            return (new_st, jnp.where(done_now, nxt, first)), None

        first0 = jnp.zeros((b,), jnp.int32)
        (state, first), _ = jax.lax.scan(
            body, (state, first0), (jnp.arange(n_chunks), chunks))
        if rows is not None:
            state = {**state, "pos": pool_pos.at[rows].set(
                state["pos"], mode="drop")}
        return first, state

    return prefill_fn
