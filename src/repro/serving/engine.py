"""Serving engines: static-batch baseline + slot-pooled continuous batching.

``ServeEngine`` is the fixed-batch baseline: one prompt matrix in, lockstep
greedy decode out, with EOS masking and deterministic padding.  It is the
token-for-token correctness anchor for the continuous engine.  Its prompt
priming is ONE jitted batched prefill call (``make_batched_prefill``), not
the old per-token replay — the anchor pays P fewer host round trips per
batch and stays honest about overhead.

``ContinuousServeEngine`` is the real serve stack (DESIGN.md §5), built so
the host is consulted once per MACRO-STEP, not once per token:

  * decode runs as jitted K-token macro-steps (``make_decode_macro_step``:
    ``lax.scan`` over K single-token steps with on-device EOS masking,
    per-slot budget countdown and per-slot position advancement); the
    horizon K is a ``CostQuery(kind=serve_macro)`` decision trading the
    once-per-macro-step host sync against lockstep steps wasted when a
    slot finishes mid-macro-step;
  * admitted requests prefill as a GROUP directly into the pooled state
    (one jitted scan-over-chunks program per group — no single-slot state
    + insert copy, no per-chunk host round trips);
  * the pooled decode state is DONATED through prefill/macro-step/reset,
    so cache updates are in-place, never copy-on-write;
  * every host synchronization and device dispatch is counted and lands in
    ``ServeReport.as_dict()`` — the overhead reduction is machine-readable.

Every admission / prefill-chunk / macro-horizon choice is a CostEngine
``CostQuery -> Decision`` ledgered with the measured wall time attached.
"""

from __future__ import annotations

import dataclasses
import re
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.costs.engine import CostEngine
from repro.models.model import Model, mrope_positions
from repro.models.transformer import pages_only
from repro.serving.faults import FaultInjector, StepFailed, guarded_call
from repro.serving.scheduler import (
    Request,
    RequestState,
    ServeScheduler,
    supports_chunked_prefill,
    validate_request,
)
from repro.serving.frontend.stream import StreamBroken, TokenStream
from repro.serving.paging import default_kv_blocks
from repro.serving.slots import SlotPool
from repro.serving.spans import Span, SpanRecorder, maybe_span
from repro.training.step import (
    make_batched_prefill,
    make_decode_macro_step,
    make_serve_step,
)


# post-SPMD HLO collective ops (GSPMD inserts these during compilation, so
# the count must come from compiled HLO, not the lowered StableHLO).  Matches
# only the opcode position — "all-reduce(" — not instruction names
# ("%all-reduce.1") or operand references; async pairs count once via the
# -start half
_COLLECTIVE_RE = re.compile(
    r"(?<!%)\b(?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


# the (dispatch, sync) span names of each jitted step
_ADMIT_STEP_SPANS = ("serve/admit/dispatch", "serve/admit/sync")
_MACRO_STEP_SPANS = ("serve/macro/dispatch", "serve/macro/sync")


def emitted_count(out: np.ndarray, eos_id: int) -> int:
    """Tokens actually generated in a (B, T) output matrix: everything up
    to and including the first EOS per row (the rest is deterministic
    padding).  Vectorized — no per-row Python loop."""
    out = np.asarray(out)
    if out.size == 0:
        return 0
    hits = out == eos_id
    per_row = np.where(hits.any(axis=1), hits.argmax(axis=1) + 1, out.shape[1])
    return int(per_row.sum())


def _check_fits(prompt_len: int, max_new: int, max_len: int, who: str) -> None:
    """One explicit slot-capacity rule instead of the old silent ``+ 8``
    slack: a request must fit its slot end to end."""
    need = prompt_len + max_new
    if need > max_len:
        raise ValueError(
            f"{who}: prompt_len {prompt_len} + max_new_tokens {max_new} "
            f"= {need} exceeds max_len {max_len}; raise max_len (it must "
            f"cover prompt + generated tokens) or shorten the request")


def _prefill_chunks(prompts: np.ndarray, chunk: int) -> np.ndarray:
    """(B, L) padded prompts -> (n_chunks, B, chunk) for the jitted batched
    prefill (L padded up to a chunk multiple so every chunk is full-width —
    one compiled program per (chunk, n_chunks), not per ragged remainder)."""
    b, length = prompts.shape
    pad = (-length) % chunk
    if pad:
        prompts = np.pad(prompts, ((0, 0), (0, pad)))
    n_chunks = prompts.shape[1] // chunk
    return np.ascontiguousarray(
        prompts.reshape(b, n_chunks, chunk).transpose(1, 0, 2))


# ---------------------------------------------------------------------------
# Static-batch baseline
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServeEngine:
    """Fixed-batch greedy decoding with EOS masking.

    All sequences decode in lockstep; a sequence that emits ``eos_id``
    keeps its EOS in the output, pads the rest with ``pad_id`` and is fed
    padding (masked) until the whole batch finishes — the loop stops early
    once every slot is done."""

    model: Model
    params: object
    max_len: int = 256
    eos_id: int = 0
    pad_id: Optional[int] = None

    def __post_init__(self):
        self._step = jax.jit(make_serve_step(self.model), donate_argnums=(1,))
        self._prefill = jax.jit(make_batched_prefill(self.model),
                                donate_argnums=(1,))
        if self.pad_id is None:
            self.pad_id = self.eos_id

    def generate(self, prompts: np.ndarray, max_new_tokens: int = 32) -> np.ndarray:
        """prompts: (B, P) int32.  Returns (B, max_new_tokens): generated
        tokens up to and including EOS, deterministically padded after it."""
        b, p = prompts.shape
        _check_fits(p, max_new_tokens, self.max_len, "ServeEngine.generate")
        state = self.model.init_decode_state(b, self.max_len, per_slot=True)
        mrope = self.model.cfg.pos_type == "mrope"
        # prime the caches with ONE batched prefill program (chunk-1 scan
        # replay for families without an exact chunked decode form)
        chunk = p if supports_chunked_prefill(self.model.cfg) else 1
        tok, state, *_ = self._prefill(
            self.params, state,
            jnp.asarray(_prefill_chunks(np.asarray(prompts, np.int32), chunk)),
            jnp.asarray(np.full((b,), p, np.int32)))
        out = np.full((b, max_new_tokens), self.pad_id, np.int32)
        done = np.zeros((b,), bool)
        cur = np.asarray(tok)
        for i in range(max_new_tokens):
            out[:, i] = np.where(done, self.pad_id, cur)
            done |= cur == self.eos_id
            if done.all() or i == max_new_tokens - 1:
                break
            feed = np.where(done, self.pad_id, cur).astype(np.int32)
            batch = {"tokens": jnp.asarray(feed[:, None])}
            if mrope:
                batch["positions"] = mrope_positions(b, 1, p + i)
            nxt, state = self._step(self.params, state, batch)
            cur = np.asarray(nxt)
        return out


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServeReport:
    """Per-request latencies + aggregate throughput for one trace run,
    plus the trace's host-synchronization / device-dispatch counts (the
    overhead the macro-step hot path exists to amortize)."""

    requests: List[Request]
    wall_s: float
    pad_id: int
    host_syncs: int = 0
    device_dispatches: int = 0
    # mesh placement + per-trace collective traffic (counted from compiled
    # HLO per program shape × dispatches); mesh_shape is None off-mesh
    mesh_shape: Optional[Dict[str, int]] = None
    device_count: int = 1
    collective_ops: int = 0
    # failure-path accounting (all zero on an unperturbed trace)
    step_retries: int = 0
    watchdog_fires: int = 0
    # paged-KV memory accounting.  Every number comes from HOST MIRRORS
    # the engine already maintains — reading them costs no device sync.
    live_tokens: int = 0        # peak sum of per-slot cache positions
    reserved_blocks: int = 0    # peak BlockPool pages in use (slots + trie)
    prefix_hit_tokens: int = 0  # prompt tokens served from the radix cache
    prefilled_tokens: int = 0   # prompt tokens actually prefilled
    cow_count: int = 0          # copy-on-write page duplications
    # prefill and decode occupancy: what the dispatched programs computed
    # (every prefill row, padded to the group's chunk-rounded length; every
    # slot for each of K steps), against which prefilled_tokens and
    # generated_tokens are the useful part
    prefill_padded_tokens: int = 0  # rows x padded length, per prefill
    decode_slot_steps: int = 0      # n_slots x K, per macro-step
    narrow_admissions: int = 0      # prefills on fewer rows than n_slots
    # expert layers (models with experts; zero otherwise), summed over
    # layers and steps of every prefill and macro-step: routed (token,
    # expert) pairs of real tokens that land on the held experts, and held
    # experts that at least one real token reached
    held_assignments: int = 0
    experts_hit: int = 0
    # streaming / front-end accounting (all zero without a token stream /
    # multi-process front end).  IPC fields are filled by Runtime.serve
    # from the ServingFrontend's counters — the engine never sees a queue.
    streamed_tokens: int = 0    # tokens published to the attached stream
    stream_events: int = 0      # publish calls (bursts) on the stream
    ipc_messages: int = 0       # frontend queue messages (intake + emission)
    ipc_bytes: int = 0          # pickled payload bytes through those queues
    frontend_workers: int = 0   # intake worker processes (0 = in-process)
    frontend_respawns: int = 0  # crashed workers auto-respawned mid-trace

    def state_counts(self) -> Dict[str, int]:
        """How many requests ended in each lifecycle state."""
        counts: Dict[str, int] = {}
        for r in self.requests:
            counts[r.state.value] = counts.get(r.state.value, 0) + 1
        return counts

    @property
    def all_terminal(self) -> bool:
        """The drain invariant: a finished run leaves NO request in a
        non-terminal state, whatever faults fired."""
        return all(r.state.terminal for r in self.requests)

    @property
    def preemptions(self) -> int:
        return sum(r.preemptions for r in self.requests)

    def output(self, rid: str, max_new_tokens: Optional[int] = None) -> np.ndarray:
        req = next(r for r in self.requests if r.rid == rid)
        n = max_new_tokens if max_new_tokens is not None else req.max_new_tokens
        out = np.full((n,), self.pad_id, np.int32)
        out[: len(req.tokens)] = req.tokens
        return out

    def outputs(self) -> Dict[str, np.ndarray]:
        return {r.rid: self.output(r.rid) for r in self.requests}

    @property
    def generated_tokens(self) -> int:
        return sum(len(r.tokens) for r in self.requests)

    @property
    def tok_per_s(self) -> float:
        return self.generated_tokens / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def host_syncs_per_token(self) -> float:
        return self.host_syncs / max(self.generated_tokens, 1)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prompt tokens served from the radix prefix cache
        instead of being prefilled."""
        total = self.prefix_hit_tokens + self.prefilled_tokens
        return self.prefix_hit_tokens / total if total else 0.0

    def latency_percentiles(self, qs=(50, 95)) -> Dict[str, float]:
        lats = [r.latency_s for r in self.requests if r.latency_s is not None]
        if not lats:
            return {f"p{q}": float("nan") for q in qs}
        return {f"p{q}": float(np.percentile(lats, q)) for q in qs}

    def ttft_percentiles(self, qs=(50, 95, 99)) -> Dict[str, float]:
        """Time-to-first-token percentiles.  ``ttft_s`` is stamped when the
        first token leaves the device boundary the engine already
        synchronized on; with a stream attached that is exactly the moment
        the token is published to the client."""
        ttfts = [r.ttft_s for r in self.requests if r.ttft_s is not None]
        if not ttfts:
            return {f"ttft_p{q}": float("nan") for q in qs}
        return {f"ttft_p{q}": float(np.percentile(ttfts, q)) for q in qs}

    def as_dict(self) -> dict:
        return {
            "wall_s": self.wall_s,
            "generated_tokens": self.generated_tokens,
            "tok_per_s": self.tok_per_s,
            "host_syncs": self.host_syncs,
            "device_dispatches": self.device_dispatches,
            "host_syncs_per_token": self.host_syncs_per_token,
            "mesh_shape": self.mesh_shape,
            "device_count": self.device_count,
            "collective_ops": self.collective_ops,
            "states": self.state_counts(),
            "all_terminal": self.all_terminal,
            "step_retries": self.step_retries,
            "watchdog_fires": self.watchdog_fires,
            "preemptions": self.preemptions,
            "live_tokens": self.live_tokens,
            "reserved_blocks": self.reserved_blocks,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefilled_tokens": self.prefilled_tokens,
            "prefill_padded_tokens": self.prefill_padded_tokens,
            "decode_slot_steps": self.decode_slot_steps,
            "narrow_admissions": self.narrow_admissions,
            "held_assignments": self.held_assignments,
            "experts_hit": self.experts_hit,
            "prefix_hit_rate": self.prefix_hit_rate,
            "cow_count": self.cow_count,
            "streamed_tokens": self.streamed_tokens,
            "stream_events": self.stream_events,
            "ipc_messages": self.ipc_messages,
            "ipc_bytes": self.ipc_bytes,
            "frontend_workers": self.frontend_workers,
            "frontend_respawns": self.frontend_respawns,
            **self.latency_percentiles(),
            **self.ttft_percentiles(),
            "requests": [
                {
                    "rid": r.rid,
                    "prompt_len": r.prompt_len,
                    "generated": len(r.tokens),
                    "arrival_s": r.arrival_s,
                    "queue_wait_s": r.queue_wait_s,
                    "ttft_s": r.ttft_s,
                    "latency_s": r.latency_s,
                    "state": r.state.value,
                    "reason": r.reason,
                    "preemptions": r.preemptions,
                    "retries": r.retries,
                }
                for r in self.requests
            ],
        }


class ContinuousServeEngine:
    """Slot-pooled continuous batching with CostEngine-driven scheduling.

    Token-for-token equivalent to ``ServeEngine`` on any fixed request set:
    same greedy decode over the same caches, just with slots admitted,
    retired and refilled independently instead of in lockstep — and with
    the decode loop running as jitted multi-token macro-steps
    (``macro_step="auto"`` lets the scheduler pick K; an int pins it;
    K=1 degenerates exactly to the per-token loop).

    Full-attention KV lives in one paged pool (DESIGN.md §5):
    ``block_size``-token pages shared by every slot through per-slot block
    tables (``kv_blocks`` overrides the can-never-run-out default), with a
    radix prefix trie that ``prefix_cache`` arms.  Window rings and
    recurrent states stay per slot.  Encoder-decoder models are refused:
    their decode state has no paged layout.

    Passing ``mesh`` puts the engine on a device mesh.  Whether serve state
    actually SHARDS over the mesh's model axis or stays replicated is the
    eighth CostEngine decision site (``CostQuery(kind=serve_shard)``;
    ``shard_params`` forces it): on a shard verdict, params take the
    training-layer logical specs, pooled KV caches shard over kv heads, and
    the jitted prefill/macro-step programs pin their outputs to the same
    layout so donation stays in-place across shards.  A replicate verdict
    executes exactly the single-device path (the decision is still
    ledgered and the mesh still reported).

    Passing ``tracer`` (a ``SpanRecorder``) records a host span at each
    layer boundary of ``run`` and ``warmup`` (``repro.serving.spans``);
    without one the loop records nothing."""

    def __init__(self, model: Model, params, *, n_slots: int = 4,
                 max_len: int = 256, eos_id: int = 0,
                 pad_id: Optional[int] = None,
                 cost_engine: Optional[CostEngine] = None,
                 prefill_chunk: Union[str, int] = "auto",
                 macro_step: Union[str, int] = "auto",
                 mesh=None, shard_params: str = "auto",
                 queue_limit: Optional[int] = None,
                 watchdog_s: Optional[float] = None,
                 max_retries: int = 2, retry_backoff_s: float = 0.01,
                 injector: Optional[FaultInjector] = None,
                 block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 stream: Optional[TokenStream] = None,
                 tracer: Optional[SpanRecorder] = None):
        self.model = model
        self.tracer = tracer
        self.params = params
        self.max_len = max_len
        self.eos_id = eos_id
        self.pad_id = eos_id if pad_id is None else pad_id
        # --- robustness knobs (all default OFF: the unperturbed hot path
        # stays thread-free with zero extra queries or host syncs) ---
        if queue_limit is not None and queue_limit < 1:
            raise ValueError(f"queue_limit must be >= 1, got {queue_limit}")
        if watchdog_s is not None and watchdog_s <= 0:
            raise ValueError(f"watchdog_s must be > 0, got {watchdog_s}")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        self.queue_limit = queue_limit
        self.watchdog_s = watchdog_s
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.injector = injector
        self.step_retries = 0  # engine-lifetime; reports carry deltas
        self.watchdog_fires = 0
        if prefill_chunk != "auto":
            prefill_chunk = int(prefill_chunk)
        self.prefill_chunk = prefill_chunk
        if macro_step != "auto":
            macro_step = max(int(macro_step), 1)
        self.macro_step = macro_step
        # --- paged KV pool + radix prefix cache (DESIGN.md §5) ---
        if model.cfg.is_encdec:
            raise ValueError(
                "the continuous engine serves decoder-only models (enc-dec "
                "decode state has no paged layout)")
        self.block_size = int(block_size)
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if kv_blocks is None:
            kv_blocks = default_kv_blocks(n_slots, max_len, self.block_size)
        self.kv_blocks = int(kv_blocks)
        # prefix reuse skips prefilling matched prompt tokens, which is
        # only sound when EVERY layer's prompt state lives in the paged
        # pool: window ring buffers and recurrent states stay per-slot
        # dense, so families with local/rglru/rwkv layers keep the paged
        # memory layout but always prefill in full.  'force' pins the
        # serve_prefix verdict to use_prefix (still priced + ledgered) —
        # toy-scale models where a CoW dispatch outweighs the skipped
        # prefill would otherwise never exercise reuse.
        if prefix_cache not in (True, False, "auto", "force"):
            raise ValueError(
                f"prefix_cache must be True/False/'auto'/'force', "
                f"got {prefix_cache!r}")
        all_attn = all(model.cfg.block_kind(i) == "attn"
                       for i in range(model.cfg.n_layers))
        self.prefix_cache = prefix_cache is not False and all_attn
        self._prefix_override = ("use_prefix" if prefix_cache == "force"
                                 else None)
        # --- incremental token stream (frontend or in-process).  The
        # engine publishes at boundaries it ALREADY synchronized on
        # (prefill return, macro-step return) — attaching a stream adds
        # zero device syncs.  Assignable after construction so warmup can
        # run stream-free (Runtime attaches it post-warmup).
        self.stream = stream
        self._stream_dead = False
        self._stream_reason = ""
        # --- cooperative graceful shutdown (DESIGN.md §8).  Either hook
        # stops INTAKE only: queued/unarrived requests go terminal
        # (REJECTED reason="shutdown"), active slots decode to completion,
        # and run() still returns its report — the drain invariant holds.
        # ``stop_event`` takes anything with ``is_set()`` (a
        # threading.Event set from a signal handler); ``request_stop()``
        # is the in-process equivalent.
        self.stop_event = None
        self._stop_requested = False
        self.scheduler = ServeScheduler(model.cfg, cost_engine, max_len=max_len)
        # --- mesh placement: shard-vs-replicate is a CostQuery, not a flag
        if shard_params not in ("auto", "shard", "replicate"):
            raise ValueError(
                f"shard_params must be 'auto', 'shard' or 'replicate', "
                f"got {shard_params!r}")
        self.mesh = mesh
        self.tp = 1
        self._ctx = None
        self._shard_decision = None
        self._state_shardings = None
        self.collective_ops = 0  # engine-lifetime; reports carry deltas
        self._collective_counts: Dict[object, int] = {}
        if mesh is not None:
            from repro.distributed.sharding import (
                ShardingCtx,
                param_shardings,
                serve_state_sharding,
                validate_serve_mesh,
            )

            mesh_tp = int(mesh.shape.get("model", 1))
            validate_serve_mesh(model.cfg, dict(mesh.shape))
            tp_choice, self._shard_decision = self.scheduler.serve_shard(
                n_slots, tp=mesh_tp,
                override=None if shard_params == "auto" else shard_params)
            if tp_choice > 1:
                self.tp = tp_choice
                # pure-TP ctx: no data axis on the serve hot path (decode
                # batch = n_slots, not a data-parallel global batch)
                self._ctx = ShardingCtx(
                    mesh=mesh, data_axes=(),
                    cost_engine=self.scheduler.engine,
                    infer_replicate_params=True)
                self.params = jax.device_put(
                    params,
                    param_shardings(jax.eval_shape(lambda: params), mesh,
                                    data_axes=()))
                self._state_shardings = serve_state_sharding(
                    jax.eval_shape(lambda: model.init_decode_state(
                        n_slots, max_len, per_slot=True,
                        paging=(self.kv_blocks, self.block_size))), mesh)
        self.pool = SlotPool(model, n_slots, max_len,
                             shardings=self._state_shardings,
                             block_size=self.block_size,
                             kv_blocks=self.kv_blocks)
        # pooled decode state is donated through both hot-path programs:
        # cache updates run in place, never copy-on-write.  Under sharding,
        # out_shardings pins (replicated tokens, same state layout) so the
        # donated buffers are reused shard-for-shard with no resharding copy
        if self._ctx is not None:
            out_sh = (NamedSharding(mesh, P()), self._state_shardings)
            self._prefill = jax.jit(make_batched_prefill(model, self._ctx),
                                    donate_argnums=(1,), out_shardings=out_sh)
            self._macro_out = out_sh
        else:
            self._prefill = jax.jit(make_batched_prefill(model),
                                    donate_argnums=(1,))
            self._macro_out = None
        self._macro_fns: Dict[int, Callable] = {}
        # an admission prefills only its group's rows, rounded up to a row
        # bucket (a power of two, capped at n_slots), when positions are the
        # only per-slot state: the paged pool's pages are shared, so nothing
        # else of the idle slots has to ride along.  Window rings and
        # recurrent states are per-slot, so those engines keep prefilling
        # every slot.
        self._row_buckets = (
            sorted({min(1 << i, n_slots)
                    for i in range(n_slots.bit_length() + 1)})
            if pages_only(self.pool.state) else [n_slots])
        # (n_chunks, rows, chunk) of every prefill compiled so far
        self._prefill_shapes: set = set()
        # host mirrors of per-slot last token / remaining token budget
        self._last_tok = np.full((n_slots,), self.pad_id, np.int32)
        self._budget = np.zeros((n_slots,), np.int32)
        self._last_macro_key = None
        # every admission group pads its prompts to the trace-wide max
        # prompt length, so the jitted group prefill compiles ONE shape per
        # trace instead of one per ragged group composition
        self._group_pad: Optional[int] = None
        # overhead accounting (engine-lifetime; ServeReport carries deltas)
        self.host_syncs = 0
        self.device_dispatches = 0
        # paged-KV accounting: hit/prefill/CoW counters are engine-lifetime
        # (reports carry deltas); peaks are reset per run.  Host mirrors
        # only — never a device sync.
        self.prefix_hit_tokens = 0
        self.prefilled_tokens = 0
        self.cow_count = 0
        self.prefill_padded_tokens = 0
        self.decode_slot_steps = 0
        self.narrow_admissions = 0
        self.held_assignments = 0
        self.experts_hit = 0
        self._peak_live_tokens = 0
        self._peak_blocks = 0

    def _macro(self, horizon: int) -> Callable:
        """Compiled K-token macro-step, cached per horizon (the candidate
        set is fixed, so this cache is bounded)."""
        fn = self._macro_fns.get(horizon)
        if fn is None:
            kw = {} if self._macro_out is None else \
                {"out_shardings": self._macro_out}
            fn = jax.jit(
                make_decode_macro_step(self.model, horizon, eos_id=self.eos_id,
                                       pad_id=self.pad_id, ctx=self._ctx),
                donate_argnums=(1,), **kw)
            self._macro_fns[horizon] = fn
        return fn

    def _count_collectives(self, key, fn, *args) -> int:
        """Collective ops in one compiled program, from post-SPMD HLO text,
        cached per program key (shapes repeat; warmup absorbs the one
        compile per key).  0 when the engine is not sharded."""
        if self._ctx is None:
            return 0
        n = self._collective_counts.get(key)
        if n is None:
            try:
                txt = fn.lower(*args).compile().as_text()
                n = len(_COLLECTIVE_RE.findall(txt))
            except Exception:  # backend without HLO text: count unavailable
                n = 0
            self._collective_counts[key] = n
        return n

    # ------------------------------------------------------------------

    def _dispatch(self, site: str, thunk, touched: List[Request]):
        """Execute one device-step thunk.  Without an injector or watchdog
        this is a DIRECT call — the unperturbed hot path stays thread-free.
        With either, the step runs under ``guarded_call``: injected faults
        fire, the watchdog bounds a stall, transient failures retry with
        backoff (counted onto the engine and the ``touched`` requests), and
        exhaustion/abandonment surfaces as ``StepFailed`` for ``run()`` to
        convert into per-request FAILED + a pool drain."""
        if self.injector is None and not self.watchdog_s:
            return thunk(None)

        def before_thunk(cancel):
            if self.injector is not None:
                self.injector.before(site, cancel)
            return thunk(cancel)

        def on_retry(attempt, err):
            self.step_retries += 1
            for r in touched:
                r.retries += 1

        def on_watchdog(attempt):
            self.watchdog_fires += 1

        return guarded_call(
            before_thunk, watchdog_s=self.watchdog_s,
            retries=self.max_retries, backoff_s=self.retry_backoff_s,
            on_retry=on_retry, on_watchdog=on_watchdog)

    def _step(self, site: str, names, call, touched: List[Request],
              parent: Optional[Span]):
        """One jitted step ``call() -> (out, new_state[, counts])`` and its
        host sync, through ``_dispatch``.  Returns the host copy of ``out``,
        the new state, and the seconds from dispatch to the end of the
        sync: with a recorder, the start of span ``names[0]`` to the end of
        ``names[1]`` (both under ``parent``, also when a guard runs the step
        on a worker thread), so the ledger and the spans read one clock.
        The expert counts a model with experts returns are added to the
        engine's and to ``parent``'s attributes."""
        tr = self.tracer

        def thunk(cancel):
            # the host sync happens INSIDE the guarded call, so the
            # watchdog covers the device execution, not just the dispatch
            if tr is None:
                t0 = time.perf_counter()
                out, state, *counts = call()
                out = np.asarray(out)
                counts = [np.asarray(c) for c in counts]
                return out, state, counts, time.perf_counter() - t0
            with tr.span(names[0], parent) as d:
                out, state, *counts = call()
            with tr.span(names[1], parent) as y:
                out = np.asarray(out)
                counts = [np.asarray(c) for c in counts]
            return out, state, counts, y.end - d.start

        out, state, counts, dt = self._dispatch(site, thunk, touched)
        if counts:
            held, hit = (int(v) for v in counts[0])
            self.held_assignments += held
            self.experts_hit += hit
            if parent is not None:
                parent.attrs.update(held_assignments=held, experts_hit=hit)
        return out, state, dt

    def _publish(self, req: Request, tokens, done: bool, t: float) -> None:
        """Publish a request's newly-emitted tokens to the attached stream
        (no-op without one).  A broken stream — the frontend's emission
        worker died — flips ``_stream_dead``; ``run()`` converts that into
        typed FAILED for everything in flight, because tokens that cannot
        reach the client are not worth generating."""
        if self.stream is None or self._stream_dead:
            return
        try:
            self.stream.publish(req.rid, tokens, done=done, t=t)
        except StreamBroken as e:
            self._stream_dead = True
            self._stream_reason = f"frontend stream broken: {e}"

    def _fail_inflight(self, reqs: List[Request], t: float,
                       reason: str) -> None:
        """Failure path: mark ``reqs`` FAILED and restore an empty, valid,
        donation-ready pool (drain falls back to reinit if an abandoned
        step consumed the donated buffers)."""
        for r in reqs:
            if not r.state.terminal:
                r.mark(RequestState.FAILED, t, reason=reason)
                self._publish(r, (), done=True, t=t)
        self.pool.drain()
        self._last_tok[:] = self.pad_id
        self._budget[:] = 0
        self._last_macro_key = None

    def _split_group(self, group: List[Request]):
        """Within-group prefix sharing.  PR 8's radix lookups all run
        BEFORE the group's single batched prefill, so same-group requests
        were blind to each other's pages and a prompt prefix shared by two
        group members prefilled once PER MEMBER.  This predicts that
        overlap from the trie and SPLITS the group: a request whose
        block-aligned shared prefix with an earlier KEPT member is not yet
        resident is deferred to the next admission round, where the
        donor's freshly-published pages turn the redundant prefill into an
        ordinary radix hit.

        Deferral only fires when the serve_prefix cost model says the
        predicted hit would actually be APPLIED (the same pricing the
        deferred request will face at its own admission) — at scales where
        reuse loses, groups stay whole and admission is unchanged.
        Progress is guaranteed: a member defers only to a donor kept in
        the CURRENT group, so every round admits at least one request."""
        bs = self.block_size
        sch = self.scheduler
        kept: List[Request] = []
        kept_prompts: List[List[int]] = []
        deferred: List[Request] = []
        for r in group:
            p = [int(t) for t in r.prompt] + [int(t) for t in r.tokens]
            plen = len(p)
            # same cap as lookup(): at most plen-1 prompt tokens can ever
            # be served from cache, and only in full blocks
            cap = ((plen - 1) // bs) * bs
            shared = 0
            for q in kept_prompts:
                n = 0
                for a, b in zip(p, q):
                    if a != b:
                        break
                    n += 1
                shared = max(shared, min((n // bs) * bs, cap))
            if (shared >= bs and self.pool.blocks.resident_prefix_tokens(
                    p[:shared]) < shared):
                kw = dict(flops_per_token=sch.flops_per_token,
                          weight_bytes=sch.weight_bytes, block_size=bs,
                          kv_bytes_per_token=sch.kv_bytes_per_token,
                          dtype_bytes=sch.dtype_bytes)
                reuse = sch.engine.model.serve_prefix_cost(
                    plen, shared, plen, **kw)
                base = sch.engine.model.serve_prefix_cost(plen, 0, plen, **kw)
                if (self._prefix_override == "use_prefix"
                        or reuse.total <= base.total):
                    deferred.append(r)
                    continue
            kept.append(r)
            kept_prompts.append(p)
        return kept, deferred

    def _admit_group(self, reqs: List[Request], now,
                     span: Optional[Span] = None) -> None:
        """Admit a group of requests with ONE batched prefill lowered
        directly into their pooled slots (no single-slot state + insert
        copy, one host sync for the whole group).  ``now`` is the run
        clock: first tokens are stamped AFTER prefill returns, so TTFT
        includes the prefill wall time.

        A request re-admitted after preemption prefills prompt + the
        tokens it already generated: greedy decode is deterministic, so
        the continuation is token-identical to an uninterrupted run (its
        original ``admitted_s`` / ``first_token_s`` stamps are kept).

        Admission consults the radix prefix cache (the tenth cost site,
        ``CostQuery(kind=serve_prefix)``): each request's prompt is looked
        up in the block trie, a ``use_prefix`` verdict pins the matched
        pages into the slot's table (partial-tail matches copy-on-write
        ONE page) and prefills only the suffix; the full prompt's pages
        are inserted back into the trie after prefill so the next request
        sharing the prefix hits.  A preempted request re-admitted here
        re-pins its own prompt's pages the same way.

        The prefill runs on the smallest row bucket that holds the group:
        its slots padded to a power of two where only positions are
        per-slot (every layer full attention), every slot otherwise.

        ``span`` is the enclosing ``serve/admit`` span, if a recorder is
        attached: its phases nest under it and it takes the group's
        attributes."""
        slots = [self.pool.acquire(r) for r in reqs]
        prompts = [np.concatenate([np.asarray(r.prompt, np.int32),
                                   np.asarray(r.tokens, np.int32)])
                   if r.tokens else np.asarray(r.prompt, np.int32)
                   for r in reqs]
        starts = np.zeros((self.pool.n_slots,), np.int32)
        prefix_decs = []  # (decision, prompt_len, applied) per request
        any_hit = False
        with maybe_span(self.tracer, "serve/admit/prefix"):
            bs = self.block_size
            for r, s, p in zip(reqs, slots, prompts):
                plen = int(p.shape[-1])
                toks = tuple(int(t) for t in p)
                match = (self.pool.blocks.lookup(toks)
                         if self.prefix_cache else None)
                hit = match.hit_tokens(bs) if match is not None else 0
                cow = 1 if (match is not None
                            and match.tail_donor is not None) else 0
                applied, dec_p = self.scheduler.serve_prefix(
                    plen, hit_tokens=hit, cow_blocks=cow, block_size=bs,
                    override=self._prefix_override)
                if applied > 0:
                    self.pool.assign_prefix(s, match.block_ids)
                    if match.tail_donor is not None:
                        self.pool.cow_block(s, match.tail_donor)
                        self.cow_count += 1
                    starts[s] = applied
                    any_hit = True
                elif match is not None:
                    # full-prefill verdict: drop the lookup's pins
                    self.pool.blocks.release(match.block_ids)
                    if match.tail_donor is not None:
                        self.pool.blocks.decref(match.tail_donor)
                self.pool.ensure_blocks(s, plen)
                self.prefix_hit_tokens += applied
                self.prefilled_tokens += plen - applied
                prefix_decs.append((dec_p, plen, applied))
        with maybe_span(self.tracer, "serve/admit/prepare"):
            # prefix-hit rows prefill SUFFIX tokens only (never empty: the
            # lookup caps hits at prompt_len - 1 so the first generated
            # token always comes from a real forward).  A group with any hit
            # pads to the longest suffix instead of the trace-wide prompt
            # pad — that's the compute reduction; the extra compiled prefill
            # shapes are bounded by the chunk grid.
            suffixes = [p[int(starts[s]):] for s, p in zip(slots, prompts)]
            lmax = max([int(sfx.shape[-1]) for sfx in suffixes]
                       + ([] if any_hit else [self._group_pad or 0]))
            override = (None if self.prefill_chunk == "auto"
                        else self.prefill_chunk)
            chunk, dec = self.scheduler.prefill_chunk(
                lmax, active_decodes=self.pool.active_count - len(reqs),
                override=override)
            n_rows = next(r for r in self._row_buckets if r >= len(reqs))
            # a prefill over every slot keeps row = slot; a narrower one
            # takes the group's slots in ascending order, which is how the
            # program finds them (where ``lengths`` is positive)
            narrow = n_rows < self.pool.n_slots
            row_of = ({s: j for j, s in enumerate(sorted(slots))} if narrow
                      else {s: s for s in slots})
            tokens = np.zeros((n_rows, lmax), np.int32)
            lengths = np.zeros((self.pool.n_slots,), np.int32)
            t_adm = now()
            for r, s, sfx in zip(reqs, slots, suffixes):
                if r.admitted_s is None:
                    r.admitted_s = t_adm
                r.mark(RequestState.PREFILLING, t_adm)
                tokens[row_of[s], : sfx.shape[-1]] = sfx
                lengths[s] = sfx.shape[-1]
            chunks = jnp.asarray(_prefill_chunks(tokens, chunk))
            lens = jnp.asarray(lengths)
            extra = (jnp.asarray(starts), self.pool.block_tables())
            self.collective_ops += self._count_collectives(
                ("prefill", chunks.shape), self._prefill,
                self.params, self.pool.state, chunks, lens, *extra)
            self._prefill_shapes.add(chunks.shape)

        first_np, self.pool.state, dt = self._step(
            "prefill", _ADMIT_STEP_SPANS,
            lambda: self._prefill(self.params, self.pool.state, chunks, lens,
                                  *extra),
            reqs, span)
        padded_len = chunks.shape[0] * chunk
        self.device_dispatches += 1
        self.host_syncs += 1
        self.prefill_padded_tokens += n_rows * padded_len
        self.narrow_admissions += narrow
        self.scheduler.record_measured(
            dec, dt, note=f"prefill group={len(reqs)} len={lmax} chunk={chunk}")
        for dec_p, plen, applied in prefix_decs:
            self.scheduler.record_measured(
                dec_p, dt,
                note=f"serve_prefix len={plen} hit={applied} "
                     f"group={len(reqs)}")
        with maybe_span(self.tracer, "serve/admit/finish"):
            t_first = now()
            for r, s, p in zip(reqs, slots, prompts):
                tk = int(first_np[row_of[s]])
                r.tokens.append(tk)
                if r.first_token_s is None:
                    r.first_token_s = t_first
                self.pool.set_pos(s, int(p.shape[-1]))
                if self.prefix_cache:
                    # publish the full prompt's pages into the trie BEFORE
                    # any release: pinned there, they survive slot turnover
                    # (dedupe swaps repoint this slot at already-resident
                    # duplicates)
                    swaps = self.pool.blocks.insert(
                        tuple(int(t) for t in p), self.pool.slot_table(s))
                    self.pool.apply_swaps(s, swaps)
                if tk == self.eos_id or len(r.tokens) >= r.max_new_tokens:
                    r.mark(RequestState.COMPLETED, t_first)
                    self.pool.release(s)
                    self._last_tok[s] = self.pad_id
                    self._budget[s] = 0
                    self._publish(r, (tk,), done=True, t=t_first)
                else:
                    r.mark(RequestState.DECODING, t_first)
                    self._last_tok[s] = tk
                    self._budget[s] = r.max_new_tokens - len(r.tokens)
                    self._publish(r, (tk,), done=False, t=t_first)
            self._peak_live_tokens = max(self._peak_live_tokens,
                                         int(self.pool.positions().sum()))
            self._peak_blocks = max(self._peak_blocks,
                                    self.pool.blocks.used_blocks)
        if span is not None:
            span.attrs.update(
                rids=[r.rid for r in reqs], rows=n_rows,
                slots=self.pool.n_slots, padded_len=padded_len,
                useful_tokens=int(lengths.sum()),
                prefix_hit_tokens=int(starts.sum()), chunk=chunk)

    def _macro_step(self, active: Dict[int, Request], now,
                    any_deadlines: bool,
                    span: Optional[Span] = None) -> Dict[int, Request]:
        """One K-token macro-step over the pool: choose the horizon and
        upload the inputs, dispatch and sync, then parse each live slot's
        tokens.  Returns the slots still active (none after a failed step).
        ``span`` is the enclosing ``serve/macro`` span, if a recorder is
        attached."""
        with maybe_span(self.tracer, "serve/macro/plan"):
            batch_size = len(active)
            remaining = tuple(sorted(int(self._budget[s]) for s in active))
            override = (None if self.macro_step == "auto"
                        else self.macro_step)
            # key on the same budget clipping the CostEngine applies, so
            # repeat compositions dedupe instead of re-recording as every
            # budget decrements
            cap = (max(self.scheduler.macro_candidates) if override is None
                   else override)
            key = (batch_size, tuple(min(r, cap) for r in remaining))
            horizon, dec = self.scheduler.macro_horizon(
                remaining, override=override,
                record=key != self._last_macro_key)
            self._last_macro_key = key
            mask = self.pool.active_mask()
            macro_fn = self._macro(horizon)
            tok_in = jnp.asarray(self._last_tok)
            mask_in = jnp.asarray(mask)
            budget_in = jnp.asarray(self._budget)
            # grow each live slot's table to cover this macro-step's K
            # cache writes, then upload the tables (fixed shape — no
            # recompile; async — no host sync; NOT donated)
            pos = self.pool.positions()
            for s in active:
                self.pool.ensure_blocks(s, int(pos[s]) + horizon)
            tables = self.pool.block_tables()
            self._peak_live_tokens = max(self._peak_live_tokens,
                                         int(pos.sum()))
            self._peak_blocks = max(self._peak_blocks,
                                    self.pool.blocks.used_blocks)
            self.collective_ops += self._count_collectives(
                ("macro", horizon), macro_fn,
                self.params, self.pool.state, tok_in, mask_in, budget_in,
                tables)

        touched = list(active.values())
        try:
            em, self.pool.state, dt_step = self._step(
                "macro", _MACRO_STEP_SPANS,
                lambda: macro_fn(self.params, self.pool.state, tok_in,
                                 mask_in, budget_in, tables),
                touched, span)
        except StepFailed as e:
            self._fail_inflight(touched, now(),
                                reason=f"macro step failed: {e}")
            return {}
        self.device_dispatches += 1
        self.host_syncs += 1
        self.decode_slot_steps += self.pool.n_slots * horizon
        self.scheduler.record_measured(
            dec, dt_step, note=f"macro K={horizon} b={batch_size}")
        if self._shard_pending:
            self.scheduler.record_measured(
                self._shard_decision, dt_step / horizon,
                note=f"serve_shard tp={self.tp} per-step from macro "
                     f"K={horizon} b={batch_size}")
            self._shard_pending = False
        emitted = 0
        with maybe_span(self.tracer, "serve/macro/emit"):
            # injected-NaN fault class: NaN logits argmax to garbage tokens;
            # the injector corrupts the host copy and the validation below
            # (piggybacked on the macro-step sync the engine already pays —
            # zero extra syncs) catches it
            bad_slots: set = set()
            if self.injector is not None:
                em = self.injector.corrupt("macro", em, sorted(active))
                vocab = self.model.cfg.vocab_size
                bad = np.argwhere((em < 0) | (em >= vocab))
                bad_slots = {int(s) for s in bad[:, 0]} & set(active)
            t_emit = now()
            for slot in list(active):
                req = active[slot]
                if slot in bad_slots:
                    # poison output fails THIS request; the other slots'
                    # device state advanced normally
                    req.mark(RequestState.FAILED, t_emit,
                             reason="corrupt step output (NaN logits)")
                    self.pool.release(slot)
                    self._last_tok[slot] = self.pad_id
                    self._budget[slot] = 0
                    self._last_macro_key = None
                    self._publish(req, (), done=True, t=t_emit)
                    del active[slot]
                    continue
                n_before = len(req.tokens)
                finished = False
                for j in range(horizon):
                    tk = int(em[slot, j])
                    req.tokens.append(tk)
                    if (tk == self.eos_id
                            or len(req.tokens) >= req.max_new_tokens):
                        finished = True
                        break
                n_emitted = len(req.tokens) - n_before
                emitted += n_emitted
                self.pool.advance(slot, n_emitted)  # before release zeroes
                # the macro-step's one host sync already happened —
                # streaming this burst costs no extra device traffic
                burst = tuple(req.tokens[n_before:])
                if finished:
                    req.mark(RequestState.COMPLETED, t_emit)
                    self.pool.release(slot)
                    self._last_tok[slot] = self.pad_id
                    self._budget[slot] = 0
                    self._publish(req, burst, done=True, t=t_emit)
                    del active[slot]
                elif (any_deadlines and req.deadline_s is not None
                      and t_emit - req.arrival_s > req.deadline_s):
                    # deadlines are enforced at macro-step boundaries:
                    # evict to TIMED_OUT, free the slot immediately
                    req.mark(RequestState.TIMED_OUT, t_emit,
                             reason="total-latency deadline exceeded "
                                    "while decoding")
                    self.pool.release(slot)
                    self._last_tok[slot] = self.pad_id
                    self._budget[slot] = 0
                    self._last_macro_key = None
                    self._publish(req, burst, done=True, t=t_emit)
                    del active[slot]
                else:
                    self._last_tok[slot] = int(em[slot, horizon - 1])
                    self._budget[slot] -= n_emitted
                    self._publish(req, burst, done=False, t=t_emit)
        if span is not None:
            span.attrs.update(
                rids=[r.rid for r in touched], k=horizon,
                rows=self.pool.n_slots, live=batch_size, emitted=emitted)
        return active

    # ------------------------------------------------------------------

    def request_stop(self) -> None:
        """Ask a running trace to shut down gracefully: intake stops at the
        next loop boundary (queued requests -> typed REJECTED), in-flight
        slots decode to terminal states, run() returns its report.  Safe to
        call from a signal handler or another thread — it only sets a
        flag.  Sticky until ``reset_stop()``."""
        self._stop_requested = True

    def reset_stop(self) -> None:
        """Re-arm after a graceful shutdown so the engine can serve another
        trace (``stop_event`` holders must also clear their event)."""
        self._stop_requested = False

    def _should_stop(self) -> bool:
        return self._stop_requested or (
            self.stop_event is not None and self.stop_event.is_set())

    def run(self, requests: List[Request],
            now_fn=time.perf_counter) -> ServeReport:
        """Run a request trace to completion: every request reaches a
        terminal lifecycle state (the drain invariant), whatever deadlines,
        preemptions or injected faults fire along the way.  ``now_fn`` is
        injectable so tests can pin a virtual clock (arrivals then resolve
        instantly).

        An unperturbed trace — no deadlines, uniform priorities, no
        injector/watchdog — takes EXACTLY the pre-lifecycle path: the same
        CostQuery sequence, the same dispatches, zero extra host syncs, and
        therefore bit-identical tokens."""
        for r in requests:
            validate_request(r, self.max_len)  # typed, names the rid
            r.reset_lifecycle()
        self._group_pad = max((r.prompt_len for r in requests), default=0)
        # deadline/priority machinery only engages when a request asks
        any_deadlines = any(r.deadline_s is not None
                            or r.ttft_deadline_s is not None
                            for r in requests)
        pending = deque(sorted(requests, key=lambda r: r.arrival_s))  # stable
        waiting: List[Request] = []  # arrived, QUEUED (incl. re-queued)
        active: Dict[int, Request] = {}
        sync0 = self.host_syncs
        disp0 = self.device_dispatches + self.pool.dispatch_count
        col0 = self.collective_ops
        ret0, wd0 = self.step_retries, self.watchdog_fires
        hit0, pf0, cow0 = (self.prefix_hit_tokens, self.prefilled_tokens,
                           self.cow_count)
        pad0, slot0 = self.prefill_padded_tokens, self.decode_slot_steps
        narrow0 = self.narrow_admissions
        held0, hit0 = self.held_assignments, self.experts_hit
        ev0 = tok0 = 0
        if self.stream is not None:
            ev0 = self.stream.published_events
            tok0 = self.stream.published_tokens
        self._stream_dead = False
        self._stream_reason = ""
        self._peak_live_tokens = 0
        self._peak_blocks = 0
        # attach ONE measured wall time per run to the serve_shard row (the
        # first macro-step, normalized per decode step)
        self._shard_pending = self._shard_decision is not None
        t0 = now_fn()
        offset = 0.0  # event-skip accumulator for frozen (virtual) clocks
        now = lambda: now_fn() - t0 + offset  # noqa: E731

        def intake(t: float) -> None:
            """Move arrived requests into the waiting queue, bouncing off a
            full bounded queue (backpressure -> typed REJECTED) and expiring
            deadlines that lapsed while QUEUED."""
            with maybe_span(self.tracer, "serve/intake") as sp:
                n_pending = len(pending)
                while pending and pending[0].arrival_s <= t:
                    r = pending.popleft()
                    if (self.queue_limit is not None
                            and len(waiting) >= self.queue_limit):
                        r.mark(RequestState.REJECTED, t, reason="queue_full")
                        continue
                    waiting.append(r)
                if any_deadlines:
                    still = []
                    for r in waiting:
                        if (r.deadline_s is not None
                                and t - r.arrival_s > r.deadline_s):
                            r.mark(RequestState.TIMED_OUT, t,
                                   reason="deadline expired while queued")
                        else:
                            still.append(r)
                    waiting[:] = still
                if sp is not None:
                    sp.attrs["arrived"] = n_pending - len(pending)

        try:
            while pending or waiting or active:
                if self._should_stop() and (pending or waiting):
                    # graceful shutdown: intake stops NOW — everything not
                    # yet holding a slot goes terminal (typed REJECTED, so
                    # a client can tell "shed at shutdown" from a fault) —
                    # while active slots keep decoding to completion below
                    t_stop = now()
                    for r in list(pending) + waiting:
                        r.mark(RequestState.REJECTED, t_stop,
                               reason="shutdown: intake stopped")
                    pending.clear()
                    waiting.clear()
                if self._stream_dead:
                    # the frontend's emission worker died: tokens can no
                    # longer reach the client, so generating more is waste.
                    # Fail everything non-terminal (typed) and drain — the
                    # invariant holds, every request still ends terminal.
                    self._fail_inflight(
                        [r for r in requests if not r.state.terminal],
                        now(), reason=self._stream_reason)
                    pending.clear()
                    waiting.clear()
                    active = {}
                    break
                # intake runs even when the pool is saturated, so bounded-
                # queue backpressure and queued-deadline expiry act on
                # arrival, not on the next free slot
                intake(now())
                # --- admission (one batched prefill per admitted group) ---
                while (pending or waiting) and self.pool.free_count:
                    t = now()
                    intake(t)
                    if not waiting:
                        break
                    with maybe_span(self.tracer, "serve/admit") as adm:
                        n_admit, _ = self.scheduler.admission(
                            active=self.pool.active_count,
                            waiting=len(waiting),
                            free_slots=self.pool.free_count)
                        if n_admit <= 0:
                            break
                        # stable sort: priority first, then arrival order —
                        # at uniform priority this IS the original FIFO order
                        waiting.sort(key=lambda r: (-r.priority, r.arrival_s))
                        group: List[Request] = []
                        want = min(n_admit, self.pool.free_count,
                                   len(waiting))
                        while len(group) < want and waiting:
                            r = waiting[0]
                            if (r.deadline_s is not None
                                    or r.ttft_deadline_s is not None):
                                ok, _ = self.scheduler.serve_admit(
                                    r, now=t,
                                    active=self.pool.active_count + len(group),
                                    n_slots=self.pool.n_slots)
                                if not ok:
                                    waiting.pop(0)
                                    r.mark(RequestState.REJECTED, t,
                                           reason="deadline_infeasible")
                                    continue
                            group.append(waiting.pop(0))
                        if not group:
                            continue  # everything at the head was shed
                        if self.prefix_cache and len(group) > 1:
                            group, deferred = self._split_group(group)
                            if deferred:
                                # back to the queue head: next admission round
                                # the donor's pages are published and these
                                # turn into radix hits
                                waiting[0:0] = deferred
                        try:
                            self._admit_group(group, now, adm)
                        except StepFailed as e:
                            # prefill died (retries exhausted or abandoned):
                            # the donated pool state is suspect — fail the
                            # group AND anything in flight, drain, keep serving
                            self._fail_inflight(
                                group + list(active.values()), now(),
                                reason=f"prefill step failed: {e}")
                            active = {}
                            continue
                    active = {s: self.pool.owner(s)
                              for s in self.pool.active_slots()}

                # --- priority preemption: a strictly-higher-priority
                # waiter evicts the lowest-priority active slot (the
                # victim re-queues and later re-prefills prompt+generated,
                # so its greedy output is unchanged).  Never fires at
                # uniform priority — the unperturbed path skips it all.
                if (waiting and active and not self.pool.free_count
                        and max(r.priority for r in waiting)
                        > min(r.priority for r in active.values())):
                    t = now()
                    victim_slot = min(
                        active, key=lambda s: (active[s].priority, -s))
                    victim = active.pop(victim_slot)
                    self.pool.release(victim_slot)
                    self._last_tok[victim_slot] = self.pad_id
                    self._budget[victim_slot] = 0
                    self._last_macro_key = None
                    victim.preemptions += 1
                    victim.mark(RequestState.PREEMPTED, t)
                    victim.mark(RequestState.QUEUED, t)
                    waiting.append(victim)
                    continue  # admission loop fills the freed slot

                if not active:
                    if waiting:
                        continue  # admission re-runs (sheds/admits)
                    if pending:
                        # sleep STRAIGHT to the next arrival: with the pool
                        # empty and nothing queued it is the only upcoming
                        # event (queued deadlines apply to arrived requests
                        # only), so the old fixed 50 ms poll was pure
                        # wakeup overhead.  A 1 ms probe sleep first
                        # distinguishes a real clock from a pinned test
                        # clock, which advances by `offset` instead of
                        # sleeping wall time.
                        with maybe_span(self.tracer, "serve/wait_arrival"):
                            wait = pending[0].arrival_s - now()
                            if wait > 0:
                                before = now()
                                time.sleep(min(wait, 0.001))
                                if now() <= before:
                                    # pinned test clock: jump straight to
                                    # the next arrival instead of sleeping
                                    offset += wait
                                else:
                                    rest = pending[0].arrival_s - now()
                                    if rest > 0:
                                        time.sleep(rest)
                    continue

                # --- one K-token macro-step over the pool ---
                with maybe_span(self.tracer, "serve/macro") as mac:
                    active = self._macro_step(active, now, any_deadlines, mac)
        except BaseException:
            # abort safety net (fatal faults, KeyboardInterrupt, bugs):
            # leave the ENGINE reusable — in-flight requests FAILED, pool
            # drained back to a valid donation-ready state — then re-raise.
            # PREFILLING catches a group that died mid-_admit_group.
            inflight = [r for r in requests
                        if r.state in (RequestState.PREFILLING,
                                       RequestState.DECODING)]
            self._fail_inflight(inflight, now(), reason="run aborted")
            raise

        return ServeReport(
            requests=list(requests), wall_s=now(), pad_id=self.pad_id,
            host_syncs=self.host_syncs - sync0,
            device_dispatches=(self.device_dispatches
                               + self.pool.dispatch_count - disp0),
            mesh_shape=(dict(self.mesh.shape)
                        if self.mesh is not None else None),
            device_count=(int(self.mesh.devices.size)
                          if self.mesh is not None else 1),
            collective_ops=self.collective_ops - col0,
            step_retries=self.step_retries - ret0,
            watchdog_fires=self.watchdog_fires - wd0,
            live_tokens=self._peak_live_tokens,
            reserved_blocks=self._peak_blocks,
            prefix_hit_tokens=self.prefix_hit_tokens - hit0,
            prefilled_tokens=self.prefilled_tokens - pf0,
            cow_count=self.cow_count - cow0,
            prefill_padded_tokens=self.prefill_padded_tokens - pad0,
            decode_slot_steps=self.decode_slot_steps - slot0,
            narrow_admissions=self.narrow_admissions - narrow0,
            held_assignments=self.held_assignments - held0,
            experts_hit=self.experts_hit - hit0,
            streamed_tokens=(self.stream.published_tokens - tok0
                             if self.stream is not None else 0),
            stream_events=(self.stream.published_events - ev0
                           if self.stream is not None else 0))

    def warmup(self, prompt_len: int, max_new_tokens: int = 2) -> None:
        """Compile the prefill/decode/reset executables outside any timed
        trace: one SHORT dummy request through the normal machinery (the
        prefill shape keys on ``prompt_len`` — pass the trace's max prompt
        length), the prefill at that length for every other row bucket
        (compiled, not run), then every macro-step horizon the scheduler
        could pick for budgets up to ``max_new_tokens`` (idle all-masked
        calls — pooled state is donated through and comes back frozen).
        The dummy generates only a couple of tokens: horizon
        precompilation is the idle loop's job, so warmup cost does not
        scale with ``max_new_tokens``."""
        with maybe_span(self.tracer, "serve/setup/warmup"):
            dummy_new = min(2, max(max_new_tokens, 1))
            req = Request("_warmup", np.ones((prompt_len,), np.int32),
                          dummy_new)
            self.run([req])
            self._compile_row_buckets()
            idle_tok = jnp.asarray(np.full((self.pool.n_slots,), self.pad_id,
                                           np.int32))
            idle_mask = jnp.zeros((self.pool.n_slots,), bool)
            idle_budget = jnp.zeros((self.pool.n_slots,), np.int32)
            horizons = [k for k in self.scheduler.macro_candidates
                        if k <= max(max_new_tokens - 1, 1)]
            if self.macro_step != "auto":
                horizons = [self.macro_step]
            idle_tables = self.pool.block_tables()
            for k in horizons:
                with maybe_span(self.tracer,
                                "serve/setup/warmup_macro") as sp:
                    if sp is not None:
                        sp.attrs["k"] = k
                    emitted, self.pool.state, *_ = self._macro(k)(
                        self.params, self.pool.state, idle_tok, idle_mask,
                        idle_budget, idle_tables)
                    np.asarray(emitted)
        self._last_macro_key = None

    def _compile_row_buckets(self) -> None:
        """Compile, without running, the prefill of every row bucket at
        each chunk grid a prefill has used so far; a later call of the
        same shapes finds the program compiled."""
        n_slots = self.pool.n_slots
        zeros = jnp.zeros((n_slots,), jnp.int32)  # lengths and starts
        tables = self.pool.block_tables()
        for n_chunks, _, chunk in sorted(self._prefill_shapes):
            for rows in self._row_buckets:
                shape = (n_chunks, rows, chunk)
                if shape in self._prefill_shapes:
                    continue
                with maybe_span(self.tracer,
                                "serve/setup/warmup_prefill") as sp:
                    if sp is not None:
                        sp.attrs["rows"] = rows
                    self._prefill.lower(
                        self.params, self.pool.state,
                        jnp.zeros(shape, jnp.int32), zeros, zeros,
                        tables).compile()
                self._prefill_shapes.add(shape)
