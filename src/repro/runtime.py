"""The public Runtime: one explicit session object owning the CostEngine,
hardware spec, calibration + autotune caches, mesh, and overhead ledger.

The paper's thesis is that overheads must be managed "to the root level" —
and the root level of this codebase is the machine model every fork-join
decision consults.  Yavits et al. and Haque et al. both argue that overhead
models only pay off when the machine model is an explicit, first-class
parameter of the algorithm API; a hidden process global is not that.  So the
patchwork this module replaces — a process-global ``get_engine()``, three
``REPRO_*`` environment variables, and four launchers each hand-wiring
config -> planner -> engine -> ledger — becomes one constructed object:

    import repro

    rt = repro.Runtime()                      # datasheet constants
    rt = repro.Runtime(repro.RuntimeConfig.from_env())   # legacy env vars
    rt = repro.Runtime(repro.RuntimeConfig(calibrate=True, autotune=True))

    plan   = rt.plan(cfg, shape)              # overhead-driven sharding plan
    result = rt.train(cfg, loop, steps=100)   # training loop + checkpoints
    served = rt.serve(cfg, trace)             # continuous-batching serving
    rt.bench(only="serving_bench")            # benchmark suites
    print(rt.ledger.report())                 # every decision, pred-vs-meas

Two Runtimes are fully isolated: separate engines, decision caches, tuners
and ledgers.  Subsystems (dispatch, sort, planner, MoE, serving scheduler,
kernel tuning) take the engine/tuner by INJECTION; when a caller passes
none, they fall back to ``default_runtime()`` — a lazily-built Runtime
configured from the environment, which is also what the deprecated
``get_engine()`` / ``get_tuner()`` shims delegate to.
"""

from __future__ import annotations

import dataclasses
import os
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Mapping, Optional

import numpy as np

from repro.core.costs.autotune import Autotuner
from repro.core.costs.corrections import CorrectionState
from repro.core.costs.engine import CostEngine
from repro.core.costs.ledger import OverheadLedger
from repro.hw import HardwareSpec, running_spec


# ---------------------------------------------------------------------------
# RuntimeConfig
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Typed construction parameters for a :class:`Runtime`.

    ``calibrate``  — microbenchmark the running backend into the hardware
                     spec on construction (was ``REPRO_CALIBRATE=1``).
    ``autotune``   — let the kernel autotuner measure block-shape candidates
                     (was ``REPRO_AUTOTUNE=1``); off, it serves cached
                     winners or the analytic prior.
    ``cache_dir``  — home of the calibration + autotune JSON caches (was
                     ``$REPRO_COST_CACHE``; default ``~/.cache/repro/...``).
    ``hardware``   — base :class:`HardwareSpec` for the analytic model
                     (default: on a TPU, the attached chip's
                     ``hw.DEVICE_SPECS`` row by ``device_kind``, an unknown
                     kind raising; elsewhere the TPU-v5e datasheet).
                     Calibration replaces measured fields on top of it.
    ``mesh_shape`` — mesh topology as ``{axis: size}`` (e.g. ``{"data": 8,
                     "model": 2}``); ``None`` means one data axis over all
                     visible devices.
    ``ledger_max_entries`` — overhead-ledger cap (drops are counted).
    ``corrections`` — close the ledger loop (DESIGN.md §10): learn per-site
                     multiplicative corrections from measured ledger rows
                     and apply them at query time (clamped, rollback- and
                     invalidation-guarded).  Off by default: an open-loop
                     session prices decisions exactly as the analytic
                     model does.
    ``auto_recalibrate`` — let ``Runtime.serve`` act on sustained raw
                     drift after a trace drains: targeted re-runs of only
                     the drifting sites' calibration probes
                     (``engine.maybe_recalibrate``).  Requires
                     ``calibrate`` to persist the healed spec.
    ``drift_window`` / ``drift_threshold`` — session defaults for the
                     ledger's per-site drift statistic; ``drift_overrides``
                     maps a site name to ``{"window": ..., "threshold":
                     ...}`` so high-rate sites can use tighter windows.
                     One knob set, shared by the warning path
                     (``ledger.report()``), the correction loop, and the
                     recalibration trigger.
    """

    calibrate: bool = False
    autotune: bool = False
    cache_dir: Optional[Path] = None
    hardware: Optional[HardwareSpec] = None
    mesh_shape: Optional[Dict[str, int]] = None
    ledger_max_entries: int = 10_000
    corrections: bool = False
    auto_recalibrate: bool = False
    drift_window: int = 20
    drift_threshold: float = 3.0
    drift_overrides: Optional[Mapping[str, Mapping[str, Any]]] = None

    @classmethod
    def from_env(cls, env: Optional[Mapping[str, str]] = None,
                 **overrides: Any) -> "RuntimeConfig":
        """The one place the legacy ``REPRO_*`` environment variables are
        read: ``REPRO_CALIBRATE=1`` -> calibrate, ``REPRO_AUTOTUNE=1`` ->
        autotune, ``REPRO_CORRECTIONS=1`` -> corrections,
        ``REPRO_COST_CACHE`` -> cache_dir.  Keyword overrides win over the
        environment."""
        env = os.environ if env is None else env
        cache = env.get("REPRO_COST_CACHE")
        fields: Dict[str, Any] = {
            "calibrate": env.get("REPRO_CALIBRATE") == "1",
            "autotune": env.get("REPRO_AUTOTUNE") == "1",
            "corrections": env.get("REPRO_CORRECTIONS") == "1",
            "cache_dir": Path(cache) if cache else None,
        }
        fields.update(overrides)
        return cls(**fields)


# ---------------------------------------------------------------------------
# Result records
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrainResult:
    """What :meth:`Runtime.train` ran and produced."""

    state: Any  # final {"params", "opt", "step", ...} pytree
    start_step: int
    steps_run: int
    wall_s: float
    final_loss: float
    plan: Any  # core.planner.Plan for the launch shape
    diverged: bool = False  # loss went non-finite; loop aborted
    interrupted: bool = False  # should_stop() fired; checkpointed + exited


@dataclasses.dataclass
class ServeResult:
    """One trace run through :meth:`Runtime.serve` (either mode)."""

    mode: str  # "static" | "continuous"
    wall_s: float
    generated_tokens: int
    tok_per_s: float
    p50_s: float
    p95_s: float
    outputs: Dict[str, np.ndarray]  # rid -> generated tokens
    report: Any = None  # serving.ServeReport (continuous mode)
    engine: Any = None  # the serve engine, reusable for follow-up traces
    stream: Any = None  # serving.frontend TokenStream (when streaming)
    texts: Optional[Dict[str, str]] = None  # rid -> detok text (frontend)


def synthetic_trace(n_requests: int, *, prompt_len: int, max_new: int,
                    vocab_size: int, arrival: str = "staggered",
                    gap_ms: float = 20.0, rate: float = 50.0,
                    seed: int = 0, prefix_share: float = 0.0,
                    prefix_len: int = 0) -> List[Any]:
    """Deterministic request trace (random prompts + an arrival process:
    ``all`` at t=0, ``staggered`` every ``gap_ms``, or ``poisson`` at
    ``rate``/s) — the trace builder the serve launcher and benches share.

    ``prefix_share`` > 0 makes that fraction of the requests (the first
    ``round(prefix_share * n)``) open with ONE fixed random prefix of
    ``prefix_len`` tokens followed by private random suffixes — the
    system-prompt traffic shape the radix prefix cache exists for."""
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    prompts = rng.integers(
        1, vocab_size, (n_requests, prompt_len)).astype(np.int32)
    if prefix_share:
        if not 0.0 < prefix_share <= 1.0:
            raise ValueError(
                f"prefix_share must be in (0, 1], got {prefix_share}")
        if not 0 < prefix_len < prompt_len:
            raise ValueError(
                f"prefix_len must be in (0, prompt_len={prompt_len}), "
                f"got {prefix_len}")
        shared = rng.integers(1, vocab_size, (prefix_len,)).astype(np.int32)
        prompts[: int(round(prefix_share * n_requests)), :prefix_len] = shared
    if arrival == "all":
        arrivals = np.zeros(n_requests)
    elif arrival == "staggered":
        arrivals = np.arange(n_requests) * (gap_ms / 1e3)
    elif arrival == "poisson":
        gaps = rng.exponential(1.0 / rate, n_requests)
        arrivals = np.cumsum(gaps) - gaps[0]
    else:
        raise ValueError(f"unknown arrival process: {arrival!r}")
    return [Request(f"r{i}", prompts[i], max_new, arrival_s=float(arrivals[i]))
            for i in range(n_requests)]


# ---------------------------------------------------------------------------
# Runtime
# ---------------------------------------------------------------------------


class Runtime:
    """An explicit repro session: engine + tuner + caches + mesh + ledger.

    Construction is cheap unless ``config.calibrate`` is set (then the
    backend microbenchmarks run once, cached under ``config.cache_dir``).
    ``engine``/``tuner`` kwargs inject prebuilt components (tests).
    """

    def __init__(self, config: Optional[RuntimeConfig] = None, *,
                 engine: Optional[CostEngine] = None,
                 tuner: Optional[Autotuner] = None):
        self.config = config if config is not None else RuntimeConfig()
        if engine is None:
            ledger = OverheadLedger(
                self.config.ledger_max_entries,
                drift_window=self.config.drift_window,
                drift_threshold=self.config.drift_threshold,
                drift_overrides=self.config.drift_overrides)
            base = (self.config.hardware if self.config.hardware is not None
                    else running_spec())
            corrections = (CorrectionState()
                           if self.config.corrections else None)
            if self.config.calibrate:
                engine = CostEngine.calibrated(
                    base, cache_dir=self.config.cache_dir, ledger=ledger,
                    corrections=corrections)
            else:
                engine = CostEngine(hw=base, ledger=ledger,
                                    corrections=corrections)
        self.engine = engine
        if tuner is None:
            tuner = Autotuner(cache_dir=self.config.cache_dir,
                              measure=self.config.autotune,
                              ledger=engine.ledger)
        self.tuner = tuner
        self._mesh = None

    # ------------------------------------------------------------------
    # Owned state
    # ------------------------------------------------------------------

    @property
    def hw(self) -> HardwareSpec:
        """The hardware spec the analytic model runs on (calibrated or
        datasheet)."""
        return self.engine.hw

    @property
    def ledger(self) -> OverheadLedger:
        """THE overhead ledger of this session: every engine decision and
        every measured tuning lands here."""
        return self.engine.ledger

    def mesh_shape(self) -> Dict[str, int]:
        """The configured mesh topology, or one data axis over every
        visible device."""
        if self.config.mesh_shape:
            return dict(self.config.mesh_shape)
        import jax

        return {"data": jax.device_count(), "model": 1}

    @property
    def mesh(self):
        """The jax Mesh for :meth:`mesh_shape` (built lazily; the axis
        sizes must multiply to the visible device count)."""
        if self._mesh is None:
            from repro.launch.mesh import make_mesh

            shape = self.mesh_shape()
            self._mesh = make_mesh(tuple(shape.values()), tuple(shape))
        return self._mesh

    # ------------------------------------------------------------------
    # Workloads
    # ------------------------------------------------------------------

    def plan(self, cfg, shape, mesh_shape: Optional[Dict[str, int]] = None):
        """Overhead-driven sharding plan for ``cfg`` at ``shape`` on this
        runtime's engine (every decision ledgered)."""
        from repro.core.planner import plan_model

        return plan_model(cfg, shape, mesh_shape or self.mesh_shape(),
                          engine=self.engine)

    def train(self, cfg, loop=None, *, steps: int = 200, batch: int = 8,
              seq: int = 64, seed: int = 0, ckpt_dir: Optional[str] = None,
              ckpt_every: int = 50, resume: bool = False,
              step_timeout: float = 0.0, log_every: int = 10,
              log: Callable[[str], None] = print,
              should_stop: Optional[Callable[[], bool]] = None,
              on_plan: Optional[Callable[[Any], None]] = None) -> TrainResult:
        """Run the training loop for ``cfg`` at smoke/launch shape.

        ``seed`` drives both parameter init and the synthetic data stream
        (step-indexed, so ``resume`` replays deterministically).
        ``should_stop`` is polled once per step; when it fires, the loop
        checkpoints (if ``ckpt_dir``) and returns with ``interrupted=True``
        — the hook launchers attach SIGTERM to.  ``on_plan`` sees the
        overhead plan before the first compile.
        """
        import jax

        from repro.checkpoint import latest_step, restore, save
        from repro.configs.base import ShapeSpec
        from repro.data import SyntheticLMData
        from repro.models import build_model
        from repro.training import (TrainLoopConfig, init_train_state,
                                    make_train_step)

        if loop is None:
            loop = TrainLoopConfig(warmup_steps=max(steps // 20, 1),
                                   total_steps=steps)
        model = build_model(cfg)
        plan = self.plan(cfg, ShapeSpec("cli_train", seq, batch, "train"))
        if on_plan is not None:
            on_plan(plan)

        ds = SyntheticLMData(cfg, seq_len=seq, global_batch=batch, seed=seed)
        state = init_train_state(model, jax.random.PRNGKey(seed), loop)
        start = 0
        if resume and ckpt_dir:
            last = latest_step(ckpt_dir)
            if last is not None:
                state = restore(ckpt_dir, last, state)
                start = int(np.asarray(state["step"]))
                log(f"resumed from step {start}")

        step_fn = jax.jit(make_train_step(model, loop))
        t_start = time.time()
        loss = float("nan")
        for i in range(start, steps):
            t0 = time.time()
            state, metrics = step_fn(state, ds.batch_at(i))
            loss = float(metrics["loss"])  # blocks; also the step watchdog
            dt = time.time() - t0
            if step_timeout and dt > step_timeout:
                log(f"[straggler] step {i} took {dt:.2f}s "
                    f"(> {step_timeout}s); continuing")
            if log_every and (i % log_every == 0 or i == steps - 1):
                log(f"step {i:5d} loss {loss:.4f} "
                    f"lr {float(metrics['lr']):.2e} "
                    f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms")
            if not np.isfinite(loss):
                log("loss is not finite; aborting")
                return TrainResult(state, start, i + 1 - start,
                                   time.time() - t_start, loss, plan,
                                   diverged=True)
            stop = bool(should_stop is not None and should_stop())
            if ckpt_dir and (stop or (i + 1) % ckpt_every == 0
                             or i == steps - 1):
                save(ckpt_dir, i + 1, state)
            if stop:
                log(f"interrupted{': checkpointed' if ckpt_dir else ''} "
                    f"step {i + 1}, exiting")
                return TrainResult(state, start, i + 1 - start,
                                   time.time() - t_start, loss, plan,
                                   interrupted=True)
        # a resume past the requested step count runs zero steps, not -N
        return TrainResult(state, start, max(steps - start, 0),
                           time.time() - t_start, loss, plan)

    def serve(self, cfg, trace, *, mode: str = "continuous", model=None,
              params=None, seed: int = 0, slots: int = 4,
              max_len: Optional[int] = None, eos_id: int = 0,
              pad_id: Optional[int] = None, prefill_chunk="auto",
              macro_step="auto", mesh_shape: Optional[Dict[str, int]] = None,
              shard_params: str = "auto", warmup: bool = True,
              queue_limit: Optional[int] = None,
              deadline_ms: Optional[float] = None,
              ttft_deadline_ms: Optional[float] = None,
              inject_fault: Optional[str] = None,
              watchdog_ms: Optional[float] = None, max_retries: int = 2,
              paged: bool = False, block_size: int = 16,
              kv_blocks: Optional[int] = None, prefix_cache="auto",
              frontend=None, stream="auto", pin: bool = False,
              stop_event=None, now_fn=time.perf_counter,
              tracer=None) -> ServeResult:
        """Run a request ``trace`` (a list of ``repro.Request``).

        ``continuous`` is the slot-pooled engine scheduled by this runtime's
        CostEngine (admission / prefill-chunk / macro-horizon decisions land
        as ``site=serve``/``site=serve_macro`` ledger rows with measured
        step times).  ``macro_step`` sets the decode macro-step horizon:
        ``"auto"`` lets the CostEngine pick K per composition, an int pins
        it (K=1 reproduces the per-token loop exactly).
        ``mesh_shape`` (e.g. ``{"data": 1, "model": 8}``) puts the
        continuous engine on a device mesh; whether serve state actually
        shards over the model axis is the ``serve_shard`` CostEngine
        decision, forced with ``shard_params='shard'``/``'replicate'``.
        The axis sizes must divide the arch's head/FFN dims and multiply
        to the visible device count.

        Robustness (continuous mode only; DESIGN.md §8): ``queue_limit``
        bounds the waiting queue (overflow -> typed REJECTED backpressure);
        ``deadline_ms``/``ttft_deadline_ms`` apply a default per-request
        latency budget to requests that don't carry their own (enforced at
        admission via the ``serve_admit`` CostQuery and at macro-step
        boundaries -> TIMED_OUT); ``inject_fault`` arms one injected device
        fault of the named class (``raise`` | ``nan`` | ``stall``) for
        failure drills; ``watchdog_ms`` bounds any single device step
        (required for ``stall``), with up to ``max_retries`` backoff
        retries before in-flight requests FAIL.

        Paged KV (continuous mode only; DESIGN.md §5): ``paged=True``
        stores full-attention KV in a shared BlockPool of
        ``block_size``-token pages (``kv_blocks`` overrides the
        can-never-OOM default) with per-slot block tables, and
        ``prefix_cache`` controls radix prefix reuse at admission
        (``'auto'`` = the serve_prefix CostQuery decides per prompt,
        ``'force'`` pins reuse on, ``False`` disables the trie).

        Front end + streaming (continuous mode only; DESIGN.md §9):
        ``frontend`` moves request intake (validation + pre-processing)
        and token emission (detokenization) into pinned worker PROCESSES
        off the engine thread.  ``frontend='auto'`` lets the ``serve_ipc``
        CostQuery (the eleventh decision site) choose between inline
        intake and 1/2/4 workers; an int pins the worker count (still
        priced + ledgered); a ``FrontendConfig`` pins every knob.  ``pin``
        requests topology-aware CPU affinity (engine thread on a reserved
        physical core, workers round-robin over the rest; hosts without
        ``sched_setaffinity`` degrade gracefully).  ``stream`` attaches a
        per-request incremental token stream published at macro-step
        boundaries from host mirrors the engine already syncs — zero
        additional device syncs ('auto' = on exactly when a frontend is
        on; a ``TokenStream`` instance is used as-is).  Token generation
        never leaves the engine process, so frontend output is
        token-identical by construction — and cross-checked against the
        emission worker's transcript at drain.

        ``tracer`` (a ``repro.serving.spans.SpanRecorder``, continuous mode
        only) records host spans: ``serve/setup`` from here to the start of
        ``engine.run``, with ``serve/setup/engine_init`` and
        ``serve/setup/warmup`` inside it, then the engine's own spans.

        ``static`` is the lockstep baseline: the batch forms at the last
        arrival and every request's latency includes that wait; it requires
        equal-length prompts.  ``params=None`` initializes fresh parameters
        from ``seed``; ``max_len=None`` sizes slots to the largest
        prompt+generation in the trace.
        """
        import jax

        from repro.models import build_model
        from repro.serving import ContinuousServeEngine, ServeEngine
        from repro.serving.engine import emitted_count
        from repro.serving.faults import FaultInjector, FaultSpec
        from repro.serving.frontend import (FrontendConfig, FrontendError,
                                            ServingFrontend, StreamBroken,
                                            TokenStream)
        from repro.serving.frontend.workers import _pickled_size
        from repro.serving.scheduler import RequestState
        from repro.serving.spans import maybe_span

        if not trace:
            raise ValueError("serve() needs a non-empty trace of Requests")
        # fail-fast robustness-flag validation (before any compile/init)
        if inject_fault is not None and inject_fault not in ("raise", "nan",
                                                             "stall"):
            raise ValueError(
                f"inject_fault must be 'raise', 'nan' or 'stall', got "
                f"{inject_fault!r}")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        if ttft_deadline_ms is not None and ttft_deadline_ms <= 0:
            raise ValueError(
                f"ttft_deadline_ms must be > 0, got {ttft_deadline_ms}")
        if queue_limit is not None and queue_limit < 1:
            raise ValueError(f"queue_limit must be >= 1, got {queue_limit}")
        if watchdog_ms is not None and watchdog_ms <= 0:
            raise ValueError(f"watchdog_ms must be > 0, got {watchdog_ms}")
        if inject_fault == "stall" and watchdog_ms is None:
            raise ValueError(
                "inject_fault='stall' without watchdog_ms would hang the "
                "trace for the stall duration; pass watchdog_ms")
        robustness = any(v is not None for v in (
            queue_limit, deadline_ms, ttft_deadline_ms, inject_fault,
            watchdog_ms))
        if mode == "static" and robustness:
            raise ValueError(
                "queue_limit/deadline/fault/watchdog options need the "
                "request lifecycle of mode='continuous'; the static "
                "lockstep baseline has no per-request scheduling")
        if mode == "static" and paged:
            raise ValueError(
                "paged KV needs the slot pool of mode='continuous'; the "
                "static lockstep baseline keeps dense per-row caches")
        if mode == "static" and frontend is not None:
            raise ValueError(
                "the multi-process front end feeds the continuous engine's "
                "request lifecycle; mode='static' has no admission to take "
                "off the engine thread")
        if mode == "static" and stream not in ("auto", False, None):
            raise ValueError(
                "token streaming needs the macro-step boundaries of "
                "mode='continuous'; the static baseline emits one matrix")
        if frontend is not None and not (
                frontend == "auto" or isinstance(frontend, int)
                or isinstance(frontend, FrontendConfig)):
            raise ValueError(
                f"frontend must be None, 'auto', an int worker count or a "
                f"FrontendConfig, got {frontend!r}")
        if isinstance(frontend, int) and frontend < 1:
            raise ValueError(f"frontend worker count must be >= 1, "
                             f"got {frontend}")
        if mode == "static" and tracer is not None:
            raise ValueError(
                "the span recorder instruments the continuous engine's "
                "loop; mode='static' has none")
        if paged and block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        setup = None if tracer is None else tracer.open("serve/setup")
        mesh = None
        if mesh_shape is not None:
            from repro.distributed.sharding import validate_serve_mesh
            from repro.launch.mesh import make_mesh

            shape = {"data": 1, "model": 1}
            unknown = set(mesh_shape) - set(shape)
            if unknown:
                raise ValueError(
                    f"serve mesh_shape axes must be 'data'/'model', got "
                    f"{sorted(unknown)}")
            shape.update({k: int(v) for k, v in mesh_shape.items()})
            # arch divisibility first: checkable on any host, independent
            # of how many devices this process happens to see
            validate_serve_mesh(cfg, shape)
            if mode == "static" and shape["model"] > 1:
                raise ValueError(
                    "mode='static' is the single-device lockstep baseline; "
                    "model-axis sharding needs mode='continuous'")
            need = shape["data"] * shape["model"]
            if need != jax.device_count():
                raise ValueError(
                    f"serve mesh {shape} needs {need} devices but jax sees "
                    f"{jax.device_count()} (forcing a CPU mesh takes "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    f"before jax initializes)")
            mesh = make_mesh((shape["data"], shape["model"]),
                             ("data", "model"))
        if model is None:
            model = build_model(cfg)
        if params is None:
            params = model.init(jax.random.PRNGKey(seed))
        if max_len is None:
            max_len = max(r.prompt_len + r.max_new_tokens for r in trace)

        if mode == "static":
            engine = ServeEngine(model, params, max_len=max_len,
                                 eos_id=eos_id, pad_id=pad_id)
            prompts = np.stack([np.asarray(r.prompt, np.int32) for r in trace])
            max_new = max(r.max_new_tokens for r in trace)
            if warmup:  # compile prefill AND the decode step outside the
                # timed window (the batched-prefill priming no longer runs
                # the decode step, so max_new must reach a real step)
                engine.generate(prompts, max_new_tokens=min(2, max_new))
            start = max(r.arrival_s for r in trace)
            t0 = time.perf_counter()
            out = engine.generate(prompts, max_new_tokens=max_new)
            wall = time.perf_counter() - t0
            # lockstep decodes to the longest budget; each request only
            # keeps (and is only credited for) its own max_new_tokens
            outputs = {r.rid: out[i, :r.max_new_tokens]
                       for i, r in enumerate(trace)}
            gen = sum(emitted_count(row[None], engine.eos_id)
                      for row in outputs.values())
            lats = [start + wall - r.arrival_s for r in trace]
            return ServeResult(
                "static", wall, gen, gen / wall if wall > 0 else 0.0,
                float(np.percentile(lats, 50)), float(np.percentile(lats, 95)),
                outputs, engine=engine)

        if mode == "continuous":
            # default deadlines apply to requests that don't carry their own
            if deadline_ms is not None or ttft_deadline_ms is not None:
                for r in trace:
                    if deadline_ms is not None and r.deadline_s is None:
                        r.deadline_s = deadline_ms / 1e3
                    if (ttft_deadline_ms is not None
                            and r.ttft_deadline_s is None):
                        r.ttft_deadline_s = ttft_deadline_ms / 1e3
            injector = None
            if inject_fault is not None:
                # one fault partway into the trace (after the second macro
                # step / first prefill group), long enough stall to need
                # the watchdog
                site = "macro"
                stall_s = (watchdog_ms or 0) / 1e3 * 20 + 1.0
                injector = FaultInjector((FaultSpec(
                    inject_fault, site=site, after=2, stall_s=stall_s),))
            with maybe_span(tracer, "serve/setup/engine_init"):
                engine = ContinuousServeEngine(
                    model, params, n_slots=slots, max_len=max_len,
                    eos_id=eos_id, pad_id=pad_id, cost_engine=self.engine,
                    prefill_chunk=prefill_chunk, macro_step=macro_step,
                    mesh=mesh, shard_params=shard_params,
                    queue_limit=queue_limit, max_retries=max_retries,
                    paged=paged, block_size=block_size, kv_blocks=kv_blocks,
                    prefix_cache=(True if prefix_cache == "auto"
                                  else prefix_cache),
                    tracer=tracer)
            if warmup:
                # compile prefill (shape keys on the trace-wide max prompt
                # length every group pads to) AND every macro horizon the
                # trace's budgets can trigger, so the timed run never
                # compiles
                engine.warmup(max(r.prompt_len for r in trace),
                              max_new_tokens=max(r.max_new_tokens
                                                 for r in trace))
            # arm the watchdog + injector only AFTER warmup: first-call
            # compiles legitimately take seconds and must not trip either
            engine.watchdog_s = (None if watchdog_ms is None
                                 else watchdog_ms / 1e3)
            engine.injector = injector
            # cooperative graceful shutdown (launch/serve.py's SIGINT/
            # SIGTERM handler sets this): stop intake, drain in-flight to
            # terminal states, still return the report
            engine.stop_event = stop_event

            # --- multi-process front end + token streaming (DESIGN.md §9)
            # serve_ipc decisions (workers / coalesce) are made here, at
            # the deployment layer that owns the processes; the engine only
            # ever sees a TokenStream.
            fe = None
            fe_cfg = None
            dec_w = dec_c = None
            run_trace = list(trace)
            failed_intake: List[Any] = []
            if frontend is not None:
                submissions = [{
                    "rid": r.rid,
                    "prompt": [int(t) for t in np.asarray(r.prompt).tolist()],
                    "max_new_tokens": int(r.max_new_tokens),
                    "arrival_s": float(r.arrival_s),
                    "priority": int(r.priority),
                    "deadline_s": r.deadline_s,
                    "ttft_deadline_s": r.ttft_deadline_s,
                } for r in trace]
                msg_bytes = max(_pickled_size(("req", s))
                                for s in submissions)
                plen = max(r.prompt_len for r in trace)
                if isinstance(frontend, FrontendConfig):
                    fe_cfg = frontend
                    _, dec_w = engine.scheduler.serve_ipc_workers(
                        len(trace), msg_bytes=msg_bytes, prompt_len=plen,
                        candidates=(fe_cfg.workers,), override="frontend")
                else:
                    w, dec_w = engine.scheduler.serve_ipc_workers(
                        len(trace), msg_bytes=msg_bytes, prompt_len=plen,
                        candidates=((1, 2, 4) if frontend == "auto"
                                    else (int(frontend),)),
                        override=(None if frontend == "auto"
                                  else "frontend"))
                    if w > 0:
                        fe_cfg = FrontendConfig(workers=w, pin=pin)
                    # an 'auto' inline verdict serves without a front end —
                    # the ledgered decision IS the cost site doing its job
            want_stream = (stream is True
                           or isinstance(stream, TokenStream)
                           or (stream == "auto" and fe_cfg is not None))
            if fe_cfg is not None and want_stream:
                event_bytes = _pickled_size((trace[0].rid, (0,), False, 0.0))
                pinned = isinstance(frontend, FrontendConfig)
                c, dec_c = engine.scheduler.serve_ipc_coalesce(
                    slots, event_bytes=event_bytes,
                    candidates=((fe_cfg.coalesce,) if pinned
                                else (1, 2, 4, 8, 16)))
                if not pinned:
                    fe_cfg = dataclasses.replace(fe_cfg, coalesce=max(c, 1))

            texts = None
            stream_obj = None
            try:
                if fe_cfg is not None:
                    fe = ServingFrontend(fe_cfg, max_len=max_len)
                    fe.start()
                    t_sub = time.perf_counter()
                    _, failures = fe.submit(submissions)
                    engine.scheduler.record_measured(
                        dec_w, time.perf_counter() - t_sub,
                        note=f"serve_ipc intake n={len(trace)} "
                             f"workers={fe_cfg.workers} "
                             f"pinned={fe.workers_pinned}")
                    if failures:
                        # intake shed these BEFORE the engine: invalid ->
                        # typed REJECTED, worker death -> typed FAILED.
                        # Both are terminal; the drain invariant holds.
                        run_trace = []
                        for r in trace:
                            why = failures.get(r.rid)
                            if why is None:
                                run_trace.append(r)
                                continue
                            r.mark((RequestState.FAILED
                                    if why.startswith("frontend:")
                                    else RequestState.REJECTED),
                                   0.0, reason=why)
                            failed_intake.append(r)
                if want_stream:
                    if isinstance(stream, TokenStream):
                        stream_obj = stream
                    elif fe is not None:
                        stream_obj = fe.stream()
                    else:
                        stream_obj = TokenStream()
                    engine.stream = stream_obj

                if setup is not None:
                    tracer.end(setup)
                report = engine.run(run_trace, now_fn=now_fn)

                if stream_obj is not None:
                    stream_obj.close()  # flush any coalesced tail burst
                if fe is not None:
                    if stream_obj is not None:
                        try:
                            transcript = fe.finish()
                        except StreamBroken:
                            transcript = None  # engine already failed typed
                        if transcript is not None:
                            texts = {rid: rec["text"]
                                     for rid, rec in transcript.items()}
                            for r in run_trace:
                                rec = transcript.get(r.rid)
                                if rec is not None and rec["tokens"] != [
                                        int(t) for t in r.tokens]:
                                    raise FrontendError(
                                        f"emission transcript diverged from "
                                        f"engine for {r.rid!r} — token "
                                        f"identity violated")
                    if dec_c is not None and fe.ping_round_trips_s:
                        engine.scheduler.record_measured(
                            dec_c, float(np.mean(fe.ping_round_trips_s)),
                            note=f"serve_ipc coalesce={fe_cfg.coalesce} "
                                 f"per-message ping round trip")
                    report.ipc_messages = fe.ipc_messages
                    report.ipc_bytes = fe.ipc_bytes
                    report.frontend_workers = fe_cfg.workers
                    report.frontend_respawns = fe.respawns
                    report.requests.extend(failed_intake)
            finally:
                if setup is not None:
                    tracer.end(setup)  # a no-op once it has ended
                if fe is not None:
                    fe.close()
                engine.stream = None  # engine stays reusable stream-free

            if self.config.auto_recalibrate:
                # drift -> action at the drain boundary: the trace's
                # measured rows are in, the device is idle, and a healed
                # spec is what the NEXT trace should be scheduled on
                self.engine.maybe_recalibrate()

            pct = report.latency_percentiles()
            return ServeResult(
                "continuous", report.wall_s, report.generated_tokens,
                report.tok_per_s, pct["p50"], pct["p95"], report.outputs(),
                report=report, engine=engine, stream=stream_obj, texts=texts)

        raise ValueError(f"unknown serve mode: {mode!r}")

    def bench(self, only: Optional[str] = None) -> List[str]:
        """Run the benchmark suites against this runtime (all of them, or
        just ``only``).  Returns the names of failed suites.  Needs the
        repo-root ``benchmarks/`` package on the path."""
        try:
            from benchmarks.run import run_suites
        except ImportError as exc:
            raise ImportError(
                "benchmarks/ is not importable — run from the repo root "
                "(the benchmarks package is not installed with repro)"
            ) from exc
        return run_suites(self, only=only)

    def dryrun(self, arch: str, shape: str, *, multi_pod: bool = False,
               probe: bool = True, verbose: bool = True) -> Dict[str, Any]:
        """Lower + compile one production-mesh cell on this runtime's
        engine.  NOTE: the dry-run forces 512 placeholder devices via
        XLA_FLAGS at module import, so it must run in a process where jax
        has not initialized yet (see launch/dryrun.py)."""
        from repro.launch.dryrun import dryrun_cell

        return dryrun_cell(arch, shape, multi_pod=multi_pod, probe=probe,
                           verbose=verbose, runtime=self)


# ---------------------------------------------------------------------------
# The default Runtime (what the deprecated shims delegate to)
# ---------------------------------------------------------------------------

_default_runtime: Optional[Runtime] = None


def default_runtime() -> Runtime:
    """The process-default Runtime, built lazily from the environment
    (``RuntimeConfig.from_env()``) — the injection fallback for call sites
    that pass no engine/tuner, and the target of the deprecated
    ``get_engine()`` / ``get_tuner()`` shims."""
    global _default_runtime
    if _default_runtime is None:
        _default_runtime = Runtime(RuntimeConfig.from_env())
    return _default_runtime


def set_default_runtime(runtime: Optional[Runtime]) -> None:
    """Replace (or, with None, reset) the process-default Runtime."""
    global _default_runtime
    _default_runtime = runtime
