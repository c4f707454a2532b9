"""One run of one cell: set-up, the measured window, the check that decides
``correct``, and the record the metric readers read.

The window drives the program's normal serve path::

    Runtime.serve(mode="continuous", paged=True) -> ServeScheduler
        -> ContinuousServeEngine (jit(prefill_fn), jit(macro_fn))
        -> the dense transformer in repro.models

``Runtime.serve`` compiles and warms up inside the call; the harness's clock
is first called when ``engine.run`` starts, and that call opens the window:
``setup_s`` ends there.  Requests are stamped by the engine on the
harness's clock, from their due time, so a stall counts against every
request behind it.  At the window's end a ``stop_intake`` mix stops intake
(requests never admitted end REJECTED and are not attempted) and the
admitted ones drain; a ``drain`` mix holds only requests due inside the
window, and all of them are served.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import math
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from chipbench import costs, generator, spec, weights, xtrace

# the reference is compared over a sample of finished requests holding at
# least this many served tokens and requests (the longest always among them)
SAMPLE_TOKENS, SAMPLE_MIN, SAMPLE_MAX = 512, 4, 12


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------- records --


@dataclasses.dataclass
class Req:
    rid: str
    prompt: np.ndarray
    max_new: int
    due_s: float
    admitted_s: Optional[float]
    first_s: Optional[float]
    finish_s: Optional[float]
    state: str
    tokens: List[int]

    @property
    def done(self) -> bool:
        return self.state == "COMPLETED"


@dataclasses.dataclass
class Step:
    """One decode macro-step as the stream saw it: when its tokens were
    published, its horizon, and each live sequence's context before it and
    tokens emitted by it."""
    t: float
    k: int
    rows: List[Tuple[int, int]]

    def contexts(self, j: int) -> List[int]:
        """Context lengths of the sequences still live at inner step ``j``,
        the new token's own position included."""
        return [c + j + 1 for c, n in self.rows if j < n]


@dataclasses.dataclass
class Admission:
    """One group prefill: admission stamp, first-token stamp, prompts."""
    t_admit: float
    t_first: float
    prompt_lens: List[int]


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""
    cell: spec.Cell
    sizes: Dict[str, Any]
    peak: Dict[str, float]
    seconds: float
    setup_s: float
    setup_compile_s: float
    window_compiles: int
    requests: List[Req]
    bursts: List[Tuple[str, int, float]]  # (rid, tokens, stamp) per publish
    steps: List[Step]
    admissions: List[Admission]
    trace: Optional[xtrace.Summary] = None
    trace_end_s: Optional[float] = None  # window time the trace stopped

    def due_in_window(self) -> List[Req]:
        return [r for r in self.requests if r.due_s < self.seconds]

    def attempted(self) -> List[Req]:
        """Requests due in the window that the engine took up: all of them
        in a drained mix, the admitted ones where intake stops."""
        due = self.due_in_window()
        if self.cell.traffic["window_end"] == "stop_intake":
            return [r for r in due if r.admitted_s is not None]
        return due

    def traced_steps(self) -> List[Step]:
        end = self.trace_end_s
        return [s for s in self.steps if end is not None and s.t <= end]


# ------------------------------------------------------- program adapters --


class CompileClock:
    """Sums the backend compiles JAX reports."""

    def __init__(self):
        import jax

        self.seconds, self.programs = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1


def _stream_class():
    from repro.serving.frontend import TokenStream

    class Recorder(TokenStream):
        """The engine's token stream, recording every burst it publishes."""

        def __init__(self):
            super().__init__()
            self.bursts: List[Tuple[str, int, float]] = []

        def publish(self, rid, tokens, done, t):
            self.bursts.append((rid, len(tokens), float(t)))
            super().publish(rid, tokens, done, t)

    return Recorder


class WindowClock:
    """The engine's clock.  Its first call opens the window; the first call
    at or after ``seconds`` closes it (stopping intake where asked)."""

    def __init__(self, seconds: float, stop: Optional[threading.Event],
                 on_open=None, on_close=None):
        self.seconds, self.stop = seconds, stop
        self.on_open, self.on_close = on_open, on_close
        self.t0: Optional[float] = None
        self.closed_at: Optional[float] = None

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.t0 is None:
            if self.on_open is not None:
                self.on_open()
            self.t0 = t = time.perf_counter()
        elif self.closed_at is None and t - self.t0 >= self.seconds:
            self.closed_at = t - self.t0
            if self.stop is not None:
                self.stop.set()
            if self.on_close is not None:
                self.on_close()
        return t


def program_params(w: Dict, model) -> Dict:
    """The drawn weights in the program's parameter layout, checked leaf by
    leaf against what ``model.init`` would make."""
    import jax

    p = {
        "embed": w["embed"], "unembed": w["lm_head"],
        "final_norm": {"scale": w["final_norm"]},
        "layers": {
            "ln1": {"scale": w["attn_norm"]},
            "ln2": {"scale": w["mlp_norm"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "ffn": {"w_in": w["w_up"], "w_gate": w["w_gate"],
                    "w_out": w["w_down"]},
        },
    }
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), p)
    if jax.tree.map(lambda a: (a.shape, a.dtype), want) != got:
        raise spec.SpecError("drawn weights do not match the program's "
                             "parameter layout")
    return p


# --------------------------------------------------------------- the run --


def device_info(require_chip: bool, chips: int) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    d = devices[0]
    if require_chip and (d.platform == "cpu" or len(devices) < chips):
        raise NoChip(f"platform {d.platform}, {len(devices)} device(s); "
                     f"the cell needs {chips} accelerator chip(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def _records(trace_reqs, stream) -> Tuple[List[Req], List[Step],
                                          List[Admission]]:
    reqs = [Req(r.rid, np.asarray(r.prompt), r.max_new_tokens, r.arrival_s,
                r.admitted_s, r.first_token_s, r.finish_s, r.state.value,
                list(r.tokens)) for r in trace_reqs]
    by_rid = {r.rid: r for r in reqs}
    seen: Dict[str, int] = {}
    groups: Dict[float, List[Tuple[str, int, bool]]] = {}
    for rid, n, t in stream.bursts:
        first = rid not in seen
        groups.setdefault(t, []).append((rid, n, first))
        seen[rid] = seen.get(rid, 0) + n
    steps, admissions = [], []
    emitted: Dict[str, int] = {}
    for t in sorted(groups):
        g = groups[t]
        if all(first for _, _, first in g):
            admissions.append(Admission(
                by_rid[g[0][0]].admitted_s, t,
                [len(by_rid[rid].prompt) for rid, _, _ in g]))
        else:
            rows = [(len(by_rid[rid].prompt) + emitted.get(rid, 0), n)
                    for rid, n, _ in g if n]
            if rows:
                # one live sequence always has budget for the whole horizon
                # (the scheduler picks K <= the largest remaining budget)
                steps.append(Step(t, max(n for _, n in rows), rows))
        for rid, n, _ in g:
            emitted[rid] = emitted.get(rid, 0) + n
    return reqs, steps, admissions


def _peak_memory() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def sample(reqs: List[Req], seed: int) -> List[Req]:
    """Finished requests to compare: the longest, then others drawn from the
    seed, until the sample holds enough requests and served tokens."""
    done = [r for r in reqs if r.done]
    if not done:
        return []
    order = generator.rng_for(seed, 2).permutation(len(done))
    longest = max(done, key=lambda r: (len(r.tokens), len(r.prompt)))
    picked = [longest]
    for i in order:
        r = done[i]
        if r is longest:
            continue
        if (len(picked) >= SAMPLE_MIN
                and sum(len(p.tokens) for p in picked) >= SAMPLE_TOKENS) \
                or len(picked) >= SAMPLE_MAX:
            break
        picked.append(r)
    return picked


def compare(cell: spec.Cell, s: Dict, seed: int, picked: List[Req],
            control: bool) -> Dict[str, float]:
    """The plain reference over each sampled prompt and its served tokens:
    the widest gap of a served token below the reference's best (and, with
    ``control``, of the float8 forward's first choice)."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(
        f"chipbench.references.{cell.config['reference']}")
    w = weights.draw(s, seed)
    fn = jax.jit(lambda w, t: ref.gaps(w, s, t, control=control))
    max_len = int(cell.traffic["max_len"])
    out = {"max_gap": 0.0, "compared_tokens": 0}
    if control:
        out["max_gap_fp8"] = 0.0
    for r in picked:
        seq = np.zeros((max_len,), np.int32)
        full = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        seq[: len(full)] = full
        g = jax.device_get(fn(w, jnp.asarray(seq)))
        lo, hi = len(r.prompt) - 1, len(full) - 1
        out["max_gap"] = max(out["max_gap"], float(g["gap"][lo:hi].max()))
        out["compared_tokens"] += hi - lo
        if control:
            out["max_gap_fp8"] = max(out["max_gap_fp8"],
                                     float(g["gap_fp8"][lo:hi].max()))
    del w
    return out


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             control: bool = False, log=None) -> Dict[str, Any]:
    """One run; returns the result line's object and, under ``"run"``, the
    record the metric readers took it from.  With ``control`` the float8
    control is compared in the program's place, and ``correct`` is its
    verdict (the program's own reading stays under ``"readings"``)."""
    import jax

    from repro.models import build_model
    from repro.runtime import Runtime, RuntimeConfig
    from repro.serving import Request

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    device = device_info(require_chip, cell.chips)
    clock = CompileClock()
    s = spec.sizes(cell.config)
    cfg = spec.program_config(cell.config)
    model = build_model(cfg)
    params = program_params(weights.draw(s, seed), model)
    t = cell.traffic
    arrivals = generator.make_requests(t, vocab=s["vocab"], seed=seed,
                                       seconds=seconds)
    trace_reqs = [Request(a.rid, a.prompt, a.max_new_tokens,
                          arrival_s=a.due_s) for a in arrivals]
    stream = _stream_class()()
    stop = threading.Event() if t["window_end"] == "stop_intake" else None
    at_open: Dict[str, float] = {}
    prof_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None

    def on_open():
        at_open.update(compile_s=clock.seconds, programs=clock.programs)
        if trace:
            # host annotations only: the Python tracer would slow the host
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(xtrace.OPEN):
                pass

    def on_close():
        # the trace is cut here; it is written after the drain, so writing
        # it stalls no request
        at_open.update(close_programs=clock.programs)
        if trace:
            with jax.profiler.TraceAnnotation(xtrace.CLOSE):
                pass

    wclock = WindowClock(seconds, stop, on_open, on_close)
    rt = Runtime(RuntimeConfig())
    try:
        res = rt.serve(cfg, trace_reqs, mode="continuous", model=model,
                       params=params, slots=int(t["slots"]),
                       max_len=int(t["max_len"]), eos_id=-1, pad_id=0,
                       paged=True, block_size=int(t["block_size"]),
                       stream=stream, now_fn=wclock, stop_event=stop)
        if wclock.closed_at is None:  # everything drained inside the window
            wclock.closed_at = time.perf_counter() - wclock.t0
            on_close()
        if trace:
            jax.profiler.stop_trace()
        peak_bytes = _peak_memory()
        reqs, steps, admissions = _records(trace_reqs, stream)
        run = Run(cell=cell, sizes=s,
                  peak=costs.peak(device["kind"]) if require_chip else
                  costs.peak("TPU v5 lite"),
                  seconds=seconds, setup_s=wclock.t0 - t_start,
                  setup_compile_s=at_open["compile_s"],
                  window_compiles=(at_open["close_programs"]
                                   - at_open["programs"]),
                  requests=reqs, bursts=list(stream.bursts), steps=steps,
                  admissions=admissions)
        del res, params, trace_reqs
        gc.collect()
        if trace:
            run.trace_end_s = wclock.closed_at
            t_read = time.perf_counter()
            run.trace = _summarize_trace(prof_dir, run)
            log(f"trace read in {time.perf_counter() - t_read:.1f} s")
    finally:
        if prof_dir is not None:
            shutil.rmtree(prof_dir, ignore_errors=True)

    log(f"window: {seconds} s, compiles inside it: {run.window_compiles}; "
        f"set-up {run.setup_s:.3f} s, of it compiling "
        f"{run.setup_compile_s:.3f} s")
    attempted = run.attempted()
    failed = [r for r in attempted if not r.done]
    picked = sample(attempted, seed)
    t_ref = time.perf_counter()
    readings = compare(cell, s, seed, picked, control) if picked else {}
    t_ref = time.perf_counter() - t_ref
    incomplete = sum(1 for r in attempted
                     if not r.done or len(r.tokens) != r.max_new)
    log(f"requests: {len(run.requests)} in the mix, "
        f"{len(run.due_in_window())} due in the window, {len(attempted)} "
        f"attempted, {len(failed)} failed; {len(run.steps)} macro-steps, "
        f"{len(run.admissions)} admissions; compared "
        f"{readings.get('compared_tokens', 0)} served tokens of "
        f"{len(picked)} requests in {t_ref:.1f} s")
    if run.trace is not None:
        tr = run.trace
        log(f"trace: window {tr.window_s:.3f} s, busy {tr.busy_s:.3f} s, "
            f"programs {tr.module_count} taking {tr.module_s} s; the "
            f"stream saw {len(run.traced_steps())} macro-steps in it")
    for line in window_profile(run):
        log(line)
    checks = correctness(cell, readings, incomplete, bool(picked),
                         control=control)

    chosen = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in chosen:
        v = spec.metric_reader(m.name)(run)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    device["memory_peak_bytes"] = peak_bytes
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": len(attempted), "failed": len(failed),
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {
            "device_ops": [[n, v] for n, v in run.trace.top_ops],
            "idle_gaps": [[n, v] for n, v in run.trace.idle_gaps]}
    out["checks"] = checks
    out["run"] = run
    out["readings"] = readings
    return out


def correctness(cell: spec.Cell, readings: Dict[str, float],
                incomplete: int, compared: bool,
                control: bool = False) -> Dict[str, Dict]:
    """Each number compared, with its limit from ``limits/<cell>.json``.
    A run that finished no request has compared nothing, and fails.  The
    control's gap stands in the program's place where ``control`` is set."""
    lim = cell.limits
    if "max_gap" not in lim:
        raise spec.SpecError(f"no max_gap limit for {cell.name}")
    gap = readings.get("max_gap_fp8" if control else "max_gap")
    return {
        "max_gap": {"value": gap if compared else math.inf,
                    "limit": float(lim["max_gap"]["limit"])},
        "incomplete": {"value": incomplete, "limit": 0},
    }


def window_profile(run: Run) -> List[str]:
    """Lines for standard error that show how the window went: each
    attempted request's TTFT, the decode step's wall time in each quarter
    of the window (macro-steps with no admission before them), and the
    longest pause between two stream bursts."""
    ttft = sorted(round(1e3 * (r.first_s - r.due_s))
                  for r in run.attempted() if r.first_s is not None)
    quarters: List[List[float]] = [[], [], [], []]
    for a, b in zip(run.steps, run.steps[1:]):
        if b.t < run.seconds and not any(a.t <= ad.t_admit < b.t
                                         for ad in run.admissions):
            quarters[int(4 * b.t / run.seconds)].append(
                1e3 * (b.t - a.t) / b.k)
    stamps = sorted({t for _, _, t in run.bursts if t < run.seconds})
    pause = max(((b - a, a) for a, b in zip(stamps, stamps[1:])),
                default=(0.0, 0.0))
    return [f"ttft ms of attempted requests: {ttft}",
            "decode step wall ms by window quarter: "
            + str([round(sum(q) / len(q), 2) if q else None
                   for q in quarters]),
            f"longest pause between bursts: {pause[0]:.3f} s at "
            f"{pause[1]:.3f} s"]


def _summarize_trace(prof_dir: str, run: Run) -> Optional[xtrace.Summary]:
    import glob

    paths = glob.glob(f"{prof_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        return None
    devices, marks = xtrace.load(paths[0])
    if not devices:
        return None
    return xtrace.summarize(devices, marks, label=lambda a, b: gap_label(
        run, a, b))


def gap_label(run: Run, a: float, b: float) -> str:
    """What the host was doing in a device-idle gap from ``a`` to ``b``
    seconds after the window opened."""
    if any(a - 1e-3 <= ad.t_admit <= b for ad in run.admissions):
        return "before a prefill (admission)"
    busy = any(r.admitted_s is not None and r.admitted_s <= a
               and (r.finish_s is None or r.finish_s > a)
               for r in run.requests)
    waiting = any(r.due_s <= a and (r.admitted_s is None or r.admitted_s > a)
                  for r in run.requests)
    if busy:
        return "between macro-steps (host parse and dispatch)"
    if waiting:
        return "queued requests, engine between loop turns"
    return "waiting for arrivals"


def use_compile_cache(path: str) -> None:
    """JAX's persistent compilation cache at ``path``, every program kept,
    so a cell's second run finds all it compiles there."""
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
