"""Frontend smoke: the multi-process serving front end must be a pure
host-side wrapper — 2 pinned intake/emission workers, streaming on — that
changes NOTHING about what the engine generates (CPU-reduced config).

Three serves over the same full-load trace:

  in-process — the continuous engine exactly as serving_bench runs it;
               the token reference
  frontend   — the same trace submitted through ``frontend=2, pin=True,
               stream=True``: request validation happens in spawned intake
               workers, token bursts are detokenized in a pinned emission
               worker, and the engine thread never blocks on either
  small pages — the frontend again, over 4-token KV pages instead of the
               engine's default 16, proving the front end does not depend
               on the page layout

Hard checks (this suite is a gate, not a report): every run terminal and
fully COMPLETED; both frontend runs token-identical to the in-process
reference; the emission transcript (``ServeResult.texts``) detokenizes
exactly the engine's tokens; streamed-token accounting consistent
(``streamed_tokens`` == generated tokens, TTFT percentiles finite); and
the serve_ipc cost site ledgered BOTH ops (workers, coalesce) with
predicted AND measured rows — the eleventh calibrated site is live, not
decorative.  The suite builds its OWN Runtime so the serve_ipc rows below
are exactly this suite's decisions.

CI smoke: ``python benchmarks/frontend_smoke.py`` (no flags — the checks
are unconditional; there is no committed baseline because every check is
exact, not a ratio).
"""

from __future__ import annotations

import math

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.runtime import Runtime, RuntimeConfig, synthetic_trace

ARCH = "tinyllama-1.1b"
REQUESTS = 6
PROMPT_LEN = 8
MAX_NEW = 8
SLOTS = 3
WORKERS = 2
BLOCK_SIZE = 4


def _trace(cfg):
    return synthetic_trace(
        REQUESTS, prompt_len=PROMPT_LEN, max_new=MAX_NEW,
        vocab_size=cfg.vocab_size, arrival="all", seed=0)


def _assert_completed(report, label: str) -> None:
    states = report.state_counts()
    if not report.all_terminal or states.get("COMPLETED", 0) != REQUESTS:
        raise AssertionError(f"{label}: expected {REQUESTS} COMPLETED, "
                             f"got {states}")


def _check_frontend_run(res, base_outputs, label: str) -> None:
    rep = res.report
    _assert_completed(rep, label)
    for rid, ref in base_outputs.items():
        if not np.array_equal(res.outputs[rid], ref):
            raise AssertionError(
                f"{label}: tokens for {rid} diverged from the in-process "
                f"engine — the front end changed generation")
    if rep.frontend_workers != WORKERS:
        raise AssertionError(
            f"{label}: expected {WORKERS} intake workers, report says "
            f"{rep.frontend_workers}")
    if rep.ipc_messages <= 0 or rep.ipc_bytes <= 0:
        raise AssertionError(
            f"{label}: no IPC traffic accounted "
            f"(messages={rep.ipc_messages}, bytes={rep.ipc_bytes})")
    if rep.streamed_tokens != REQUESTS * MAX_NEW:
        raise AssertionError(
            f"{label}: streamed {rep.streamed_tokens} tokens, engine "
            f"generated {REQUESTS * MAX_NEW}")
    if rep.stream_events < REQUESTS:
        raise AssertionError(
            f"{label}: only {rep.stream_events} stream bursts for "
            f"{REQUESTS} requests")
    ttft = rep.ttft_percentiles()
    if not all(math.isfinite(v) and v >= 0 for v in ttft.values()):
        raise AssertionError(f"{label}: non-finite TTFT percentiles {ttft}")
    if res.texts is None or set(res.texts) != set(base_outputs):
        raise AssertionError(
            f"{label}: emission transcript missing requests "
            f"(got {sorted(res.texts or ())})")
    for rid, ref in base_outputs.items():
        want = " ".join(str(int(t)) for t in ref)
        if res.texts[rid] != want:
            raise AssertionError(
                f"{label}: transcript text for {rid} is not the "
                f"detokenized engine output")


def run(csv=True, runtime=None) -> None:
    # own session => the serve_ipc rows below are ours (corrections on:
    # the loop must not change a single token for this gate to pass)
    rt = Runtime(RuntimeConfig(corrections=True))
    cfg = get_config(ARCH).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    common = dict(model=model, params=params, max_len=PROMPT_LEN + MAX_NEW,
                  eos_id=0, mode="continuous", slots=SLOTS)

    base = rt.serve(cfg, _trace(cfg), **common)
    _assert_completed(base.report, "in-process reference")
    base_outputs = {f"r{i}": np.asarray(base.outputs[f"r{i}"])
                    for i in range(REQUESTS)}

    fe = rt.serve(cfg, _trace(cfg), frontend=WORKERS, pin=True,
                  stream=True, **common)
    _check_frontend_run(fe, base_outputs, "frontend (16-token pages)")

    fe_small = rt.serve(cfg, _trace(cfg), frontend=WORKERS, pin=True,
                        stream=True, block_size=BLOCK_SIZE, **common)
    _check_frontend_run(fe_small, base_outputs, "frontend (4-token pages)")

    # --- the eleventh cost site must have ledgered, for BOTH ops, a
    # decision row (predicted) AND an appended measured row ---
    ipc_rows = [e for e in rt.ledger.entries if e.site == "serve_ipc"]
    for op in ("workers", "coalesce"):
        rows = [e for e in ipc_rows if e.query.get("op") == op]
        measured = [e for e in rows if e.measured_s is not None]
        if not rows or not measured:
            raise AssertionError(
                f"serve_ipc op={op!r}: expected decision + measured ledger "
                f"rows, got {len(rows)} rows / {len(measured)} measured")
        if any(e.predicted_s is None or e.predicted_s <= 0 for e in rows):
            raise AssertionError(
                f"serve_ipc op={op!r}: a ledger row has no positive "
                f"predicted cost")

    for label, res in (("pages16", fe), ("pages4", fe_small)):
        rep = res.report
        ttft = rep.ttft_percentiles()
        print(f"frontend_smoke,engine={label},workers={rep.frontend_workers},"
              f"ipc_msgs={rep.ipc_messages},ipc_bytes={rep.ipc_bytes},"
              f"streamed={rep.streamed_tokens},bursts={rep.stream_events},"
              f"ttft_p50_ms={ttft['ttft_p50']*1e3:.1f},"
              f"ttft_p99_ms={ttft['ttft_p99']*1e3:.1f}")
    w_rows = [e for e in ipc_rows if e.query.get("op") == "workers"]
    c_rows = [e for e in ipc_rows if e.query.get("op") == "coalesce"]
    print(f"frontend_smoke,site=serve_ipc,rows={len(ipc_rows)},"
          f"workers_measured="
          f"{sum(1 for e in w_rows if e.measured_s is not None)},"
          f"coalesce_measured="
          f"{sum(1 for e in c_rows if e.measured_s is not None)}")
    # drift gate only bites on a spec calibrated against THIS backend;
    # datasheet-spec runs drift by construction and prove nothing
    if rt.engine.calibration is not None:
        rt.engine.assert_drift_resolved()
    print("frontend_smoke,token_identical=True,transcript_identical=True,"
          "drift_check="
          + ("ok" if rt.engine.calibration is not None
             else "skipped_uncalibrated"))


if __name__ == "__main__":
    run()
