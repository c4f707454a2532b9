"""Serving benchmark: static-batch vs continuous batching (CPU-reduced
config) — a thin adapter over ``Runtime.serve``.

Two traces over the same request set:

  staggered   — arrivals every GAP_MS; the latency story (continuous
                batching wins p50/p95 because nobody waits for the batch)
  full-load   — everything arrives at t=0; the throughput story (the
                macro-step decode hot path closes the gap to the static
                lockstep bound: host consulted once per K tokens, batched
                group prefill, donated in-place decode buffers)

plus a SHARDED full-load row: the same trace on a forced
``{data:1, model:8}`` CPU mesh in a subprocess (shard verdict forced —
the reduced config sits below the serve_shard crossover), token-checked
against the single-device static baseline, with per-trace collective
counts and the serve_shard ledger rows reported,

plus a PAGED full-load row: the same trace with the KV cache stored as
fixed-size pages behind per-slot block tables (block_size=4 so the
8-token prompts span multiple pages), token-checked against the dense
continuous run and reported as a machine-normalized paged/dense
throughput ratio,

plus a SHARED-PREFIX row: every request opens with the same 6-token
prefix (system-prompt traffic); with the radix prefix cache pinned on
(``prefix_cache="force"`` — the reduced config sits below the
serve_prefix crossover, so 'auto' would honestly full-prefill) only the
first request prefills the prefix and the rest reuse its pages, cutting
prefilled tokens >=2x, with the serve_prefix ledger rows reported.

Reports aggregate tok/s and per-request p50/p95 latency for both engines on
both traces, verifies the token-for-token equivalence anchor on the shared
request set, records the continuous engine's host-sync / device-dispatch
counts per trace, and appends the run to the machine-readable perf
TRAJECTORY in ``BENCH_serving.json`` so the overhead reduction is
comparable across PRs.  With ``check_regression=True`` (CI smoke: ``python
benchmarks/serving_bench.py --check-regression``) the run FAILS if the
equivalence anchor breaks or full-load continuous throughput — normalized
by the same machine's static bound, so the gate is robust to runner speed
— falls more than 20% below the committed ratio.  Everything runs on the
prior/analytic path (no measurement loops beyond the traces themselves),
so the suite stays tier-1 fast.  The suite builds its OWN Runtime — two
sessions have isolated ledgers, so the serve rows below are exactly this
suite's decisions regardless of what the harness ran before.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.runtime import Runtime, RuntimeConfig, synthetic_trace

BENCH_JSON = "BENCH_serving.json"
TRAJECTORY_TAG = "pr9-frontend-ipc"
REGRESSION_FRACTION = 0.8  # fail below 80% of the committed baseline
# the paged/dense ratio divides two ~10ms walls, so runner noise moves it
# far more than the static-normalized ratio — wider guard, same idea
PAGED_REGRESSION_FRACTION = 0.5

ARCH = "tinyllama-1.1b"
REQUESTS = 6
PROMPT_LEN = 8
MAX_NEW = 8
SLOTS = 3
GAP_MS = 10.0
# the sharded full-load row runs in a subprocess with a forced N-device CPU
# mesh (jax pins its device count at first init, so the parent process
# cannot host it)
SHARD_DEVICES = 8
# paged rows: small pages so the 8-token prompts span several of them,
# and a shared 6-token prefix = one full page + a 2-token copy-on-write
# tail at block_size=4
BLOCK_SIZE = 4
PREFIX_LEN = 6
# the shared-prefix row used to serialize admission (1 slot): group
# prefill is ONE dispatch and trie lookups precede it, so requests
# admitted in the same group could not see each other's pages.  The
# scheduler now SPLITS an admission group when the trie predicts a
# within-group prefix overlap (the donor prefills first, the overlapping
# members re-queue and hit its pages), so the row runs at full SLOTS and
# the hit rate no longer depends on 1-slot serialization
PREFIX_SLOTS = SLOTS


def _trace(cfg, *, arrival: str, prefix_share: float = 0.0):
    return synthetic_trace(
        REQUESTS, prompt_len=PROMPT_LEN, max_new=MAX_NEW,
        vocab_size=cfg.vocab_size, arrival=arrival, gap_ms=GAP_MS, seed=0,
        prefix_share=prefix_share,
        prefix_len=PREFIX_LEN if prefix_share else 0)


def _engine_dict(res) -> dict:
    d = {"tok_per_s": res.tok_per_s, "p50_s": res.p50_s, "p95_s": res.p95_s}
    if res.report is not None:
        d["host_syncs"] = res.report.host_syncs
        d["device_dispatches"] = res.report.device_dispatches
        d["host_syncs_per_token"] = res.report.host_syncs_per_token
    return d


def _report_dict(report) -> dict:
    pct = report.latency_percentiles()
    return {
        "tok_per_s": report.tok_per_s,
        "p50_s": pct["p50"],
        "p95_s": pct["p95"],
        "host_syncs": report.host_syncs,
        "device_dispatches": report.device_dispatches,
        "host_syncs_per_token": report.host_syncs_per_token,
    }


# child script for the sharded full-load row: continuous engine on a
# {data:1, model:N} mesh with the shard verdict FORCED (the reduced CPU
# config sits below the analytic crossover, so 'auto' would replicate and
# exercise nothing) — the auto verdict is still queried and reported.
# Emits one SHARDED_JSON line on stdout for the parent to embed.
_SHARDED_CHILD = r"""
import json, sys
import jax, numpy as np
from repro.configs import get_config
from repro.models import build_model
from repro.runtime import Runtime, synthetic_trace
from repro.serving.scheduler import ServeScheduler

arch, requests, prompt_len, max_new, slots = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]))
cfg = get_config(arch).reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
rt = Runtime()
max_len = prompt_len + max_new
trace = lambda: synthetic_trace(
    requests, prompt_len=prompt_len, max_new=max_new,
    vocab_size=cfg.vocab_size, arrival="all", seed=0)
_, auto_dec = ServeScheduler(cfg, rt.engine, max_len=max_len).serve_shard(
    slots, tp=jax.device_count())
res = rt.serve(cfg, trace(), mode="continuous", slots=slots,
               mesh_shape={"data": 1, "model": jax.device_count()},
               shard_params="shard", model=model, params=params,
               max_len=max_len, eos_id=0)
rep = res.report
for _ in range(2):  # best-of-3, same as the parent's full-load timing
    r2 = res.engine.run(trace())
    if r2.tok_per_s > rep.tok_per_s:
        rep = r2
rows = [e for e in rt.ledger.entries if e.site == "serve_shard"]
print("SHARDED_JSON:" + json.dumps({
    "devices": jax.device_count(),
    "mesh_shape": rep.mesh_shape,
    "tok_per_s": rep.tok_per_s,
    "host_syncs_per_token": rep.host_syncs_per_token,
    "collective_ops": rep.collective_ops,
    "auto_choice": auto_dec.choice,
    "serve_shard_rows": len(rows),
    "serve_shard_measured": sum(
        1 for e in rows if e.measured_s is not None),
    "outputs": [rep.output(f"r{i}", max_new).tolist()
                for i in range(requests)],
}))
"""


def _sharded_row(static_out: np.ndarray) -> dict:
    """Run the forced-mesh child and verify its greedy decode is
    token-identical to THIS process's single-device static baseline."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{SHARD_DEVICES}").strip()
    src = str(Path(__file__).resolve().parent.parent / "src")
    # a CPU mesh rehearsal: the child must never reach for the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_CHILD, ARCH, str(REQUESTS),
         str(PROMPT_LEN), str(MAX_NEW), str(SLOTS)],
        capture_output=True, text=True, env=env, timeout=900)
    if proc.returncode != 0:
        raise AssertionError(
            f"sharded serve subprocess failed:\n{proc.stderr[-2000:]}")
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("SHARDED_JSON:"))
    row = json.loads(line[len("SHARDED_JSON:"):])
    sharded_out = np.asarray(row.pop("outputs"), np.int32)
    row["token_identical"] = bool(np.array_equal(sharded_out, static_out))
    return row


def _load_previous() -> dict:
    try:
        with open(BENCH_JSON) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _trajectory(previous: dict, entry: dict) -> list:
    """Append this run to the cross-PR perf trajectory (replacing an
    earlier run with the same tag).  A pre-trajectory BENCH_serving.json
    seeds the list with its per-token-loop numbers so the macro-step win
    is visible against PR 3/4."""
    traj = list(previous.get("trajectory", []))
    if not traj and "continuous" in previous:
        traj.append({
            "tag": "pr4-per-token-loop",
            "staggered_continuous_tok_per_s":
                previous["continuous"].get("tok_per_s"),
            "full_load_continuous_tok_per_s": None,
            "host_syncs_per_token": 1.0,  # one sync per generated token
        })
    traj = [t for t in traj if t.get("tag") != entry["tag"]]
    traj.append(entry)
    return traj


def run(csv=True, runtime=None, check_regression: bool = False) -> None:
    # own session => fresh ledger: serve rows are this suite's.  The online
    # correction loop is live: argmin sweeps are invariant under its uniform
    # per-site scaling, so decisions (and tokens) are untouched — but the
    # drift gate below can require any out-of-band site to be absorbed.
    rt = Runtime(RuntimeConfig(corrections=True))
    previous = _load_previous()
    cfg = get_config(ARCH).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len = PROMPT_LEN + MAX_NEW

    common = dict(model=model, params=params, max_len=max_len, eos_id=0)

    # --- staggered trace: the latency story ---
    static_st = rt.serve(cfg, _trace(cfg, arrival="staggered"), mode="static",
                         **common)
    cont_st = rt.serve(cfg, _trace(cfg, arrival="staggered"),
                       mode="continuous", slots=SLOTS, **common)

    # --- full-load trace: the throughput story (and equivalence anchor:
    # identical request set, so outputs must match the static run) ---
    static_fl = rt.serve(cfg, _trace(cfg, arrival="all"), mode="static",
                         **common)
    cont_fl = rt.serve(cfg, _trace(cfg, arrival="all"), mode="continuous",
                       slots=SLOTS, **common)
    # best-of-3 on the already-compiled engine: the per-trace wall is a few
    # ms, so a single OS scheduling hiccup can halve the reported tok/s
    fl_report = cont_fl.report
    for _ in range(2):
        rep = cont_fl.engine.run(_trace(cfg, arrival="all"))
        if rep.tok_per_s > fl_report.tok_per_s:
            fl_report = rep
    static_out = np.stack([static_fl.outputs[f"r{i}"] for i in range(REQUESTS)])
    cont_out = np.stack([fl_report.output(f"r{i}", MAX_NEW)
                         for i in range(REQUESTS)])
    token_identical = bool(np.array_equal(static_out, cont_out))

    # --- sharded full-load row: same trace on a forced {data:1, model:N}
    # CPU mesh in a subprocess, token-checked against THIS process's
    # single-device static baseline ---
    sharded = _sharded_row(static_out)

    # --- paged full-load row: same trace, KV stored as fixed-size pages
    # behind per-slot block tables; must be token-identical to dense ---
    paged_fl = rt.serve(cfg, _trace(cfg, arrival="all"), mode="continuous",
                        slots=SLOTS, paged=True, block_size=BLOCK_SIZE,
                        **common)
    paged_report = paged_fl.report
    for _ in range(4):  # best-of-5: the ratio below divides two tiny walls
        rep = paged_fl.engine.run(_trace(cfg, arrival="all"))
        if rep.tok_per_s > paged_report.tok_per_s:
            paged_report = rep
    dense_best = fl_report.tok_per_s
    for _ in range(2):  # top the dense side up to best-of-5 as well
        rep = cont_fl.engine.run(_trace(cfg, arrival="all"))
        dense_best = max(dense_best, rep.tok_per_s)
    paged_out = np.stack([paged_report.output(f"r{i}", MAX_NEW)
                          for i in range(REQUESTS)])
    paged_identical = bool(np.array_equal(paged_out, static_out))
    paged_row = _report_dict(paged_report)
    paged_row.update({
        "block_size": BLOCK_SIZE,
        "live_tokens": paged_report.live_tokens,
        "reserved_blocks": paged_report.reserved_blocks,
        "token_identical": paged_identical,
        # normalized by the dense continuous run on the same machine, so
        # the regression gate below is robust to runner speed
        "paged_over_dense": (paged_report.tok_per_s / dense_best
                             if dense_best > 0 else None),
    })

    # --- shared-prefix row: every request opens with the same PREFIX_LEN
    # tokens; with reuse pinned on, only the first request prefills the
    # prefix — the rest pin its pages and prefill just their suffix ---
    static_px = rt.serve(cfg, _trace(cfg, arrival="all", prefix_share=1.0),
                         mode="static", **common)
    prefix_fl = rt.serve(cfg, _trace(cfg, arrival="all", prefix_share=1.0),
                         mode="continuous", slots=PREFIX_SLOTS, paged=True,
                         block_size=BLOCK_SIZE, prefix_cache="force",
                         **common)
    px_report = prefix_fl.report
    px_static_out = np.stack([static_px.outputs[f"r{i}"]
                              for i in range(REQUESTS)])
    px_out = np.stack([px_report.output(f"r{i}", MAX_NEW)
                       for i in range(REQUESTS)])
    px_identical = bool(np.array_equal(px_out, px_static_out))
    prefix_rows = [e for e in rt.ledger.entries if e.site == "serve_prefix"]
    total_prompt = REQUESTS * PROMPT_LEN
    prefix_row = {
        "prefix_len": PREFIX_LEN,
        "prefix_share": 1.0,
        "slots": PREFIX_SLOTS,
        "tok_per_s": px_report.tok_per_s,
        "prefilled_tokens": px_report.prefilled_tokens,
        "prefix_hit_tokens": px_report.prefix_hit_tokens,
        "prefix_hit_rate": px_report.prefix_hit_rate,
        "cow_count": px_report.cow_count,
        # prefill reduction vs the hit-less bound (every request prefills
        # its full prompt): the >=2x acceptance anchor
        "prefill_reduction": (total_prompt / px_report.prefilled_tokens
                              if px_report.prefilled_tokens > 0 else None),
        "token_identical": px_identical,
        "serve_prefix_rows": len(prefix_rows),
        "serve_prefix_measured": sum(
            1 for e in prefix_rows if e.measured_s is not None),
    }

    serve_rows = [e for e in rt.ledger.entries
                  if e.site in ("serve", "serve_macro")]
    measured = [e for e in serve_rows if e.measured_s is not None]

    result = {
        "arch": ARCH,
        "trace": {"requests": REQUESTS, "prompt_len": PROMPT_LEN,
                  "max_new": MAX_NEW, "slots": SLOTS, "gap_ms": GAP_MS},
        "static": _engine_dict(static_st),
        "continuous": _engine_dict(cont_st),
        "full_load": {
            "static": _engine_dict(static_fl),
            "continuous": _report_dict(fl_report),
            "continuous_over_static":
                fl_report.tok_per_s / static_fl.tok_per_s
                if static_fl.tok_per_s > 0 else None,
            "sharded": sharded,
            "paged": paged_row,
        },
        "shared_prefix": prefix_row,
        "p50_speedup": (static_st.p50_s / cont_st.p50_s
                        if cont_st.p50_s > 0 else None),
        "token_identical": token_identical,
        "serve_ledger_rows": len(serve_rows),
        "serve_ledger_measured": len(measured),
    }
    # stress_bench / chaos_bench own these keys; carry them forward
    for theirs in ("stress", "chaos"):
        if theirs in previous:
            result[theirs] = previous[theirs]
    result["trajectory"] = _trajectory(previous, {
        "tag": TRAJECTORY_TAG,
        "staggered_continuous_tok_per_s": cont_st.tok_per_s,
        "full_load_continuous_tok_per_s": fl_report.tok_per_s,
        "host_syncs_per_token": fl_report.host_syncs_per_token,
        "sharded_full_load_tok_per_s": sharded["tok_per_s"],
        "paged_full_load_tok_per_s": paged_report.tok_per_s,
        "prefix_hit_rate": px_report.prefix_hit_rate,
    })
    with open(BENCH_JSON, "w") as f:
        json.dump(result, f, indent=1)

    for name, res in (("static", static_st), ("continuous", cont_st)):
        print(f"serving_bench,trace=staggered,engine={name},"
              f"tok_s={res.tok_per_s:.1f},p50_ms={res.p50_s*1e3:.1f},"
              f"p95_ms={res.p95_s*1e3:.1f}")
    print(f"serving_bench,trace=full_load,engine=static,"
          f"tok_s={static_fl.tok_per_s:.1f}")
    print(f"serving_bench,trace=full_load,engine=continuous,"
          f"tok_s={fl_report.tok_per_s:.1f},"
          f"syncs_per_tok={fl_report.host_syncs_per_token:.3f},"
          f"dispatches={fl_report.device_dispatches}")
    print(f"serving_bench,trace=full_load,engine=sharded,"
          f"mesh=model:{SHARD_DEVICES},tok_s={sharded['tok_per_s']:.1f},"
          f"collectives={sharded['collective_ops']},"
          f"auto_choice={sharded['auto_choice']},"
          f"shard_rows={sharded['serve_shard_rows']},"
          f"shard_measured={sharded['serve_shard_measured']},"
          f"token_identical={sharded['token_identical']}")
    print(f"serving_bench,trace=full_load,engine=paged,"
          f"block_size={BLOCK_SIZE},tok_s={paged_report.tok_per_s:.1f},"
          f"paged_over_dense={paged_row['paged_over_dense']:.2f},"
          f"live_tokens={paged_report.live_tokens},"
          f"blocks={paged_report.reserved_blocks},"
          f"token_identical={paged_identical}")
    print(f"serving_bench,trace=shared_prefix,engine=paged,"
          f"prefix_len={PREFIX_LEN},"
          f"hit_tokens={px_report.prefix_hit_tokens},"
          f"hit_rate={px_report.prefix_hit_rate:.2f},"
          f"prefilled={px_report.prefilled_tokens},"
          f"reduction={prefix_row['prefill_reduction']:.2f},"
          f"cow={px_report.cow_count},"
          f"prefix_rows={len(prefix_rows)},"
          f"prefix_measured={prefix_row['serve_prefix_measured']},"
          f"token_identical={px_identical}")
    print(f"serving_bench,token_identical={token_identical},"
          f"serve_rows={len(serve_rows)},measured={len(measured)},"
          f"json={BENCH_JSON}")
    if not token_identical:
        raise AssertionError(
            "continuous engine diverged from the static baseline")
    if not sharded["token_identical"]:
        raise AssertionError(
            "sharded continuous engine diverged from the single-device "
            "static baseline")
    if not paged_identical:
        raise AssertionError(
            "paged continuous engine diverged from the dense baseline")
    if not px_identical:
        raise AssertionError(
            "shared-prefix paged run diverged from the static baseline "
            "on the same trace (prefix reuse changed the decode)")
    if prefix_row["prefill_reduction"] is None \
            or prefix_row["prefill_reduction"] < 2.0:
        raise AssertionError(
            f"shared-prefix trace prefilled {px_report.prefilled_tokens} "
            f"of {total_prompt} prompt tokens — reuse below the 2x "
            f"reduction anchor")
    if check_regression:
        _check_regression(previous, result["full_load"],
                          result["shared_prefix"])
        # drift gate: this run's measured rows must leave no site out of
        # band without the correction loop absorbing it — meaningful only
        # when the spec was calibrated against THIS backend (a datasheet
        # spec on a different machine drifts by construction)
        if rt.engine.calibration is not None:
            rt.engine.assert_drift_resolved()
            print("serving_bench,drift_check=ok")
        else:
            print("serving_bench,drift_check=skipped_uncalibrated")


def _check_regression(previous: dict, full_load: dict,
                      shared_prefix: dict) -> None:
    """CI smoke gate, three metrics against the committed baseline:

      continuous_over_static — full-load continuous throughput RELATIVE to
        the static lockstep bound on the same machine.  Normalizing by the
        static run cancels absolute machine speed (a CI runner 2x slower
        than the machine that committed the baseline slows both engines
        alike), so the gate trips on real serve-path regressions, not
        runner lottery.
      paged_over_dense — paged continuous throughput relative to the dense
        continuous run, machine-normalized the same way: the cost of the
        block-table indirection must not creep.
      prefix_hit_rate — fraction of prompt tokens served from the radix
        prefix cache on the shared-prefix trace.  Deterministic for a
        fixed trace, but held to the same 80% floor so a benign change in
        admission grouping doesn't flap CI.

    Each gate is skipped when the committed file predates its metric."""
    checks = (
        ("continuous_over_static", REGRESSION_FRACTION,
         previous.get("full_load", {}).get("continuous_over_static"),
         full_load.get("continuous_over_static")),
        ("paged_over_dense", PAGED_REGRESSION_FRACTION,
         previous.get("full_load", {}).get("paged", {}).get(
             "paged_over_dense"),
         full_load.get("paged", {}).get("paged_over_dense")),
        ("prefix_hit_rate", REGRESSION_FRACTION,
         previous.get("shared_prefix", {}).get("prefix_hit_rate"),
         shared_prefix.get("prefix_hit_rate")),
    )
    failures = []
    for name, fraction, base, ratio in checks:
        if base is None or ratio is None:
            print(f"serving_bench,regression_check=skipped,metric={name} "
                  f"(no committed baseline)")
            continue
        floor = fraction * base
        status = "ok" if ratio >= floor else "FAIL"
        print(f"serving_bench,regression_check={status},metric={name},"
              f"value={ratio:.2f},committed={base:.2f},floor={floor:.2f}")
        if ratio < floor:
            failures.append(
                f"{name} regressed: {ratio:.2f} < {floor:.2f} "
                f"({int(fraction * 100)}% of the committed {base:.2f})")
    if failures:
        raise AssertionError("; ".join(failures))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--check-regression", action="store_true",
                    help="fail if token equivalence breaks or any gated "
                         "metric (continuous/static ratio, paged/dense "
                         "ratio, prefix hit rate) drops >20%% below the "
                         f"committed {BENCH_JSON}")
    args = ap.parse_args()
    run(check_regression=args.check_regression)
