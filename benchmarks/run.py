"""Benchmark harness — one module per paper table/figure + framework extras.

  matmul_crossover — paper Fig. 2 (serial/parallel crossover over order)
  sort_pivots      — paper Table 3 (pivot strategies; imbalance on 8 devices)
  wkv_chunk        — fork-join chunk sweep for the RWKV6 recurrence
  kernels_bench    — Pallas kernels (interpret) vs XLA oracles + the
                     autotuner's measured block-shape search (tuned vs
                     static-default configs, warm-cache proof); writes the
                     machine-readable perf trajectory BENCH_kernels.json
  roofline_table   — renders §Roofline from results/dryrun_*.json (if present)
  cost_ledger      — CostEngine predicted-vs-measured ledger, v5e datasheet
                     vs backend-calibrated constants (decision flips + table)
                     + autotune prior-vs-measured-optimum deltas
  serving_bench    — static-batch vs continuous-batching serving under a
                     staggered arrival trace (tok/s + p50/p95 latency,
                     token-equivalence anchor, site=serve ledger rows),
                     plus sharded / paged-KV / shared-prefix full-load
                     rows; writes the machine-readable BENCH_serving.json
  stress_bench     — overload (2x Poisson) + fault-injection drills
                     (raise | nan | stall) against the request lifecycle:
                     every request terminal, transient faults retry to a
                     token-identical finish; writes the SLO row under
                     BENCH_serving.json's "stress" key
  chaos_bench      — closed-loop recovery drill: perturb the calibrated
                     HardwareSpec 4x + noisy measurements, prove decisions
                     at three serve sites reconverge to the unperturbed
                     verdicts within a bounded measurement budget (token
                     identity intact, corrections persisted across a
                     Runtime restart); writes BENCH_serving.json's
                     "chaos" key

Every suite is a thin adapter over the public Runtime API: ``run(csv=True,
runtime=None)`` receives the session (engine + caches + ledger) from this
harness (or ``repro.Runtime().bench(...)``).  Prints ``name,key=value,...``
CSV lines.  Run:
  PYTHONPATH=src python -m benchmarks.run [--only NAME] [--list]
"""

import argparse
import sys
import time
import traceback

# static: --list and --only validation must not import jax-heavy suites
SUITE_NAMES = (
    "matmul_crossover",
    "sort_pivots",
    "wkv_chunk",
    "kernels_bench",
    "roofline_table",
    "cost_ledger",
    "serving_bench",
    "stress_bench",
    "chaos_bench",
)


def _suites():
    from benchmarks import (
        chaos_bench,
        cost_ledger,
        kernels_bench,
        matmul_crossover,
        roofline_table,
        serving_bench,
        sort_pivots,
        stress_bench,
        wkv_chunk,
    )

    suites = {
        "matmul_crossover": matmul_crossover.run,
        "sort_pivots": sort_pivots.run,
        "wkv_chunk": wkv_chunk.run,
        "kernels_bench": kernels_bench.run,
        "roofline_table": roofline_table.run,
        "cost_ledger": cost_ledger.run,
        "serving_bench": serving_bench.run,
        "stress_bench": stress_bench.run,
        "chaos_bench": chaos_bench.run,
    }
    assert set(suites) == set(SUITE_NAMES)
    return suites


def run_suites(runtime, only=None):
    """Run all suites (or just ``only``) against ``runtime``; returns the
    names of failed suites.  Unknown ``only`` raises KeyError — running
    zero suites is an error, never a silent success."""
    suites = _suites()
    if only is not None:
        if only not in suites:
            raise KeyError(
                f"unknown suite {only!r}; available: {', '.join(SUITE_NAMES)}")
        suites = {only: suites[only]}
    failed = []
    for name, fn in suites.items():
        print(f"### {name}")
        t0 = time.time()
        try:
            fn(runtime=runtime)
            print(f"### {name} done in {time.time() - t0:.1f}s\n")
        except Exception:
            traceback.print_exc()
            failed.append(name)
    _print_drift(runtime)
    return failed


def _print_drift(runtime) -> None:
    """Calibration-drift summary over everything the suites just measured:
    per-site geometric-mean measured/predicted ratio from the CostEngine
    ledger, with RAW drift (outside the site's configured band) called out
    alongside the live correction factor and whether it absorbs the drift
    (``resolved``) — the open question a DRIFTING flag leaves behind is
    exactly what the closed loop (DESIGN.md §10) answers."""
    try:
        drift = runtime.engine.drift_report()
    except Exception:
        traceback.print_exc()
        return
    if not drift:
        return
    print("### calibration drift (measured/predicted, trailing window)")
    for site, row in sorted(drift.items()):
        if row.get("drifting"):
            flag = ("  DRIFTING(resolved)" if row.get("resolved")
                    else "  DRIFTING")
        else:
            flag = ""
        ratio = row.get("geomean_ratio", float("nan"))
        print(f"drift,site={site},geomean_ratio={ratio:.3g},"
              f"raw_ratio={row.get('raw_ratio', float('nan')):.3g},"
              f"correction={row.get('correction', 1.0):.3g},"
              f"rows={row.get('n', 0)}{flag}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"run a single suite; one of: {', '.join(SUITE_NAMES)}")
    ap.add_argument("--list", action="store_true",
                    help="list available suites and exit")
    args = ap.parse_args()

    if args.list:
        print("\n".join(SUITE_NAMES))
        return
    if args.only is not None and args.only not in SUITE_NAMES:
        ap.error(f"unknown suite {args.only!r}; "
                 f"available: {', '.join(SUITE_NAMES)}")

    from repro.launch.process import enable_compile_cache
    from repro.runtime import Runtime, RuntimeConfig

    enable_compile_cache()
    runtime = Runtime(RuntimeConfig.from_env())
    failed = run_suites(runtime, only=args.only)
    if failed:
        print(f"FAILED suites: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
