#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Makes the weights and the requests from ``--seed``, warms up every shape
the cell uses, measures for ``--seconds``, then checks what the window
served against the plain reference.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.  The last line of standard output is one JSON object;
the numbers that decide ``correct`` are the last lines of standard error.
Exits non-zero, with no result, where JAX finds no accelerator or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)  # import chipbench.*, not its files as top level
sys.path.insert(1, str(ROOT / "src"))
# the persistent compile cache lives in the checkout, at a fixed path
CACHE = str(ROOT / ".jax_cache")
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def finite(v):
    """JSON has no infinity: a failed request's latency is the largest
    double instead."""
    return v if not isinstance(v, float) or math.isfinite(v) \
        else sys.float_info.max


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness, spec

    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"error: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    harness.use_compile_cache(CACHE)
    try:
        out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                               trace=bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    out.pop("run")
    out.pop("readings")
    for m in out["metrics"].values():
        m["value"] = finite(m["value"])
    checks = out["checks"]
    for name, c in checks.items():
        c["value"] = finite(c["value"])
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
