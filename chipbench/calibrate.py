#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: one process runs a
cell on many seeds with the float8 control in the program's place, and
reads, on each, the program's widest gap below the plain reference and the
control's.

    python3 chipbench/calibrate.py --workload NAME --seconds S --seeds N ...

Not part of a measured run.  Prints one line per seed (the control's run
has to read ``correct`` false) and, at the end, the lower reading (the
program's largest) and the upper (the control's smallest).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from run import CACHE, ROOT  # noqa: F401  (sets sys.path and the cache)

from chipbench import harness, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    prog, ctrl, caught = [], [], []
    harness.use_compile_cache(CACHE)
    for seed in args.seeds:
        out = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, t_start=time.perf_counter(),
                               control=True)
        r = out["readings"]
        prog.append(r["max_gap"])
        ctrl.append(r["max_gap_fp8"])
        caught.append(not out["correct"])
        print(json.dumps({"seed": seed, **r,
                          "control_correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "peak": out["device"]["memory_peak_bytes"]}),
              flush=True)
    print(json.dumps({"workload": cell.name, "seeds": len(args.seeds),
                      "lower": max(prog), "upper": min(ctrl),
                      "control_caught": sum(caught),
                      "program": sorted(prog), "control": sorted(ctrl)}),
          flush=True)
    return 0 if all(caught) else 1


if __name__ == "__main__":
    sys.exit(main())
