"""Paper Table 3: quicksort pivot strategies, serial vs parallel.

TPU adaptation: distributed sample sort; the paper's pivot strategies become
splitter strategies.  Two measurements:

  * serial wall time (XLA sort, CPU) at the paper's element counts,
  * parallel execution on 8 placeholder devices (subprocess — the main bench
    process stays single-device): per-strategy bucket imbalance, the
    quantity that makes random/left/right pivots slow (paper's observation),
    plus predicted v5e times from the overhead model.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

PAPER_NS = (1000, 1100, 1500, 2000)  # paper Table 3 element counts
BIG_NS = (100_000, 1_000_000)

_SUBPROC = r"""
import jax, jax.numpy as jnp, numpy as np, json
from repro.core.sort import distributed_sort, PIVOT_STRATEGIES
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
out = {}
for n in %NS%:
    x = jax.random.normal(jax.random.PRNGKey(1), (n,))
    ref = np.sort(np.asarray(x))
    per = {}
    for pivot in PIVOT_STRATEGIES:
        res, rep = distributed_sort(x, mesh, "data", pivot=pivot, force_parallel=True)
        assert np.array_equal(np.asarray(res), ref)
        per[pivot] = rep.imbalance
    out[str(n)] = per
print("JSON:" + json.dumps(out))
"""


def run(csv=True, runtime=None):
    from repro.runtime import default_runtime

    rt = runtime if runtime is not None else default_runtime()
    om = rt.engine.model  # the session's analytic model (v5e by default)
    rows = []
    # serial measurement (the paper's 'serial' column)
    for n in PAPER_NS + BIG_NS:
        x = jax.random.normal(jax.random.PRNGKey(0), (n,))
        f = jax.jit(jnp.sort)
        f(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(5):
            f(x).block_until_ready()
        serial_us = (time.perf_counter() - t0) / 5 * 1e6
        pred_par = om.sort_cost(n, chips=8, strategy="parallel").total * 1e6
        pred_ser = om.sort_cost(n, strategy="serial").total * 1e6
        rows.append({"n": n, "serial_measured_us": serial_us,
                     "v5e_serial_us": pred_ser, "v5e_parallel8_us": pred_par})
        if csv:
            print(f"sort_serial,n={n},measured={serial_us:.1f}us,"
                  f"v5e_serial={pred_ser:.2f}us,v5e_par8={pred_par:.2f}us")
    # parallel imbalance per pivot strategy (subprocess, 8 devices)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # a CPU mesh rehearsal: the child must never reach for the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    code = _SUBPROC.replace("%NS%", str(list(PAPER_NS)))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode == 0:
        data = json.loads(proc.stdout.split("JSON:")[1])
        for n, per in data.items():
            if csv:
                print("sort_pivot_imbalance,n=" + n + "," +
                      ",".join(f"{k}={v:.2f}" for k, v in per.items()))
        rows.append({"imbalance": data})
    else:
        print("sort_pivots subprocess failed:", proc.stderr[-500:])
    return rows


if __name__ == "__main__":
    run()
