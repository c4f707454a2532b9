"""Distributed sample sort demo — the paper's quicksort study on a mesh.

Standalone script: owns the process, so it forces 8 placeholder devices
(like the dry-run does with 512) BEFORE importing jax.

Run:  PYTHONPATH=src python examples/distributed_sort.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import OverheadModel  # noqa: E402
from repro.core.sort import PIVOT_STRATEGIES, distributed_sort  # noqa: E402


def main():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    om = OverheadModel()
    print(f"devices: {len(jax.devices())}; "
          f"v5e sort crossover @8 chips: n >= {om.sort_crossover_n(8)}")

    x = jnp.exp(jax.random.normal(jax.random.PRNGKey(0), (20_000,)))  # skewed
    ref = np.sort(np.asarray(x))

    print(f"{'pivot':>10s} {'correct':>8s} {'imbalance':>10s}   (paper Table 3: "
          f"random pivots worst)")
    for pivot in PIVOT_STRATEGIES:
        out, rep = distributed_sort(x, mesh, "data", pivot=pivot,
                                    force_parallel=True)
        ok = np.array_equal(np.asarray(out), ref)
        print(f"{pivot:>10s} {str(ok):>8s} {rep.imbalance:>10.2f}")

    # the overhead-managed path: small n -> serial, huge n -> parallel
    small, rep_s = distributed_sort(jnp.arange(100.0)[::-1], mesh, "data")
    print(f"\nadaptive: n=100 -> {rep_s.strategy} (overhead says serial wins)")


if __name__ == "__main__":
    main()
