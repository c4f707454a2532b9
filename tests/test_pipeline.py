"""Pipeline parallelism: GPipe schedule equals sequential stage application."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.distributed.pipeline import best_microbatch_count, pipeline_bubble_fraction

REPO = Path(__file__).resolve().parent.parent


def test_bubble_fraction():
    assert pipeline_bubble_fraction(1, 8) == 0.0
    assert pipeline_bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert pipeline_bubble_fraction(4, 29) == pytest.approx(3 / 32)


def test_best_microbatch_count():
    assert best_microbatch_count(1, 1024) == 1
    m = best_microbatch_count(4, 1024, bubble_budget=0.1)
    assert pipeline_bubble_fraction(4, m) <= 0.1
    assert pipeline_bubble_fraction(4, m - 1) > 0.1


def test_gpipe_matches_sequential():
    body = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import gpipe

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        S, M, mb, d = 4, 6, 3, 8
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (S, d, d)) * 0.3
        bs = jax.random.normal(jax.random.fold_in(key, 1), (S, d)) * 0.1
        x = jax.random.normal(jax.random.fold_in(key, 2), (M, mb, d))

        def stage(params, h):
            w, b = params
            return jnp.tanh(h @ w + b)

        out = gpipe(stage, (ws, bs), x, mesh, "pod")
        # sequential reference
        ref = x
        for s in range(S):
            ref = stage((ws[s], bs[s]), ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
        print("PIPELINE_OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "PIPELINE_OK" in proc.stdout
