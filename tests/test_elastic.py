"""Elastic rescaling: a checkpoint written under one mesh restores onto a
different mesh (different device count) — the pod-count-change scenario."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _run(body, devices):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


def test_checkpoint_elastic_reshard(tmp_path):
    # train 3 steps on a 4-device (2,2) mesh, checkpoint
    out1 = _run(f"""
        import jax, numpy as np
        from repro.configs import get_config
        from repro.models import build_model
        from repro.data import SyntheticLMData
        from repro.training import TrainLoopConfig, init_train_state, make_train_step
        from repro.distributed.sharding import param_shardings, batch_sharding
        from repro.checkpoint import save

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = get_config("tinyllama-1.1b").reduced()
        model = build_model(cfg)
        loop = TrainLoopConfig()
        state = init_train_state(model, jax.random.PRNGKey(0), loop)
        psh = param_shardings(jax.eval_shape(lambda: state), mesh)
        state = jax.device_put(state, psh)
        ds = SyntheticLMData(cfg, seq_len=16, global_batch=4)
        step = jax.jit(make_train_step(model, loop))
        for i in range(3):
            state, m = step(state, ds.batch_at(i))
        save(r"{tmp_path}", 3, state)
        print("LOSS1", float(m["loss"]))
    """, devices=4)
    loss1 = float(out1.split("LOSS1")[1].strip())

    # restore on an 8-device (4,2) mesh and take the SAME 4th step
    out2 = _run(f"""
        import jax, numpy as np
        from repro.configs import get_config
        from repro.models import build_model
        from repro.data import SyntheticLMData
        from repro.training import TrainLoopConfig, init_train_state, make_train_step
        from repro.distributed.sharding import param_shardings
        from repro.checkpoint import restore_resharded

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("tinyllama-1.1b").reduced()
        model = build_model(cfg)
        loop = TrainLoopConfig()
        like = init_train_state(model, jax.random.PRNGKey(0), loop)
        psh = param_shardings(jax.eval_shape(lambda: like), mesh)
        state = restore_resharded(r"{tmp_path}", 3, like, psh)
        assert int(np.asarray(state["step"])) == 3
        ds = SyntheticLMData(cfg, seq_len=16, global_batch=4)
        step = jax.jit(make_train_step(model, loop))
        state, m = step(state, ds.batch_at(3))
        print("LOSS2", float(m["loss"]))
    """, devices=8)
    loss2 = float(out2.split("LOSS2")[1].strip())
    # same data, same restored state -> the next step's loss is well-defined
    import numpy as np

    assert np.isfinite(loss2)
