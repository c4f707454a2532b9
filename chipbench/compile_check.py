#!/usr/bin/env python3
"""Compile each cell's hot programs for a described TPU v5e, without a chip,
and print what ``memory_analysis()`` says they hold on the device.

    JAX_PLATFORMS=cpu python3 chipbench/compile_check.py [--cell NAME]
        [--slots N [N ...]] [--max-len L [L ...]]

For each cell: the continuous engine's batched paged prefill at the cell's
padded prompt shape (every admission pads to the longest prompt of the mix)
and its decode macro-step at the largest horizon, 32.  Nothing runs; the
compiler refuses what would not fit or not compile on the chip.
``--slots`` and ``--max-len`` compile the cell at other slot counts and
slot lengths, to choose them.
"""

from __future__ import annotations

import argparse
import itertools
import math
import os
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import spec  # noqa: E402

GIB = 2**30


def programs(cell, slots: int, one_chip, max_len=None):
    """(name, jitted fn, argument shapes) of the prefill and macro-step."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.serving.scheduler import ServeScheduler
    from repro.training.step import (make_batched_prefill,
                                     make_decode_macro_step)

    cfg = spec.program_config(cell.config)
    model = build_model(cfg)
    t = cell.traffic
    max_len = int(max_len or t["max_len"])
    bs = int(t["block_size"])
    blocks = slots * math.ceil(max_len / bs) + 1
    max_blocks = math.ceil(max_len / bs)
    pad = min(int(t["prompt_tokens"]["max"]), max_len - 1)
    chunk, _ = ServeScheduler(cfg, max_len=max_len).prefill_chunk(
        pad, active_decodes=0)
    n_chunks = -(-pad // chunk)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    state = on_chip(jax.eval_shape(lambda: model.init_decode_state(
        slots, max_len, per_slot=True, paging=(blocks, bs))))
    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    mask = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    prefill = jax.jit(make_batched_prefill(model), donate_argnums=(1,))
    macro = jax.jit(make_decode_macro_step(model, 32, eos_id=-1, pad_id=0),
                    donate_argnums=(1,))
    return [
        (f"prefill {n_chunks}x{slots}x{chunk}", prefill,
         (params, state, i32(n_chunks, slots, chunk), i32(slots), i32(slots),
          i32(slots, max_blocks))),
        ("macro-step K=32", macro,
         (params, state, i32(slots), mask, i32(slots),
          i32(slots, max_blocks))),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", action="append",
                    help="cell name (default: every cell)")
    ap.add_argument("--slots", type=int, nargs="+",
                    help="slot counts to compile instead of the cell's own")
    ap.add_argument("--max-len", type=int, nargs="+",
                    help="slot lengths to compile instead of the cell's own")
    args = ap.parse_args(argv)
    sys.path.insert(1, str(spec.ROOT / "src"))

    import json

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    names = args.cell or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = spec.load_cell(name, bench=bench)
        for slots, max_len in itertools.product(
                args.slots or [int(cell.traffic["slots"])],
                args.max_len or [int(cell.traffic["max_len"])]):
            for label, fn, shapes in programs(cell, slots, one_chip, max_len):
                t0 = time.perf_counter()
                try:
                    m = fn.lower(*shapes).compile().memory_analysis()
                except Exception as e:  # the compiler's refusal is the answer
                    print(f"{name} slots={slots} max_len={max_len} {label}: "
                          f"refused: {str(e).splitlines()[0][:200]}",
                          flush=True)
                    continue
                total = (m.argument_size_in_bytes + m.output_size_in_bytes
                         + m.temp_size_in_bytes - m.alias_size_in_bytes)
                print(f"{name} slots={slots} max_len={max_len} {label}: "
                      f"arguments "
                      f"{m.argument_size_in_bytes / GIB:.2f} GiB, outputs "
                      f"{m.output_size_in_bytes / GIB:.2f} GiB (aliased "
                      f"{m.alias_size_in_bytes / GIB:.2f}), temporaries "
                      f"{m.temp_size_in_bytes / GIB:.2f} GiB, total "
                      f"{total / GIB:.2f} GiB; compiled in "
                      f"{time.perf_counter() - t0:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
