"""Compile every Pallas kernel family, and the serve hot path, at real
widths for a described TPU v5e.

Interpret-mode tests check the kernels' numerics but not what only the
chip's compiler enforces: the (8, 128) block tiling rule, the primitives
Mosaic can lower, the scoped VMEM limit, and whether a program fits HBM.
Each test here lowers and compiles one kernel, ``interpret=False``, through
the block shape the tuner would choose, or one serve program, for one chip
of a ``v5e:2x2`` topology that is described, not attached.  Nothing runs.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and under
several test workers only the worker given this file must load it.  The
persistent compilation cache is off around the compiles: what a described
chip compiles cannot be read back without one.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.costs.autotune import Autotuner
from repro.hw import V5E
from repro.kernels import tuning
from repro.kernels.bitonic_sort import bitonic_sort_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.wkv import wkv_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        assert topo.devices[0].device_kind == "TPU v5 lite"
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        compilation_cache.reset_cache()
        jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def tuner(tmp_path_factory):
    # the analytic priors, never measured, in a cache of the test's own
    return Autotuner(cache_dir=tmp_path_factory.mktemp("tune"), measure=False)


def _compile_kernel(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, compiled
    return compiled


@pytest.mark.parametrize("m,n,k,dtype", [
    (4096, 8192, 3072, jnp.bfloat16),  # phi3-mini FFN widths
    (2048, 2048, 2048, jnp.float32),
])
def test_matmul_compiles(one_chip, tuner, m, n, k, dtype):
    c = tuning.tune_matmul(m, n, k, dtype, interpret=False, tuner=tuner,
                           hw=V5E).config
    block = (c["bm"], c["bn"], c["bk"])
    _compile_kernel(
        functools.partial(matmul_pallas, block_shape=block, interpret=False),
        jax.ShapeDtypeStruct((m, k), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((k, n), dtype, sharding=one_chip))


@pytest.mark.parametrize("head_dim", [96, 128])
def test_flash_attention_compiles(one_chip, tuner, head_dim):
    bh, s = 32, 4096
    c = tuning.tune_flash(bh, s, s, head_dim, jnp.bfloat16, causal=True,
                          interpret=False, tuner=tuner, hw=V5E).config
    x = jax.ShapeDtypeStruct((bh, s, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    _compile_kernel(
        functools.partial(flash_attention_pallas, causal=True,
                          block_q=c["block_q"], block_kv=c["block_kv"],
                          interpret=False), x, x, x)


def test_wkv_compiles_at_rwkv6_3b_widths(one_chip, tuner):
    cfg = get_config("rwkv6-3b")
    n = cfg.rnn_head_dim
    bh, s = cfg.d_model // n, 4096
    chunk = tuning.tune_wkv(bh, s, n, jnp.bfloat16, interpret=False,
                            tuner=tuner, hw=V5E).config["chunk"]
    x = jax.ShapeDtypeStruct((bh, s, n), jnp.bfloat16, sharding=one_chip)
    u = jax.ShapeDtypeStruct((bh, n), jnp.bfloat16, sharding=one_chip)
    _compile_kernel(
        functools.partial(wkv_pallas, chunk=chunk, interpret=False),
        x, x, x, x, u)


@pytest.mark.parametrize("rows,n", [(8, 4096), (8, 65536)])
def test_bitonic_sort_compiles(one_chip, tuner, rows, n):
    block_rows = tuning.tune_sort(rows, n, jnp.float32, interpret=False,
                                  tuner=tuner, hw=V5E).config["block_rows"]
    _compile_kernel(
        functools.partial(bitonic_sort_pallas, block_rows=block_rows,
                          interpret=False),
        jax.ShapeDtypeStruct((rows, n), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("program", ["prefill", "macro_step"])
def test_phi3_serve_program_compiles_and_fits(one_chip, program):
    """The continuous engine's paged prefill and decode macro-step for
    phi3-mini-3.8b at its published widths (4 slots of 576 tokens, 16-token
    pages) compile for one chip, and weights, pool and temporaries fit its
    HBM."""
    from repro.serving.paging import default_kv_blocks
    from repro.training.step import (make_batched_prefill,
                                     make_decode_macro_step)
    from repro.models import build_model

    model = build_model(get_config("phi3-mini-3.8b"))
    slots, max_len, page = 4, 576, 16
    pages = default_kv_blocks(slots, max_len, page)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    state = on_chip(jax.eval_shape(lambda: model.init_decode_state(
        slots, max_len, per_slot=True, paging=(pages, page))))
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    tables = ints(slots, -(-max_len // page))
    if program == "prefill":
        fn = make_batched_prefill(model)
        args = (ints(2, slots, 256), ints(slots), ints(slots), tables)
    else:
        fn = make_decode_macro_step(model, 8, eos_id=-1, pad_id=0)
        args = (ints(slots), jax.ShapeDtypeStruct((slots,), bool,
                                                  sharding=one_chip),
                ints(slots), tables)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, state, *args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= V5E.hbm_bytes, used
