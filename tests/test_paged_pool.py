"""The paged pool stacked over layers is written in place by the layer scan.

* each scan layout that stacks a pool leaf (the uniform scan, the expert
  scan after a leading dense layer, a period scan with attention
  positions) compiles its decode macro-step and its batched prefill with
  no copy of the stacked pool and with temporaries below one layer's
  share of it, however large the pool
* the paged helpers addressed at a layer of a stacked pool equal, bit for
  bit, the same helper on that layer's pool written back: the new rows
  only, a masked row's writes in the null block, prefill chunks too
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn_lib
from repro.models import build_model
from repro.models.transformer import POOL_LEAVES
from repro.training.step import make_batched_prefill, make_decode_macro_step

SLOTS, MAX_LEN, BLOCK = 2, 16, 4
MAX_BLOCKS = MAX_LEN // BLOCK
# spare pages, far more than the slots' tables reach, so the pool's bytes
# dominate everything else a program holds
N_BLOCKS = 256 * SLOTS * MAX_BLOCKS + 1

# layout -> (architecture, overrides of its reduced configuration)
LAYOUTS = {
    "uniform": ("phi3-mini-3.8b", {"n_layers": 4}),
    "lead": ("moonshot-v1-16b-a3b", {}),  # latent pool ``pl``
    "period": ("recurrentgemma-2b",
               {"n_layers": 4, "block_pattern": ("rglru", "attn")}),
}


def _stacked_pools(state):
    """The pool leaves that carry a leading layer (or group) axis."""
    return [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
        state)[0] if getattr(path[-1], "key", None) in POOL_LEAVES
        and leaf.ndim == (5 if path[-1].key in ("pk", "pv") else 4)]


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_layer_scan_writes_the_pool_in_place(layout):
    arch, overrides = LAYOUTS[layout]
    cfg = dataclasses.replace(get_config(arch).reduced(), **overrides)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: model.init_decode_state(
        SLOTS, MAX_LEN, per_slot=True, paging=(N_BLOCKS, BLOCK)))
    pools = _stacked_pools(state)
    assert pools, f"{layout}: no pool stacked over layers"
    pool = pools[0]
    # one layer's (or period group's) pool
    layer_bytes = pool.size * pool.dtype.itemsize // pool.shape[0]
    shape = re.escape(",".join(map(str, pool.shape)))
    pool_copy = re.compile(rf"= \w+\[{shape}\]\S* copy\(")

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    macro = jax.jit(make_decode_macro_step(model, 4, eos_id=0, pad_id=0),
                    donate_argnums=(1,))
    prefill = jax.jit(make_batched_prefill(model), donate_argnums=(1,))
    programs = {
        "macro": macro.lower(params, state, i32(SLOTS),
                             jax.ShapeDtypeStruct((SLOTS,), jnp.bool_),
                             i32(SLOTS), i32(SLOTS, MAX_BLOCKS)),
        "prefill": prefill.lower(params, state, i32(2, SLOTS, BLOCK),
                                 i32(SLOTS), i32(SLOTS),
                                 i32(SLOTS, MAX_BLOCKS)),
    }
    for name, lowered in programs.items():
        compiled = lowered.compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < layer_bytes, (layout, name, temp, layer_bytes)
        copies = pool_copy.findall(compiled.as_text())
        assert not copies, (layout, name, copies)


def _pool_case(kind, rng):
    """(stacked pools, new entries' shape) of a pool kind."""
    n_layers, n_blocks = 3, 9
    if kind == "kv":
        shape = (n_layers, n_blocks, BLOCK, 2, 8)
        pools = tuple(jnp.asarray(rng.standard_normal(shape), jnp.float32)
                      for _ in range(2))
        return pools, (2, 8)
    width = 40  # padded to one 128-lane tile in the pool
    shape = (n_layers, n_blocks, BLOCK, attn_lib.latent_pool_width(width))
    return (jnp.asarray(rng.standard_normal(shape), jnp.float32),), (width,)


def _update(kind, pools, new, pos, tables, layer=None):
    if kind == "kv":
        return attn_lib.paged_update_kv_cache(*pools, *new, pos, tables,
                                              BLOCK, layer)
    return (attn_lib.paged_update_latent(*pools, *new, pos, tables, BLOCK,
                                         layer),)


def _gather(kind, pools, tables, width, layer=None):
    if kind == "kv":
        return attn_lib.paged_gather_kv(*pools, tables, BLOCK, layer)
    return (attn_lib.paged_gather_latent(*pools, tables, BLOCK, width,
                                         layer),)


@pytest.mark.parametrize("kind", ["kv", "latent"])
@pytest.mark.parametrize("s_new", [1, 5])
def test_layer_indexed_helpers_equal_the_per_layer_call(kind, s_new):
    rng = np.random.default_rng(s_new)
    pools, entry = _pool_case(kind, rng)
    # row 1 is masked: its table is zeroed, so it writes the null block
    tables = jnp.asarray([[3, 5, 1], [0, 0, 0], [8, 2, 4]], jnp.int32)
    pos = jnp.asarray([2, 6, 7], jnp.int32)  # row 2 crosses a page end
    new = tuple(jnp.asarray(rng.standard_normal((3, s_new) + entry),
                            jnp.float32)
                for _ in pools)
    layer = 1

    @jax.jit
    def stacked(pools, new, at):
        out = _update(kind, pools, new, pos, tables, at)
        return out, _gather(kind, out, tables, entry[-1], at)

    got, got_view = stacked(pools, new, jnp.int32(layer))
    one = _update(kind, tuple(p[layer] for p in pools), new, pos, tables)
    want = tuple(p.at[layer].set(o) for p, o in zip(pools, one))
    want_view = _gather(kind, one, tables, entry[-1])
    for g, w, p in zip(got, want, pools):
        np.testing.assert_array_equal(g, w)
        # other layers untouched; in this layer, only the pages the live
        # rows' tables hold and the null block changed
        changed = np.nonzero(np.any(
            np.asarray(g != p).reshape(p.shape[:2] + (-1,)), -1))
        assert set(changed[0]) == {layer}
        assert set(changed[1]) <= {0, 2, 3, 4, 5}
        assert 0 in set(changed[1])  # the masked row's writes
    for g, w in zip(got_view, want_view):
        assert g.shape == (3, 3 * BLOCK) + entry
        np.testing.assert_array_equal(g, w)
    if kind == "latent":  # a written row's pad columns hold zeros
        assert not np.any(np.asarray(got[0])[layer, 3, 2, entry[-1]:])
