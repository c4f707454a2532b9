"""Random weights of a configuration, drawn from the seed on the device in one
jitted call, in the type they are served in.

The arrays are the benchmark's input, like the prompts: the harness hands
them to the program in its layout (``program_params``), and the plain
reference draws them again from the same seed once the program is gone.
The configuration's family names the leaves and their shapes
(``leaf_shapes``); the rule that draws them is the same for every family:
leaf ``i`` of the sorted names from ``fold_in(key, i)``, matrices in the
served type at fan-in scale (embedding and output head at 0.02), each
RMSNorm gain ``1 + g`` with ``g`` a float32 offset.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench import spec


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: low 32 bits, high bits folded in."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _draw(leaves: Dict[str, tuple], dtype, key) -> Dict[str, jax.Array]:
    out = {}
    for i, (name, (shape, fan)) in enumerate(sorted(leaves.items())):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if fan == "norm":
            out[name] = 0.1 * z
        else:
            scale = 0.02 if fan is None else fan ** -0.5
            out[name] = (z * scale).astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def _drawer(family, frozen_sizes):
    s = dict(frozen_sizes)
    return jax.jit(functools.partial(_draw, family.leaf_shapes(s),
                                     jnp.dtype(s["dtype"])))


def draw(family, s: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight of ``family`` at sizes ``s`` for ``seed``, in one device
    program."""
    return _drawer(family, tuple(sorted(s.items())))(seed_key(seed))


def shapes(family, s: Dict) -> Dict[str, jax.ShapeDtypeStruct]:
    """The shapes and types ``draw`` gives, with nothing drawn."""
    return jax.eval_shape(_drawer(family, tuple(sorted(s.items()))),
                          seed_key(0))


def program_params(family, w: Dict, model) -> Dict:
    """The drawn weights ``w`` (arrays or shapes) in the program's parameter
    layout, as the family maps them, checked leaf by leaf against what
    ``model.init`` would make."""
    p = family.program_params(w, model)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if (jax.tree.map(lambda a: (a.shape, a.dtype), want)
            != jax.tree.map(lambda a: (a.shape, a.dtype), p)):
        raise spec.SpecError("drawn weights do not match the program's "
                             "parameter layout")
    return p
