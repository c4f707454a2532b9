"""Random weights of a configuration, drawn from the seed on the device in one
jitted call, in the type they are served in.

The arrays are the benchmark's input, like the prompts: the harness hands
them to the program in its layout (``harness.program_params``), and the
plain reference draws them again from the same seed once the program is
gone.  Matrices are bfloat16 at fan-in scale (embedding and output head at
0.02); each RMSNorm gain is ``1 + g`` with ``g`` a float32 offset.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp


def leaf_shapes(s: Dict) -> Dict[str, tuple]:
    """name -> (shape, scale): ``scale`` is the fan-in of a matrix, None for
    the 0.02 tables, ``"norm"`` for a gain offset."""
    L, d, h, kv, hd, f, v = (s["layers"], s["d"], s["heads"], s["kv_heads"],
                             s["head_dim"], s["ff"], s["vocab"])
    return {
        "embed": ((v, d), None),
        "lm_head": ((v, d), None),
        "final_norm": ((d,), "norm"),
        "attn_norm": ((L, d), "norm"),
        "wq": ((L, d, h, hd), d),
        "wk": ((L, d, kv, hd), d),
        "wv": ((L, d, kv, hd), d),
        "wo": ((L, h * hd, d), h * hd),
        "mlp_norm": ((L, d), "norm"),
        "w_gate": ((L, d, f), d),
        "w_up": ((L, d, f), d),
        "w_down": ((L, f, d), f),
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: low 32 bits, high bits folded in."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _draw(s: Dict, key) -> Dict[str, jax.Array]:
    dtype = jnp.dtype(s["dtype"])
    out = {}
    for i, (name, (shape, fan)) in enumerate(sorted(leaf_shapes(s).items())):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if fan == "norm":
            out[name] = 0.1 * z
        else:
            scale = 0.02 if fan is None else fan ** -0.5
            out[name] = (z * scale).astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def _drawer(frozen_sizes):
    return jax.jit(functools.partial(_draw, dict(frozen_sizes)))


def draw(s: Dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight of sizes ``s`` for ``seed``, in one device program."""
    return _drawer(tuple(sorted(s.items())))(seed_key(seed))
