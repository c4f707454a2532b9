"""Plain reference of a dense decoder-only transformer of the Llama / Mistral /
Phi-3 family, written from the published description and importing nothing
of the program under test.

Per layer: ``x += o(attn(rope(q(n1(x))), rope(k(n1(x))), v(n1(x))))``, then
``x += down(silu(gate(n2(x))) * up(n2(x)))``, with ``n`` an RMSNorm of gain
``1 + g``; attention is causal, grouped-query (query head ``i`` reads
key/value head ``i // (heads / kv_heads)``), scaled by ``head_dim ** -0.5``;
rotary embedding turns the two halves of each head (``rotate_half``) by
``pos / rope_theta ** (2j / head_dim)``.  A final RMSNorm and an untied
output head give the logits.

Everything is float32 with every matrix product at ``Precision.HIGHEST``,
one sequence at a time, layer by layer under ``lax.scan``, so only one
layer's weights are ever widened to float32.  ``fp8=True`` computes every
matrix product from operands rounded to float8 e4m3 (one scale per tensor,
max 448): the control that a comparison with this reference must catch.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def to_e4m3(t: jax.Array) -> jax.Array:
    """Round to the float8 e4m3 grid after scaling the tensor's largest
    magnitude to 448: 3 mantissa bits, subnormals below 2^-6."""
    t = t.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / E4M3_MAX
    x = t / scale
    _, e = jnp.frexp(x)  # x = m * 2^e, 0.5 <= |m| < 1
    step = jnp.exp2((jnp.maximum(e, -5) - 4).astype(F32))
    return jnp.clip(jnp.round(x / step) * step, -E4M3_MAX, E4M3_MAX) * scale


def _mm(a, b, fp8: bool):
    if fp8:
        a, b = to_e4m3(a), to_e4m3(b)
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(F32))


def _rope(x, theta):
    """x (S, H, hd): rotate_half rotary embedding at positions 0..S-1."""
    s, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s: Dict, fp8: bool, x, lw):
    n, d = x.shape
    h, kv, hd = s["heads"], s["kv_heads"], s["head_dim"]
    mm = functools.partial(_mm, fp8=fp8)
    a = _rmsnorm(x, lw["attn_norm"], s["eps"])
    q = mm(a, lw["wq"].reshape(d, h * hd)).reshape(n, h, hd)
    k = mm(a, lw["wk"].reshape(d, kv * hd)).reshape(n, kv, hd)
    v = mm(a, lw["wv"].reshape(d, kv * hd)).reshape(n, kv, hd)
    q, k = _rope(q, s["rope_theta"]), _rope(k, s["rope_theta"])
    group = h // kv
    k = jnp.repeat(k, group, axis=1)  # query head i reads kv head i // group
    v = jnp.repeat(v, group, axis=1)
    scores = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) * hd ** -0.5
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = mm(p, v.transpose(1, 0, 2)).transpose(1, 0, 2).reshape(n, h * hd)
    x = x + mm(att, lw["wo"])
    m = _rmsnorm(x, lw["mlp_norm"], s["eps"])
    x = x + mm(jax.nn.silu(mm(m, lw["w_gate"])) * mm(m, lw["w_up"]),
               lw["w_down"])
    return x, None


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
              "w_up", "w_down")


def logits(w: Dict, s: Dict, tokens: jax.Array, fp8: bool = False):
    """(S, vocab) float32 logits of one sequence ``tokens`` (S,)."""
    if s["tied"]:
        raise NotImplementedError("tied output head")
    x = w["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(functools.partial(_layer, s, fp8), x,
                        {k: w[k] for k in LAYER_KEYS})
    x = _rmsnorm(x, w["final_norm"], s["eps"])
    return _mm(x, w["lm_head"].T, fp8)


def gaps(w: Dict, s: Dict, tokens: jax.Array, control: bool = False):
    """Per position ``p`` of ``tokens``: how far the logit of the token at
    ``p + 1`` lies below the reference's best, in units of the reference
    logits' standard deviation at ``p``.  With ``control``, also the same
    gap of the token that the float8 forward puts first at ``p``."""
    ref = logits(w, s, tokens)
    best = jnp.max(ref, -1)
    std = jnp.std(ref, -1)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    served = jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
    out = {"gap": (best - served) / std}
    if control:
        top8 = jnp.argmax(logits(w, s, tokens, fp8=True), -1)
        out["gap_fp8"] = (best - jnp.take_along_axis(
            ref, top8[:, None], -1)[:, 0]) / std
    return out
