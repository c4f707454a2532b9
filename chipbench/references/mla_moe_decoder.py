"""Plain reference of a DeepSeek-V3-layout decoder (Moonlight-16B-A3B),
written from the published description and importing nothing of the
program under test.

Per layer: ``x += o(mla(n1(x)))``, then ``x += mlp(n2(x))``, with ``n`` an
RMSNorm of gain ``1 + g``.

Latent attention, in the plain form: ``q = n1(x) Wq`` (heads of nope +
rope); ``[c, k_pe] = n1(x) Wkv_a``; ``c`` is RMS-normed (``kv_norm``) and
``Wkv_b`` turns it into each head's key part and value, ``[k_nope, v]``;
the head's key is ``[k_nope, rope(k_pe)]``, the rope key shared by every
head, its query ``[q_nope, rope(q_pe)]``; causal softmax attention scaled
by ``(nope + rope) ** -0.5``.  RoPE rotates the halves of the rope parts
(``rotate_half``) by ``pos / rope_theta ** (2j / rope)``.

The MLP of the first ``dense_layers`` layers is a gated SiLU MLP.  Every
other layer's is an expert layer: ``sigmoid`` scores of the router's
logits over all routed experts; the choice is the top ``top_k`` of score +
correction bias (one group); the weights are the chosen scores over their
sum, times ``routed_scale``; of the chosen experts only the held ones
(``0 .. held - 1``) are computed, and the others' part is left out, as the
program leaves it out; the shared experts (one gated MLP of ``shared x
expert_ff``) are added for every token.  A final RMSNorm and an untied
output head give the logits.

Departures from the published model: the held experts' share alone (the
configuration's cut); RoPE in the rotate-half layout (for random weights a
fixed permutation of the rope columns); no training-time balance loss.

Everything is float32 with every matrix product at ``Precision.HIGHEST``,
one sequence at a time, the expert layers under ``lax.scan``, so only one
layer's weights are widened to float32 at a time.  ``rounding`` rounds
every matrix product's operands first: ``to_e4m3`` (float8) gives the
control.

**Near ties.**  The choice is a top-k over 64 experts, so it jumps where two
experts' score + bias lie close: any rounding of the hidden state that
reaches such a token changes the expert set, and a held expert joining or
leaving it moves the token's state by a whole expert's output.  In 26
expert layers that happens to a large share of positions under bfloat16
(section 6 of PERF.md gives the counts), and the moved state then moves
the logits as far as the float8 control moves them.  ``gaps`` therefore
compares only positions that no expert layer leaves undecided at the
served precision: a position is a near tie (``near_tie``) where, in some
expert layer, a held expert's score + bias lies within ``tie_width`` (one
unit in the last place of the served type) of the midpoint between the
``top_k``-th and the next one.  A flip among experts that are not held
leaves the held part unchanged and is no tie here.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.references.dense_decoder import (F32, HIGHEST, _rmsnorm,
                                                _rope, to_e4m3)


def tie_width(s: Dict) -> float:
    """The distance, in score + bias, within which a held expert counts as
    tied at the cut of the top-k choice (see the module docstring): one
    unit in the last place of a value in [0.5, 1), where the chosen
    experts' values lie, in the served type (bfloat16: 2^-8)."""
    return float(jnp.finfo(jnp.dtype(s["dtype"])).eps) / 2


def _mm(a, b, rnd):
    if rnd is not None:
        a, b = rnd(a), rnd(b)
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HIGHEST)


def _ein(spec, a, b, rnd):
    if rnd is not None:
        a, b = rnd(a), rnd(b)
    return jnp.einsum(spec, a.astype(F32), b.astype(F32), precision=HIGHEST)


def _attention(s: Dict, rnd, x, lw):
    """x + the layer's latent attention, plain form."""
    n, d = x.shape
    h, nope, rope, r = s["heads"], s["nope"], s["rope"], s["rank"]
    dv = s["v_dim"]
    mm = functools.partial(_mm, rnd=rnd)
    a = _rmsnorm(x, lw["attn_norm"], s["eps"])
    q = mm(a, lw["wq"].reshape(d, -1)).reshape(n, h, nope + rope)
    kv = mm(a, lw["wkv_a"])
    c = _rmsnorm(kv[:, :r], lw["kv_norm"], s["eps"])
    k_pe = _rope(kv[:, None, r:], s["rope_theta"])  # (n, 1, rope)
    kvb = mm(c, lw["wkv_b"].reshape(r, -1)).reshape(n, h, nope + dv)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_pe, (n, h, rope))], -1)
    v = kvb[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                              s["rope_theta"])], -1)
    scores = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) \
        * (nope + rope) ** -0.5
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = mm(p, v.transpose(1, 0, 2)).transpose(1, 0, 2).reshape(n, h * dv)
    return x + mm(att, lw["wo"])


def _mlp(rnd, m, gate, up, down):
    return _mm(jax.nn.silu(_mm(m, gate, rnd)) * _mm(m, up, rnd), down, rnd)


def routing(s: Dict, logits, bias):
    """(weights (n, top_k), ids (n, top_k), choice values (n, experts)) of
    each token's chosen experts: sigmoid scores, chosen by score + bias,
    weighted by their share of the chosen scores times ``routed_scale``."""
    if s["scoring"] != "sigmoid":
        raise NotImplementedError(f"scoring {s['scoring']!r}")
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias.astype(F32)
    _, ids = jax.lax.top_k(choice, s["top_k"])
    w = jnp.take_along_axis(scores, ids, -1)
    return (w / (w.sum(-1, keepdims=True) + 1e-20) * s["routed_scale"], ids,
            choice)


def _dense_layer(s: Dict, rnd, x, lw):
    x = _attention(s, rnd, x, lw)
    m = _rmsnorm(x, lw["mlp_norm"], s["eps"])
    return x + _mlp(rnd, m, lw["w_gate"], lw["w_up"], lw["w_down"]), None


def _expert_layer(s: Dict, rnd, x, lw):
    x = _attention(s, rnd, x, lw)
    m = _rmsnorm(x, lw["mlp_norm"], s["eps"])
    w, ids, choice = routing(s, _mm(m, lw["router"], rnd), lw["router_bias"])
    # each held expert's weight for each token, zero where not chosen
    gate = jnp.sum(jnp.where(ids[:, :, None] == jnp.arange(s["held"]),
                             w[..., None], 0.0), axis=1)  # (n, held)
    hid = (jax.nn.silu(_ein("nd,edf->nef", m, lw["w_gate"], rnd))
           * _ein("nd,edf->nef", m, lw["w_up"], rnd))
    routed = _ein("nef,efd->nd", hid * gate[..., None], lw["w_down"], rnd)
    shared = _mlp(rnd, m, lw["shared_gate"], lw["shared_up"],
                  lw["shared_down"])
    return x + routed + shared, (ids, choice)


ATTN_KEYS = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "mlp_norm")
DENSE_KEYS = ATTN_KEYS + ("w_gate", "w_up", "w_down")
EXPERT_KEYS = ATTN_KEYS + ("router", "router_bias", "w_gate", "w_up",
                           "w_down", "shared_gate", "shared_up",
                           "shared_down")


def forward(w: Dict, s: Dict, tokens: jax.Array, rounding=None):
    """(logits (S, vocab) float32, routed expert ids (expert layers, S,
    top_k), choice values (expert layers, S, experts)) of one sequence
    ``tokens`` (S,), every matrix product's operands rounded by
    ``rounding`` (None: exact)."""
    if s["tied"]:
        raise NotImplementedError("tied output head")
    x = w["embed"][tokens].astype(F32)
    for i in range(s["dense_layers"]):
        x, _ = _dense_layer(s, rounding, x,
                            {k: w[f"dense{i}_{k}"] for k in DENSE_KEYS})
    x, (ids, choice) = jax.lax.scan(
        functools.partial(_expert_layer, s, rounding), x,
        {k: w[k] for k in EXPERT_KEYS})
    x = _rmsnorm(x, w["final_norm"], s["eps"])
    return _mm(x, w["lm_head"].T, rounding), ids, choice


def logits(w: Dict, s: Dict, tokens: jax.Array, rounding=None):
    """(S, vocab) float32 logits of one sequence ``tokens`` (S,)."""
    return forward(w, s, tokens, rounding)[0]


def tie_distance(s: Dict, choice: jax.Array) -> jax.Array:
    """(S,) per position: the least distance, over expert layers and held
    experts, of a held expert's choice value from the midpoint between the
    ``top_k``-th and the next largest value of its layer."""
    top = jax.lax.top_k(choice, s["top_k"] + 1)[0]
    cut = 0.5 * (top[..., -2] + top[..., -1])  # (layers, S)
    held = choice[..., :s["held"]]
    return jnp.min(jnp.abs(held - cut[..., None]), axis=(0, 2))


def gaps(w: Dict, s: Dict, tokens: jax.Array, control: bool = False,
         tie: float = None):
    """Per position ``p`` of ``tokens``: how far the logit of the token at
    ``p + 1`` lies below the reference's best, in units of the reference
    logits' standard deviation at ``p``; 0 at a near tie (``near_tie``,
    true there; ``tie`` overrides ``tie_width``).  With ``control``, also
    the same gap of the token that the float8 forward puts first at
    ``p``."""
    ref, _, choice = forward(w, s, tokens)
    near = tie_distance(s, choice) < (tie_width(s) if tie is None else tie)
    best = jnp.max(ref, -1)
    std = jnp.std(ref, -1)

    def gap(tok):
        g = (best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]) / std
        return jnp.where(near, 0.0, g)

    out = {"gap": gap(jnp.concatenate([tokens[1:], tokens[:1]])),
           "near_tie": near}
    if control:
        out["gap_fp8"] = gap(jnp.argmax(logits(w, s, tokens, to_e4m3), -1))
    return out
