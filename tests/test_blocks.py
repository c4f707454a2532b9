"""Block-level correctness: every fancy/parallel form is checked against a
naive sequential oracle (the paper's serial-vs-parallel equivalence, applied
as a test invariant)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn_lib
from repro.models import ffn as ffn_lib
from repro.models import rglru as rglru_lib
from repro.models import rwkv as rwkv_lib


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _qkv(key, b=2, s=64, hq=4, hkv=2, hd=16, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, hq, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("s,chunk", [(64, 16), (96, 32), (128, 128)])
def test_chunked_matches_dense(rng, s, chunk):
    q, k, v = _qkv(rng, s=s)
    ref = attn_lib.dense_attention(q, k, v, causal=True)
    out = attn_lib.chunked_attention(q, k, v, causal=True, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("window", [8, 16, 64])
def test_local_matches_dense_windowed(rng, window):
    q, k, v = _qkv(rng, s=96)
    ref = attn_lib.dense_attention(q, k, v, causal=True, window=window)
    out = attn_lib.local_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_gqa_equals_repeated_mha(rng):
    """GQA with kv heads repeated == MHA."""
    q, k, v = _qkv(rng, hq=4, hkv=2)
    out_gqa = attn_lib.dense_attention(q, k, v)
    k_rep = jnp.repeat(k, 2, axis=2)
    v_rep = jnp.repeat(v, 2, axis=2)
    out_mha = attn_lib.dense_attention(q, k_rep, v_rep)
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_mha), atol=1e-5, rtol=1e-5)


def test_decode_matches_prefill_lastpos(rng):
    """decode_attention at position t == dense attention row t."""
    q, k, v = _qkv(rng, s=32)
    full = attn_lib.dense_attention(q, k, v, causal=True)
    smax = 48
    kc = jnp.zeros((2, smax, 2, 16)).at[:, :32].set(k)
    vc = jnp.zeros((2, smax, 2, 16)).at[:, :32].set(v)
    out = attn_lib.decode_attention(q[:, -1:], kc, vc, jnp.int32(32))
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full[:, -1]), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# RWKV6 chunked WKV vs sequential recurrence
# ---------------------------------------------------------------------------


def _wkv_sequential(r, k, v, logw, u):
    b, s, h, n = r.shape
    S = jnp.zeros((b, h, n, n))
    outs = []
    for t in range(s):
        rt, kt, vt, wt = r[:, t], k[:, t], v[:, t], jnp.exp(logw[:, t])
        o = jnp.einsum("bhn,bhnm->bhm", rt, S) + jnp.einsum(
            "bhn,hn,bhn,bhm->bhm", rt, u, kt, vt
        )
        S = wt[..., None] * S + jnp.einsum("bhn,bhm->bhnm", kt, vt)
        outs.append(o)
    return jnp.stack(outs, axis=1), S


@pytest.mark.parametrize("s,chunk", [(16, 4), (33, 8), (64, 64), (40, 16)])
def test_wkv_chunked_matches_sequential(rng, s, chunk):
    b, h, n = 2, 3, 8
    ks = jax.random.split(rng, 4)
    r = jax.random.normal(ks[0], (b, s, h, n))
    k = jax.random.normal(ks[1], (b, s, h, n))
    v = jax.random.normal(ks[2], (b, s, h, n))
    logw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, n)))  # strong + weak decay
    u = jnp.full((h, n), 0.3)
    ref, S_ref = _wkv_sequential(r, k, v, logw, u)
    out, S_out = rwkv_lib.wkv_chunked(r, k, v, logw, u, None, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(S_out), np.asarray(S_ref), atol=1e-4, rtol=1e-4)


def test_wkv_extreme_decay_stable(rng):
    """Log-space chunking must survive near-zero decay (w -> 0)."""
    b, s, h, n = 1, 32, 1, 4
    ks = jax.random.split(rng, 3)
    r = jax.random.normal(ks[0], (b, s, h, n))
    k = jax.random.normal(ks[1], (b, s, h, n))
    v = jax.random.normal(ks[2], (b, s, h, n))
    logw = jnp.full((b, s, h, n), -50.0)  # catastrophic decay
    u = jnp.zeros((h, n))
    out, S = rwkv_lib.wkv_chunked(r, k, v, logw, u, None, chunk=8)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(S)).all()


def test_wkv_step_matches_chunked(rng):
    b, s, h, n = 2, 12, 2, 8
    ks = jax.random.split(rng, 4)
    r = jax.random.normal(ks[0], (b, s, h, n))
    k = jax.random.normal(ks[1], (b, s, h, n))
    v = jax.random.normal(ks[2], (b, s, h, n))
    logw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, n)) - 1.0)
    u = jnp.full((h, n), 0.1)
    ref, S_ref = rwkv_lib.wkv_chunked(r, k, v, logw, u, None, chunk=4)
    S = jnp.zeros((b, h, n, n))
    outs = []
    for t in range(s):
        o, S = rwkv_lib.wkv_step(r[:, t:t+1], k[:, t:t+1], v[:, t:t+1], logw[:, t:t+1], u, S)
        outs.append(o[:, 0])
    out = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# RG-LRU associative scan vs sequential
# ---------------------------------------------------------------------------


def test_rglru_parallel_matches_sequential(rng):
    d, w, b, s = 16, 16, 2, 40
    params = rglru_lib.rglru_init(rng, d, w)
    x = jax.random.normal(rng, (b, s, d)) * 0.5
    out_par, _ = rglru_lib.rglru_apply(params, x, state=None)
    # sequential path via the decode branch
    st = rglru_lib.rglru_init_state(b, w)
    out_seq, _ = rglru_lib.rglru_apply(params, x, state=st)
    np.testing.assert_allclose(np.asarray(out_par), np.asarray(out_seq), atol=1e-5, rtol=1e-5)


def test_rglru_step_streaming(rng):
    """Feeding tokens one at a time == full-sequence processing."""
    d, w, b, s = 8, 8, 1, 10
    params = rglru_lib.rglru_init(rng, d, w)
    x = jax.random.normal(rng, (b, s, d)) * 0.5
    full, _ = rglru_lib.rglru_apply(params, x, state=None)
    st = rglru_lib.rglru_init_state(b, w)
    outs = []
    for t in range(s):
        o, st = rglru_lib.rglru_apply(params, x[:, t:t+1], state=st)
        outs.append(o[:, 0])
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs, 1)), np.asarray(full), atol=1e-5, rtol=1e-5
    )


# ---------------------------------------------------------------------------
# MoE: dense oracle properties
# ---------------------------------------------------------------------------


def test_moe_dense_topk_weights_sum_to_one(rng):
    d, f, e = 8, 16, 4
    params = ffn_lib.moe_init(rng, d, f, e, "swiglu")
    x = jax.random.normal(rng, (2, 6, d))
    y, aux, _ = ffn_lib.moe_apply(params, x, top_k=2, activation="swiglu")
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) > 0.0


def test_moe_topk1_equals_best_expert(rng):
    """With top_k=1, output == the single selected expert's FFN."""
    d, f, e = 4, 8, 3
    params = ffn_lib.moe_init(rng, d, f, e, "swiglu")
    x = jax.random.normal(rng, (1, 5, d))
    y, _, _ = ffn_lib.moe_apply(params, x, top_k=1, activation="swiglu")
    t = x.reshape(-1, d)
    logits = t @ params["router"]
    ids = np.asarray(jnp.argmax(logits, -1))
    for i, eid in enumerate(ids):
        p_e = {
            "w_in": params["w_in"][eid],
            "w_gate": params["w_gate"][eid],
            "w_out": params["w_out"][eid],
        }
        ref = ffn_lib.ffn_apply(p_e, t[i], "swiglu")
        np.testing.assert_allclose(np.asarray(y.reshape(-1, d)[i]), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
