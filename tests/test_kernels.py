"""Per-kernel allclose validation (interpret mode) against the ref.py jnp
oracles, with shape/dtype sweeps and hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

MM_SHAPES = [
    (128, 128, 128),
    (256, 128, 384),
    (384, 256, 128),
    (100, 60, 72),  # non-aligned: exercises padding
    (1, 128, 257),
    (512, 512, 512),
]


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_matches_ref(rng, m, k, n, dtype):
    k1, k2 = jax.random.split(rng)
    a = jax.random.normal(k1, (m, k), dtype)
    b = jax.random.normal(k2, (k, n), dtype)
    out = ops.matmul(a, b, interpret=True)
    expect = ref.matmul_ref(a, b)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol, rtol=tol
    )


def test_matmul_block_shape_accumulation(rng):
    """Multiple K steps must accumulate exactly (fp32 scratch)."""
    a = jax.random.normal(rng, (128, 512), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(rng, 1), (512, 128), jnp.float32)
    out = ops.matmul(a, b, block_shape=(128, 128, 128), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul_ref(a, b)),
                               atol=1e-4, rtol=1e-5)


def test_pick_block_shape_fits_vmem():
    from repro.hw import V5E
    from repro.kernels.matmul import pick_block_shape

    for m, n, k in [(8192, 8192, 8192), (128, 128, 128), (65536, 1024, 4096)]:
        bm, bn, bk = pick_block_shape(m, n, k, 4)
        assert bm % 128 == 0 and bn % 128 == 0 and bk % 128 == 0
        assert (bm * bk + bk * bn + bm * bn) * 4 <= V5E.vmem_bytes


# ---------------------------------------------------------------------------
# bitonic sort
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [8, 64, 128, 100, 257, 1024])
def test_sort_kernel_matches_ref(rng, n):
    x = jax.random.normal(rng, (n,))
    out = ops.sort(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref.sort_ref(x)))


@pytest.mark.parametrize("rows", [1, 2, 8, 16])
def test_sort_kernel_rows(rng, rows):
    x = jax.random.normal(rng, (rows, 64))
    out = ops.sort(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref.sort_ref(x)))


@given(st.lists(st.floats(min_value=-1e6, max_value=1e6, allow_nan=False,
                          allow_subnormal=False, width=32), min_size=1, max_size=200))
@settings(max_examples=25, deadline=None)
def test_sort_kernel_property(values):
    x = jnp.asarray(values, jnp.float32)
    out = np.asarray(ops.sort(x, interpret=True))
    np.testing.assert_array_equal(out, np.sort(np.asarray(x)))


def test_sort_kernel_duplicates_and_presorted():
    x = jnp.asarray([3.0, 3.0, 1.0, 1.0, 2.0, 2.0, 0.0, 0.0])
    np.testing.assert_array_equal(np.asarray(ops.sort(x, interpret=True)),
                                  np.sort(np.asarray(x)))
    y = jnp.arange(32, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(ops.sort(y, interpret=True)), np.asarray(y))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    # (B, S, Hq, Hkv, hd, causal)
    (1, 128, 2, 2, 64, True),
    (2, 256, 4, 2, 32, True),
    (1, 384, 2, 1, 64, True),
    (2, 128, 2, 2, 64, False),
    (1, 200, 2, 2, 32, True),  # padded seq
]


@pytest.mark.parametrize("b,s,hq,hkv,hd,causal", FA_CASES)
def test_flash_attention_matches_ref(rng, b, s, hq, hkv, hd, causal):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, s, hq, hd))
    k = jax.random.normal(ks[1], (b, s, hkv, hd))
    v = jax.random.normal(ks[2], (b, s, hkv, hd))
    out = ops.flash_attention(q, k, v, causal=causal, interpret=True)

    from repro.models.attention import dense_attention

    expect = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(rng, dtype):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64), dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), dtype)
    out = ops.flash_attention(q, k, v, interpret=True)
    from repro.models.attention import dense_attention

    expect = dense_attention(q, k, v, causal=True)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol, rtol=tol)


def test_flash_attention_blocks_skipped_are_exact(rng):
    """Different block sizes must agree bit-near (same math, different tiling)."""
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 32))
    k = jax.random.normal(ks[1], (1, 256, 2, 32))
    v = jax.random.normal(ks[2], (1, 256, 2, 32))
    o1 = ops.flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    o2 = ops.flash_attention(q, k, v, block_q=128, block_kv=128, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# fused WKV
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,chunk", [(32, 8), (64, 16), (40, 16), (128, 64)])
def test_wkv_kernel_matches_sequential_ref(rng, s, chunk):
    b, h, n = 2, 3, 8
    ks = jax.random.split(rng, 4)
    r = jax.random.normal(ks[0], (b, s, h, n))
    k = jax.random.normal(ks[1], (b, s, h, n))
    v = jax.random.normal(ks[2], (b, s, h, n))
    logw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, n)))
    u = jnp.full((h, n), 0.3)
    out, state = ops.wkv(r, k, v, logw, u, chunk=chunk, interpret=True)
    exp_out, exp_state = ref.wkv_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp_out),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(exp_state),
                               atol=1e-4, rtol=1e-4)


def test_wkv_kernel_extreme_decay(rng):
    b, s, h, n = 1, 32, 1, 4
    ks = jax.random.split(rng, 3)
    r = jax.random.normal(ks[0], (b, s, h, n))
    k = jax.random.normal(ks[1], (b, s, h, n))
    v = jax.random.normal(ks[2], (b, s, h, n))
    logw = jnp.full((b, s, h, n), -50.0)
    u = jnp.zeros((h, n))
    out, state = ops.wkv(r, k, v, logw, u, chunk=8, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(np.asarray(state)).all()


def test_wkv_kernel_matches_xla_chunked(rng):
    """Kernel vs the XLA chunked implementation (same math, different tiling)."""
    from repro.models.rwkv import wkv_chunked

    b, s, h, n = 2, 48, 2, 8
    ks = jax.random.split(rng, 4)
    r = jax.random.normal(ks[0], (b, s, h, n))
    k = jax.random.normal(ks[1], (b, s, h, n))
    v = jax.random.normal(ks[2], (b, s, h, n))
    logw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, n)) - 1.0)
    u = jnp.full((h, n), 0.1)
    out_k, _ = ops.wkv(r, k, v, logw, u, chunk=16, interpret=True)
    out_x, _ = wkv_chunked(r, k, v, logw, u, None, chunk=16)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("rows,n,dtype", [
    (1, 1 << 14, jnp.float32),  # two 64-row chunks: the across-chunk stage
    (2, 2048, jnp.float32),  # one chunk spans both rows
    (3, 1500, jnp.int32),
    (2, 300, jnp.bfloat16),  # widened to f32 and back
])
def test_sort_exchange_layouts_match_ref(rng, rows, n, dtype):
    """The roll-and-select partner exchange in every form the kernel has:
    lanes, rows within a chunk, whole chunks, and rows sharing a chunk."""
    if dtype == jnp.int32:
        x = jax.random.randint(rng, (rows, n), -1000, 1000, dtype)
    else:
        x = jax.random.normal(rng, (rows, n)).astype(dtype)
    out = ops.sort(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref.sort_ref(x)))


def test_wkv_kernel_bonus_per_head_and_channel(rng):
    """u differs across heads and channels, and batch > 1: the (BH, 1, N)
    bonus layout must pick each folded head's own row."""
    b, s, h, n = 3, 24, 2, 8
    ks = jax.random.split(rng, 5)
    r, k, v = (jax.random.normal(kk, (b, s, h, n)) for kk in ks[:3])
    logw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, n)))
    u = jax.random.normal(ks[4], (h, n))
    out, state = ops.wkv(r, k, v, logw, u, chunk=8, interpret=True)
    exp_out, exp_state = ref.wkv_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp_out),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(exp_state),
                               atol=1e-4, rtol=1e-4)
