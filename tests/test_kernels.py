"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracle,
swept over shapes and dtypes (deliverable c)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.aircomp import (
    aircomp_fused,
    aircomp_fused_batch,
    aircomp_fused_batch_ref,
    aircomp_fused_ref,
)
from repro.kernels.aircomp.kernel import DEFAULT_TILE_D, _clamp_tile, _rows_tile
from repro.kernels.attention import flash_attention, mha_ref
from repro.kernels.ssd import ssd_chunked_ref, ssd_naive, ssd_pallas

# --------------------------------------------------------------------------
# aircomp fused
# --------------------------------------------------------------------------


@pytest.mark.parametrize("n,d", [(4, 512), (30, 1024), (7, 700), (1, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_aircomp_fused_matches_ref(n, d, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    g = jax.random.normal(ks[0], (n, d), dtype)
    coeff = jax.random.uniform(ks[1], (n,)) * (
        jax.random.uniform(ks[2], (n,)) > 0.3
    )
    z = jax.random.normal(ks[3], (d,), dtype)
    m_g = jnp.float32(0.13)
    v_g = jnp.float32(0.7)
    a = jnp.float32(2.4)

    got = aircomp_fused(g, coeff, m_g, v_g, a, z, interpret=True)
    want = aircomp_fused_ref(
        g.astype(jnp.float32), coeff, m_g, v_g, a, z.astype(jnp.float32)
    )
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol
    )


# D values off the tile grid: not multiples of tile_d, including D < tile_d
# (a model-mesh shard's local block) where the tile must CLAMP to the
# 128-lane-aligned D instead of padding a near-empty DEFAULT_TILE_D grid
_ODD_DIMS = (64, 100, 128, 300, 512 + 1, 981, 2 * 512 + 17)


@pytest.mark.parametrize("d", _ODD_DIMS)
def test_aircomp_fused_padding_property(d):
    key = jax.random.PRNGKey(d)
    ks = jax.random.split(key, 4)
    n = 6
    g = jax.random.normal(ks[0], (n, d))
    coeff = jax.random.uniform(ks[1], (n,)) * (
        jax.random.uniform(ks[2], (n,)) > 0.3
    )
    z = jax.random.normal(ks[3], (d,))
    m_g, v_g, a = jnp.float32(0.21), jnp.float32(0.9), jnp.float32(1.7)

    got = aircomp_fused(g, coeff, m_g, v_g, a, z, interpret=True)
    want = aircomp_fused_ref(g, coeff, m_g, v_g, a, z)
    assert got.shape == (d,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d", _ODD_DIMS)
def test_aircomp_fused_batch_padding_property(d):
    key = jax.random.PRNGKey(1000 + d)
    ks = jax.random.split(key, 6)
    bt, n = 3, 5
    g = jax.random.normal(ks[0], (bt, n, d))
    coeff = jax.random.uniform(ks[1], (bt, n)) * (
        jax.random.uniform(ks[2], (bt, n)) > 0.3
    )
    z = jax.random.normal(ks[3], (bt, d))
    m_g = jax.random.normal(ks[4], (bt,)) * 0.1
    v_g = jax.random.uniform(ks[5], (bt,)) + 0.5
    a = jnp.full((bt,), 2.0)

    got = aircomp_fused_batch(g, coeff, m_g, v_g, a, z, interpret=True)
    want = aircomp_fused_batch_ref(g, coeff, m_g, v_g, a, z)
    assert got.shape == (bt, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_clamp_tile_rule():
    # oversized default tile clamps to the 128-lane-aligned D...
    assert _clamp_tile(100, DEFAULT_TILE_D) == 128
    assert _clamp_tile(128, DEFAULT_TILE_D) == 128
    assert _clamp_tile(300, DEFAULT_TILE_D) == 384
    # ...never past D's own tile when D is large...
    assert _clamp_tile(7850, DEFAULT_TILE_D) == DEFAULT_TILE_D
    assert _clamp_tile(DEFAULT_TILE_D, DEFAULT_TILE_D) == DEFAULT_TILE_D
    # ...and a caller-requested SMALL tile passes through untouched
    assert _clamp_tile(512, 8) == 8
    assert _clamp_tile(4, 8) == 8


def test_aircomp_fused_zero_noise_is_weighted_sum():
    key = jax.random.PRNGKey(1)
    g = jax.random.normal(key, (8, 512))
    coeff = jnp.ones((8,)) / 8
    out = aircomp_fused(
        g, coeff, jnp.float32(0.0), jnp.float32(1.0), jnp.float32(1.0),
        jnp.zeros((512,)), interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(g.mean(0)), rtol=1e-5, atol=1e-6)


# the lane-dense (n_devices, rows, L) form: logreg's carried weight, a small
# one, L off the lane grid, and one long enough to tile with a ragged end
_ROWS_SHAPES = [(30, 10, 784), (6, 10, 128), (5, 3, 300), (7, 10, 5000)]


@pytest.mark.parametrize("n,rows,length", _ROWS_SHAPES)
def test_aircomp_fused_rows_matches_ref(n, rows, length):
    ks = jax.random.split(jax.random.PRNGKey(n * length + rows), 4)
    g = jax.random.normal(ks[0], (n, rows, length))
    coeff = jax.random.uniform(ks[1], (n,)) * (
        jax.random.uniform(ks[2], (n,)) > 0.3
    )
    z = jax.random.normal(ks[3], (rows, length))
    m_g, v_g, a = jnp.float32(0.21), jnp.float32(0.9), jnp.float32(1.7)

    got = aircomp_fused(g, coeff, m_g, v_g, a, z, interpret=True)
    want = aircomp_fused_ref(g, coeff, m_g, v_g, a, z)
    assert got.shape == (rows, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    # the same numbers as the flat form over the same device rows
    flat = aircomp_fused(
        g.reshape(n, -1), coeff, m_g, v_g, a, z.reshape(-1), interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got).reshape(-1), np.asarray(flat), rtol=1e-5, atol=1e-5
    )


def test_aircomp_fused_rows_batched_as_the_lattice_batches():
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    bt, n, rows, length = 3, 6, 10, 784
    g = jax.random.normal(ks[0], (bt, n, rows, length))
    coeff = jax.random.uniform(ks[1], (bt, n)) * (
        jax.random.uniform(ks[2], (bt, n)) > 0.3
    )
    z = jax.random.normal(ks[3], (bt, rows, length))
    m_g = jax.random.normal(ks[4], (bt,)) * 0.1
    v_g = jax.random.uniform(ks[5], (bt,)) + 0.5
    a = jnp.full((bt,), 2.0)

    got = aircomp_fused_batch(g, coeff, m_g, v_g, a, z, interpret=True)
    want = aircomp_fused_batch_ref(g, coeff, m_g, v_g, a, z)
    assert got.shape == (bt, rows, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_rows_tile_rule():
    # a whole logreg cell (30 x 16 x 896 floats, 1.7 MB) is one block...
    assert _rows_tile(30, 10, 784) == 784
    assert _rows_tile(6, 3, 300) == 300
    # ...a longer segment tiles in whole lanes under the block budget
    assert _rows_tile(30, 10, 100_000) == 1024
    assert _rows_tile(7, 10, 5000) == 4608  # two tiles, the second ragged
    assert _rows_tile(300, 100, 100_000) == 128


# --------------------------------------------------------------------------
# ssd
# --------------------------------------------------------------------------


def _ssd_inputs(key, b, s, h, p, n, dtype):
    ks = jax.random.split(key, 4)
    xdt = jax.random.normal(ks[0], (b, s, h, p), dtype)
    # realistic log decays in [-3, 0)
    la = -jax.random.uniform(ks[1], (b, s, h), jnp.float32, 0.01, 3.0)
    B = jax.random.normal(ks[2], (b, s, n), dtype)
    C = jax.random.normal(ks[3], (b, s, n), dtype)
    return xdt, la, B, C


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 64, 4, 32, 16, 16),
    (1, 128, 2, 64, 64, 32),
    (3, 32, 8, 16, 8, 32),   # chunk == s
])
def test_ssd_chunked_ref_matches_naive(b, s, h, p, n, chunk):
    xdt, la, B, C = _ssd_inputs(jax.random.PRNGKey(0), b, s, h, p, n, jnp.float32)
    got = ssd_chunked_ref(xdt, la, B, C, chunk)
    want = ssd_naive(xdt, la, B, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 64, 4, 32, 16, 16),
    (1, 128, 2, 64, 64, 32),
    (2, 32, 8, 16, 8, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas_matches_naive(b, s, h, p, n, chunk, dtype):
    xdt, la, B, C = _ssd_inputs(jax.random.PRNGKey(1), b, s, h, p, n, dtype)
    got = ssd_pallas(xdt, la, B.astype(dtype), C.astype(dtype), chunk=chunk, interpret=True)
    want = ssd_naive(
        xdt.astype(jnp.float32), la, B.astype(jnp.float32), C.astype(jnp.float32)
    )
    tol = 5e-4 if dtype == jnp.float32 else 8e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol
    )


def test_ssd_pallas_state_reset_across_batch():
    """The scratch state must reset at chunk 0 of every batch row —
    batch rows are independent."""
    xdt, la, B, C = _ssd_inputs(jax.random.PRNGKey(2), 3, 64, 2, 16, 8, jnp.float32)
    full = ssd_pallas(xdt, la, B, C, chunk=16, interpret=True)
    # row 2 computed alone must equal row 2 of the batched run
    solo = ssd_pallas(xdt[2:], la[2:], B[2:], C[2:], chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(full[2:]), np.asarray(solo), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------


@pytest.mark.parametrize("b,sq,sk,h,kv,dh,bq,bk", [
    (2, 64, 64, 4, 4, 32, 16, 16),    # MHA causal
    (1, 128, 128, 8, 2, 64, 32, 32),  # GQA 4:1
    (2, 64, 64, 4, 1, 32, 64, 16),    # MQA, single q block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_causal_matches_ref(b, sq, sk, h, kv, dh, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, sk, kv, dh), dtype)
    v = jax.random.normal(ks[2], (b, sk, kv, dh), dtype)
    got = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)
    want = mha_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), causal=True
    )
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("window", [16, 32, 100])
def test_flash_sliding_window_matches_ref(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    b, s, h, kv, dh = 1, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, kv, dh))
    v = jax.random.normal(ks[2], (b, s, kv, dh))
    got = flash_attention(
        q, k, v, causal=True, sliding_window=window,
        block_q=32, block_k=32, interpret=True,
    )
    want = mha_ref(q, k, v, causal=True, sliding_window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_non_causal_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    b, sq, sk, h, dh = 2, 32, 64, 2, 32
    q = jax.random.normal(ks[0], (b, sq, h, dh))
    k = jax.random.normal(ks[1], (b, sk, h, dh))
    v = jax.random.normal(ks[2], (b, sk, h, dh))
    got = flash_attention(q, k, v, causal=False, block_q=32, block_k=32, interpret=True)
    want = mha_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_q_offset_decode_tail():
    """q_offset places the query block at the end of a longer context
    (chunked prefill / speculative-decode pattern)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, sk, h, dh = 1, 128, 2, 32
    sq, off = 32, 96
    k = jax.random.normal(ks[1], (b, sk, h, dh))
    v = jax.random.normal(ks[2], (b, sk, h, dh))
    q = jax.random.normal(ks[0], (b, sq, h, dh))
    got = flash_attention(
        q, k, v, causal=True, q_offset=off, block_q=32, block_k=32, interpret=True
    )
    want = mha_ref(q, k, v, causal=True, q_offset=off)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
