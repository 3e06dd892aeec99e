"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.

Kernels run in interpret mode on CPU (the kernel body itself executes),
asserted allclose against repro.kernels.ref oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.dsc_quantize import dsc_quantize
from repro.kernels.dsc_update import dsc_update
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quantize import QBLOCK, dequantize, quantize

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------- DSC
@pytest.mark.parametrize("n,block_rows", [(1024, 1), (4096, 2), (8192, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("p", [0.1, 0.5, 1.0])
def test_dsc_update_matches_ref(n, block_rows, dtype, p):
    g = jax.random.normal(KEY, (n,), jnp.float32).astype(dtype)
    s = jax.random.normal(jax.random.fold_in(KEY, 1), (n,))
    seed = jnp.uint32(42)
    v, s_new = dsc_update(g, s, seed, p=p, gamma=0.5,
                          block_rows=block_rows, interpret=True)
    v_ref, s_ref = ref.dsc_update_ref(g, s, seed, p=p, gamma=0.5)
    np.testing.assert_allclose(np.asarray(v, np.float32),
                               np.asarray(v_ref, np.float32),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_new), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)


def test_dsc_update_retention_and_unbiasedness():
    n, p = 64 * 1024, 0.25
    g = jax.random.normal(KEY, (n,))
    s = jnp.zeros(n)
    vs = []
    for seed in range(30):
        v, _ = dsc_update(g, s, jnp.uint32(seed), p=p, gamma=0.5,
                          interpret=True)
        vs.append(np.asarray(v))
    frac = np.mean([np.mean(v != 0) for v in vs])
    assert abs(frac - p) < 0.02
    err = np.abs(np.mean(vs, 0) - np.asarray(g)).mean()
    assert err < 0.5   # MC mean approaches g (unbiased compressor)


# ------------------------------------------------------------- quantize
@pytest.mark.parametrize("n", [QBLOCK, 4 * QBLOCK, 64 * QBLOCK])
def test_quantize_matches_ref(n):
    x = 3.0 * jax.random.normal(KEY, (n,))
    seed = jnp.uint32(7)
    q, sc = quantize(x, seed, interpret=True)
    q_ref, sc_ref = ref.quantize_ref(x, seed)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref)[:n])
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sc_ref), rtol=1e-6)
    # dequantize roundtrip error bounded by one quantization step
    xd = dequantize(q, sc, interpret=True)
    step = np.repeat(np.asarray(sc), QBLOCK)
    assert np.all(np.abs(np.asarray(xd) - np.asarray(x)) <= step + 1e-6)


def test_quantize_unbiased():
    n = 8 * QBLOCK
    x = jax.random.normal(KEY, (n,))
    outs = []
    for seed in range(50):
        q, sc = quantize(x, jnp.uint32(seed), interpret=True)
        outs.append(np.asarray(dequantize(q, sc, interpret=True)))
    err = np.abs(np.mean(outs, 0) - np.asarray(x)).mean()
    scale_mean = np.asarray(sc).mean()
    assert err < 0.6 * scale_mean  # MC mean within a fraction of one step


def test_quantize_zero_block_safe():
    x = jnp.zeros(QBLOCK)
    q, sc = quantize(x, jnp.uint32(0), interpret=True)
    assert not np.any(np.asarray(q))
    assert float(sc[0]) == 0.0


# ------------------------------------------- masked-tail (ragged) contract
# The counter-based RNG indexes the FLAT GLOBAL element position, so a
# kernel's internal zero-padding must never displace a real element's
# draw: kernel(x[:n]) == ref-on-exactly-n for ANY n, not just tiles.
@settings(max_examples=12, deadline=None)
@given(n=st.integers(1, 5000), seed=st.integers(0, 1000))
def test_dsc_update_ragged_matches_ref(n, seed):
    g = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    s = jax.random.normal(jax.random.PRNGKey(seed + 1), (n,))
    v, s_new = dsc_update(g, s, jnp.uint32(seed), p=0.3, gamma=0.5,
                          interpret=True)
    v_ref, s_ref = ref.dsc_update_ref(g, s, jnp.uint32(seed), p=0.3,
                                      gamma=0.5)
    assert v.shape == (n,) and s_new.shape == (n,)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_new), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=12, deadline=None)
@given(n=st.integers(1, 4 * QBLOCK + 37), seed=st.integers(0, 1000))
def test_quantize_ragged_matches_ref(n, seed):
    x = 2.0 * jax.random.normal(jax.random.PRNGKey(seed), (n,))
    q, sc = quantize(x, jnp.uint32(seed), interpret=True)
    q_ref, sc_ref = ref.quantize_ref(x, jnp.uint32(seed))
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sc_ref),
                               rtol=1e-6)
    # padded tail must quantize to exact zeros (scale of a zero block = 0)
    pad = (-n) % QBLOCK
    if pad:
        assert not np.any(np.asarray(q)[n:])


# ------------------------------------------------- fused DSC -> int8 wire
@pytest.mark.parametrize("n", [8 * QBLOCK, 2305, 511])
@pytest.mark.parametrize("p", [0.25, 1.0])
def test_dsc_quantize_matches_ref(n, p):
    g = jax.random.normal(KEY, (n,))
    s = 0.1 * jax.random.normal(jax.random.fold_in(KEY, 1), (n,))
    sm, sr = jnp.uint32(11), jnp.uint32(12)
    q, sc, s_new = dsc_quantize(g, s, sm, sr, p=p, gamma=0.5,
                                interpret=True)
    q_ref, sc_ref, s_ref = ref.dsc_quantize_ref(g, s, sm, sr, p=p,
                                                gamma=0.5)
    # bit-exact: same RNG indices, same blockmax, same stochastic round
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sc_ref),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s_new), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-6)


def test_dsc_quantize_matches_unfused_chain():
    """The one-pass kernel == dsc_update -> quantize -> dequantize ->
    shift update composed from the standalone kernels (the unfused wire
    path it replaces), sharing the same two seeds."""
    n, p, gamma = 4 * QBLOCK, 0.5, 0.7
    g = jax.random.normal(KEY, (n,))
    s = 0.2 * jax.random.normal(jax.random.fold_in(KEY, 2), (n,))
    sm, sr = jnp.uint32(3), jnp.uint32(4)
    q, sc, s_new = dsc_quantize(g, s, sm, sr, p=p, gamma=gamma,
                                interpret=True)
    v, _ = dsc_update(g, s, sm, p=p, gamma=gamma, interpret=True)
    q2, sc2 = quantize(v, sr, interpret=True)
    v_hat = dequantize(q2, sc2, interpret=True)[:n]
    np.testing.assert_array_equal(np.asarray(q)[:n], np.asarray(q2)[:n])
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sc2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s_new),
                               np.asarray(s + gamma * v_hat),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------- several grid steps, ragged tail
# Row blocks are whole tiles and the row count pads up to whole blocks;
# the draws are indexed by global position, so a grid of several steps
# with a padded last block must still draw exactly what the oracle does.
@pytest.mark.parametrize("kernel", ["dsc_update", "quantize",
                                    "dsc_quantize"])
def test_multi_block_grid_matches_ref(kernel):
    g = jax.random.normal(KEY, (3 * 32 * 1024 + 37,))
    s = 0.1 * jax.random.normal(jax.random.fold_in(KEY, 1), g.shape)
    sm, sr = jnp.uint32(5), jnp.uint32(6)
    if kernel == "dsc_update":
        got = dsc_update(g, s, sm, p=0.3, gamma=0.5, block_rows=32,
                         interpret=True)
        want = ref.dsc_update_ref(g, s, sm, p=0.3, gamma=0.5)
    elif kernel == "quantize":
        q, sc = quantize(g, sm, block_b=64, interpret=True)
        got = (q, sc, dequantize(q, sc, block_b=64, interpret=True))
        q_ref, sc_ref = ref.quantize_ref(g, sm)
        want = (q_ref, sc_ref, ref.dequantize_ref(q_ref, sc_ref))
    else:
        got = dsc_quantize(g, s, sm, sr, p=0.3, gamma=0.5, block_b=64,
                           interpret=True)
        want = ref.dsc_quantize_ref(g, s, sm, sr, p=0.3, gamma=0.5)
    for a, b in zip(got, want):     # int8 q and scales match exactly
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- flash attention
@pytest.mark.parametrize("S,bq,bk", [(128, 128, 128), (256, 128, 64),
                                     (256, 64, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(S, bq, bk, causal):
    B, H, d = 2, 3, 64
    q = jax.random.normal(KEY, (B, H, S, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, H, S, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, H, S, d))
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=8, deadline=None)
@given(s_blocks=st.integers(1, 4), d=st.sampled_from([32, 64, 128]),
       seed=st.integers(0, 100))
def test_flash_attention_property_sweep(s_blocks, d, seed):
    B, H, bq = 1, 2, 64
    S = s_blocks * bq
    kk = jax.random.PRNGKey(seed)
    q, k, v = (jax.random.normal(jax.random.fold_in(kk, i), (B, H, S, d))
               for i in range(3))
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bq,
                          interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=3e-4, atol=3e-4)


def test_flash_attention_bf16():
    B, H, S, d = 1, 2, 128, 64
    q, k, v = (jax.random.normal(jax.random.fold_in(KEY, i), (B, H, S, d)
                                 ).astype(jnp.bfloat16) for i in range(3))
    out = flash_attention(q, k, v, causal=True, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2, atol=3e-2)
