"""Attention correctness: chunked == unchunked, GQA grouping, sliding
window, decode-vs-prefill consistency (incl. ring buffer)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L

KEY = jax.random.PRNGKey(0)


def naive_attention(q, k, v, window=None, q_offset=0):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k) / np.sqrt(hd)
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(k.shape[1])
    mask = kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, -1)
    return jnp.einsum("bkgqs,bskh->bqkgh", w, v).reshape(B, Sq, H, hd)


@pytest.mark.parametrize("S,chunk", [(64, 64), (64, 16), (60, 16), (7, 3)])
@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (6, 1)])
def test_chunked_equals_naive(S, chunk, H, KV):
    hd, B = 8, 2
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KV, hd))
    got = L.causal_attention(q, k, v, chunk=chunk)
    ref = naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_sliding_window_masks_far_keys():
    B, S, H, hd, W = 1, 32, 2, 4, 8
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, H, hd))
    got = L.causal_attention(q, k, v, window=W, chunk=16)
    ref = naive_attention(q, k, v, window=W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)
    # changing a key outside every window must not change outputs
    k2 = k.at[:, 0].set(99.0)
    got2 = L.causal_attention(q, k2, v, window=W, chunk=16)
    np.testing.assert_allclose(np.asarray(got2[:, W:]),
                               np.asarray(got[:, W:]), atol=2e-5)


def test_decode_matches_prefill_row():
    """decode_attention at position p == row p of full causal attention."""
    B, S, H, KV, hd = 2, 24, 4, 2, 8
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 5), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 6), (B, S, KV, hd))
    full = naive_attention(q, k, v)
    for p in [0, 5, 23]:
        got = L.decode_attention(q[:, p:p + 1], k, v, p)
        np.testing.assert_allclose(np.asarray(got[:, 0]),
                                   np.asarray(full[:, p]), atol=2e-5)


def test_decode_ring_buffer_window():
    """Ring-buffered sliding-window cache == windowed attention."""
    B, S, H, hd, W = 1, 20, 2, 4, 8
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 7), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 8), (B, S, H, hd))
    ref = naive_attention(q, k, v, window=W)
    k_cache = jnp.zeros((B, W, H, hd))
    v_cache = jnp.zeros((B, W, H, hd))
    for p in range(S):
        slot = p % W
        k_cache = k_cache.at[:, slot].set(k[:, p])
        v_cache = v_cache.at[:, slot].set(v[:, p])
        got = L.decode_attention(q[:, p:p + 1], k_cache, v_cache, p, window=W)
        np.testing.assert_allclose(np.asarray(got[:, 0]),
                                   np.asarray(ref[:, p]), atol=2e-5,
                                   err_msg=f"pos {p}")


def test_rope_relative():
    """RoPE: dot products depend only on relative distance."""
    hd = 16
    x = jax.random.normal(KEY, (1, 1, 1, hd))
    y = jax.random.normal(jax.random.fold_in(KEY, 9), (1, 1, 1, hd))
    def dot_at(p, q):
        xp = L.rope(x, jnp.array([[p]]))
        yq = L.rope(y, jnp.array([[q]]))
        return float((xp * yq).sum())
    assert dot_at(3, 1) == pytest.approx(dot_at(12, 10), abs=1e-4)
    assert dot_at(3, 1) != pytest.approx(dot_at(3, 2), abs=1e-3)


def test_flash_shape_gate_falls_back_loudly_once(caplog):
    """A sequence the flash kernel cannot tile takes the chunked path,
    and says so once, not silently and not on every trace."""
    import dataclasses
    import logging
    from repro.configs import get_config
    from repro.kernels import flash_attention as fa
    from repro.kernels.common import log_fallback
    from repro.models import transformer as tr
    cfg = dataclasses.replace(get_config("qwen2-0.5b").smoke(), n_layers=1)
    assert cfg.flash_attention and not fa.supports(200, cfg.hd)
    params = tr.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (1, 200), 0, cfg.vocab)
    log_fallback.cache_clear()
    with caplog.at_level(logging.WARNING, logger="repro.kernels"):
        losses = [tr.loss_fn(params, cfg, {"tokens": toks}) for _ in range(2)]
    assert np.isfinite(np.asarray(losses)).all()
    assert sum("flash_attention" in r.getMessage()
               for r in caplog.records) == 1
