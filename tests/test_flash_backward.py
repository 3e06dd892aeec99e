"""Flash-attention TRAINING path: custom-VJP backward gradient parity.

The forward is pinned to the oracle in test_kernels.py; here jax.grad
through the Pallas kernels (interpret mode on CPU) must match jax.grad
through the naive jnp reference — the blocked backward recomputes
p = exp(s - lse) per tile instead of saving the S x S score matrix, so
any drift in the recompute (mask bounds, GQA group sums, lse handling)
shows up as gradient error here and nowhere else.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention, supports
from repro.models import layers
from repro.models import transformer as tr

KEY = jax.random.PRNGKey(0)


def _qkv(B, H, KV, S, d, seed=0):
    kk = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(kk, 0), (B, H, S, d))
    k = jax.random.normal(jax.random.fold_in(kk, 1), (B, KV, S, d))
    v = jax.random.normal(jax.random.fold_in(kk, 2), (B, KV, S, d))
    return q, k, v


def _grad_parity(B, H, KV, S, d, *, causal, window, bq=64, bk=64,
                 plan=None):
    q, k, v = _qkv(B, H, KV, S, d)
    w = jax.random.normal(jax.random.fold_in(KEY, 9), (B, H, S, d))

    def loss_flash(q, k, v):
        if plan is None:
            o = flash_attention(q, k, v, causal=causal, window=window,
                                block_q=bq, block_k=bk, interpret=True)
        else:
            o = fa._flash(q, k, v, (causal, window, plan, True))
        return jnp.sum(o * w)

    def loss_ref(q, k, v):
        return jnp.sum(ref.flash_attention_ref(
            q, k, v, causal=causal, window=window) * w)

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    exp = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, g, e in zip("qkv", got, exp):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(e), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name} B={B} H={H} KV={KV} S={S} d={d} "
                    f"causal={causal} window={window}")


# TP-local head counts: 8 heads at tp=1, the tp=2 shard (4 heads), and
# the tp=4 shard with grouped KV (the shapes _attn hands the kernel)
@pytest.mark.parametrize("H,KV", [(8, 8), (4, 2), (2, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_ref(H, KV, causal):
    _grad_parity(2, H, KV, 128, 32, causal=causal, window=None)


@pytest.mark.parametrize("H,KV", [(4, 4), (4, 1)])
@pytest.mark.parametrize("window", [32, 64])
def test_flash_backward_sliding_window(H, KV, window):
    _grad_parity(1, H, KV, 128, 32, causal=True, window=window)


def test_flash_backward_uneven_blocks():
    # block_q != block_k exercises the asymmetric loop bounds in both
    # the dq and dkv kernels
    _grad_parity(1, 2, 2, 256, 32, causal=True, window=None, bq=128, bk=64)
    _grad_parity(1, 2, 2, 256, 32, causal=True, window=64, bq=64, bk=128)


# a GQA group split over several steps: the forward / dq tiles stack
# part of the group, and the dk/dv grid's last axis walks the group in
# chunks summed in its f32 scratch
@pytest.mark.parametrize("heads,heads_dkv", [(2, 1), (1, 2), (4, 4)])
@pytest.mark.parametrize("window", [None, 48])
def test_flash_backward_head_chunks(heads, heads_dkv, window):
    plan = fa.Tiles(block_q=64, block_k=32, heads=heads,
                    heads_dkv=heads_dkv, buffers=1)
    _grad_parity(1, 8, 2, 128, 32, causal=True, window=window, plan=plan)


def test_supports_gate():
    assert supports(128, 64) and supports(1024, 64)
    assert supports(64, 32)          # blocks clamp to S
    assert not supports(192, 32)     # 192 % min(128, 192) != 0
    assert supports(256, 32, block_q=64, block_k=128)
    assert not supports(256, 32, block_q=96)
    # grouped-query heads: qwen2-0.5b's 7 query heads per kv head get a
    # plan exactly where the gate admits, at the lengths the model trains
    # and up to the longest the gate admits
    for S in (1024, 4096, 8192, 16384, 32768):
        assert supports(S, 64, itemsize=2) == (S <= 16384)
        assert (fa.tiles(S, 64, 7, 2) is not None) == supports(S, 64, 2)
    assert fa.tiles(64, 32, 4, 4) is not None
    assert fa.tiles(192, 32, 7, 4) is None


def _old_gate(S, d, itemsize):
    """What the gate admitted before heads were stacked: both block
    sizes tile S, and K/V (or q/dO) double-buffered fit 12 MiB."""
    return (S % min(128, S) == 0
            and 2 * 2 * S * max(d, 128) * itemsize <= 12 * 1024 * 1024)


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("G", [1, 2, 4, 7, 8, 16, 32])
def test_tiles_fit_vmem_and_tile_the_sequence(G, itemsize):
    """The tile chooser, a pure function of (S, d, G, itemsize): every
    plan tiles S and the group exactly and fits the VMEM budget, and
    every shape the gate admitted before is still admitted."""
    for S in (32, 64, 128, 192, 256, 384, 512, 640, 1024, 1536, 2048,
              3072, 4096, 6144, 8192, 12288, 16384, 32768):
        for d in (32, 64, 96, 128):
            t = fa.tiles(S, d, G, itemsize)
            assert t == fa.tiles(S, d, G, itemsize)
            assert supports(S, d, itemsize) == (t is not None)
            if _old_gate(S, d, itemsize):
                assert t is not None, (S, d, G, itemsize)
            if t is None:
                continue
            assert S % t.block_q == 0 and S % t.block_k == 0, t
            assert G % t.heads == 0 and G % t.heads_dkv == 0, t
            assert t.buffers in (1, 2)
            assert fa._vmem_bytes(S, d, itemsize, t) <= fa.WHOLE_SEQ_VMEM


def test_tiles_of_the_benchmarked_shapes():
    """qwen2-0.5b (7 query heads per kv head, d 64) stacks its whole
    group in every step; phi3-mini (one query head per kv head, d 96)
    gets a block above 128 rows instead."""
    qwen2 = fa.tiles(1024, 64, 7, 2)
    assert qwen2.heads == qwen2.heads_dkv == 7
    assert qwen2.heads * qwen2.block_q > 7 * 128
    phi3 = fa.tiles(1024, 96, 1, 2)
    assert max(phi3.block_q, phi3.block_k) > 128


def _rel_err(x, expect):
    x = np.asarray(x, np.float64)
    expect = np.asarray(expect, np.float64)
    return np.linalg.norm(x - expect) / np.linalg.norm(expect)


@functools.lru_cache(maxsize=None)
def _bf16_errors(H, KV, d):
    """{o, dq, dk, dv: (kernel error, model XLA attention error)}, each
    a relative error against the f32 reference on the upcast bf16
    inputs."""
    B, S = 1, 256
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(B, H, KV, S, d, seed=3))
    w = jax.random.normal(jax.random.fold_in(KEY, 7), (B, H, S, d))

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True)

    def model_xla(q, k, v):
        return layers.causal_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2)
        ).swapaxes(1, 2)

    def reference(q, k, v):
        return ref.flash_attention_ref(q, k, v, causal=True)

    def outputs(attend, q, k, v):
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32) * w)
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return (attend(q, k, v),) + grads

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    exp = outputs(reference, *f32)
    got = outputs(kernel, q, k, v)
    xla = outputs(model_xla, q, k, v)
    return {name: (_rel_err(g, e), _rel_err(x, e))
            for name, g, x, e in zip(("o", "dq", "dk", "dv"), got, xla, exp)}


# bf16 at qwen2-0.5b's group shape and phi3-mini's (H == KV, d 96): the
# kernel's error against the f32 reference is held to that of the
# model's own bf16 XLA attention on the same inputs, so the tolerance is
# the model's stated precision, not a chosen constant
@pytest.mark.parametrize("quantity", ["o", "dq", "dk", "dv"])
@pytest.mark.parametrize("H,KV,d", [(14, 2, 64), (4, 4, 96)],
                         ids=["qwen2-group", "phi3-heads"])
def test_flash_bf16_error_within_model_attention(H, KV, d, quantity):
    kernel_err, xla_err = _bf16_errors(H, KV, d)[quantity]
    assert kernel_err <= 1.5 * xla_err, (quantity, kernel_err, xla_err)


def test_model_train_grads_flash_vs_naive():
    """End-to-end: loss_fn grads with ModelConfig.flash_attention on ==
    the naive chunked-attention path (same params, same batch)."""
    cfg = get_config("qwen2-0.5b").smoke()
    assert supports(64, cfg.hd)
    params = tr.init_params(KEY, cfg)
    batch = {"tokens": jax.random.randint(jax.random.fold_in(KEY, 1),
                                          (2, 64), 0, cfg.vocab)}
    loss_n, g_n = jax.value_and_grad(tr.loss_fn)(params, cfg, batch)
    cfg_f = dataclasses.replace(cfg, flash_attention=True)
    loss_f, g_f = jax.value_and_grad(tr.loss_fn)(params, cfg_f, batch)
    np.testing.assert_allclose(float(loss_f), float(loss_n), rtol=1e-5)
    flat_n = jax.tree_util.tree_leaves(g_n)
    flat_f = jax.tree_util.tree_leaves(g_f)
    for a, b in zip(flat_f, flat_n):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
