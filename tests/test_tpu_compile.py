"""Compile every Pallas kernel for a described TPU v5e chip.

Interpret mode runs the kernel bodies on the CPU but cannot see what the
chip's compiler refuses: blocks that break the (8, 128) tiling, casts
Mosaic lacks, or more fast memory than a kernel may hold.  These tests
lower each kernel at qwen2-0.5b widths with ``interpret=False`` for one
chip of a ``v5e:2x2`` topology that is described, not attached, and
check that the compiled program holds the Mosaic kernel.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import base64
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import dsc_quantize as dq
from repro.kernels import dsc_update as du
from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa
from repro.kernels import quantize as qk

CFG = get_config("qwen2-0.5b")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# the whole model as one flat update vector (the simulator's layout)
N_MODEL = CFG.param_count()


def test_quantize_dequantize_compile(one_chip):
    x = _spec(one_chip, (N_MODEL,), jnp.float32)
    seed = _spec(one_chip, (), jnp.uint32)
    _compile(functools.partial(qk.quantize, interpret=False), x, seed)
    n_pad = -(-N_MODEL // qk.QBLOCK) * qk.QBLOCK
    _compile(functools.partial(qk.dequantize, interpret=False),
             _spec(one_chip, (n_pad,), jnp.int8),
             _spec(one_chip, (n_pad // qk.QBLOCK,), jnp.float32))


@pytest.mark.parametrize("kernel", ["dsc_update", "dsc_quantize"])
def test_dsc_kernels_compile(one_chip, kernel):
    g = _spec(one_chip, (N_MODEL,), jnp.float32)
    seed = _spec(one_chip, (), jnp.uint32)
    if kernel == "dsc_update":
        _compile(functools.partial(du.dsc_update, p=0.1, gamma=0.5,
                                   interpret=False),
                 g, g, seed)
    else:
        _compile(functools.partial(dq.dsc_quantize, p=0.1, gamma=0.5,
                                   interpret=False),
                 g, g, seed, seed)


def _flash_grad(sharding, seq, heads=(CFG.n_heads, CFG.n_kv_heads),
                d=CFG.hd, plan=None):
    B, (H, KV) = 8, heads

    def attend(q, k, v):
        if plan is None:
            return fa.flash_attention(q, k, v, causal=True, interpret=False)
        return fa._flash(q, k, v, (True, None, plan, False))

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    q = _spec(sharding, (B, H, seq, d), jnp.bfloat16)
    kv = _spec(sharding, (B, KV, seq, d), jnp.bfloat16)
    return functools.partial(_compile, jax.grad(loss, argnums=(0, 1, 2)),
                             q, kv, kv)


@pytest.mark.parametrize("seq", [1024, 4096, 8192, 16384])
def test_flash_forward_backward_compile(one_chip, seq):
    """Forward and custom-VJP backward at the train_1k / train_4k
    sequence lengths up to the longest the gate admits, qwen2-0.5b's
    heads, bf16 activations."""
    assert fa.supports(seq, CFG.hd, itemsize=2)
    _flash_grad(one_chip, seq)()


def test_flash_compile_full_multi_head_width(one_chip):
    """Phi-3-mini's attention (32 heads, each its own kv head, head_dim
    96): one head per tile, so the tiles grow along the sequence."""
    assert fa.tiles(1024, 96, 1, 2).block_q > 128
    _flash_grad(one_chip, 1024, (32, 32), 96)()


def test_flash_supports_refuses_what_vmem_cannot_hold(one_chip):
    """The kernels hold whole-sequence operands in fast memory; the gate
    refuses the first sequence length the chip's compiler cannot fit,
    even with the smallest tiles."""
    assert not fa.supports(32768, CFG.hd, itemsize=2)
    smallest = fa.Tiles(block_q=128, block_k=128, heads=1, heads_dkv=1,
                        buffers=1)
    with pytest.raises(Exception, match="vmem"):
        _flash_grad(one_chip, 32768, plan=smallest)()


def test_flash_compiles_to_the_three_named_kernels(one_chip):
    """Forward + backward at qwen2-0.5b's shapes is exactly three Pallas
    calls whose Mosaic bodies name the forward, dq and dk/dv kernels, as
    the benchmark's ``bench/hlo.py`` reads them, and this file.  (The
    source file the reader puts before the name is the first one a body
    lists, which depends on the caller: the benchmark's round reads
    ``flash_attention``.)"""
    from bench.hlo import pallas_kernels
    text = _flash_grad(one_chip, 1024)().as_text()
    kernels = pallas_kernels(text).values()
    assert sorted(k.rsplit(".", 1)[1] for k in kernels) == [
        "_dkv_kernel", "_dq_kernel", "_fwd_kernel"]
    bodies = [line for line in text.splitlines()
              if "tpu_custom_call" in line and "=" in line]
    assert len(bodies) == 3
    assert all(b"flash_attention.py" in base64.b64decode(
        re.search(r'"body":"([^"]*)"', line).group(1)) for line in bodies)


def test_paged_attention_compile(one_chip):
    """One decode step of the serving engine's default pool layout."""
    B, H, KV, hd, bs, n_blocks, pages = (8, CFG.n_heads, CFG.n_kv_heads,
                                         CFG.hd, 16, 128, 16)
    assert pa.supports(H, KV, hd)
    fn = functools.partial(pa.paged_attention, interpret=False)
    _compile(fn, _spec(one_chip, (B, H, hd), jnp.bfloat16),
             _spec(one_chip, (n_blocks, KV, bs, hd), jnp.bfloat16),
             _spec(one_chip, (n_blocks, KV, bs, hd), jnp.bfloat16),
             _spec(one_chip, (B, pages), jnp.int32),
             _spec(one_chip, (B,), jnp.int32))
