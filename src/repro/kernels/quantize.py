"""Pallas TPU kernels: per-block stochastic int8 (de)quantization.

Beyond-paper wire compression: the FSA reduce-scatter payload drops from
2 B/coord (bf16) to ~1.03 B/coord (int8 + one f32 scale per 256 coords).
Quantization is unbiased (stochastic rounding), so it composes with the
paper's Definition 3.1 analysis as an omega-compressor.

Tiling: flat vector viewed as (n_blocks, 256); a grid step handles
(BLOCK_B, 256) = up to 1 MiB of f32 in VMEM, emitting int8 + scales.
The block count pads up to whole row blocks (``common.row_blocks``), and
the scales travel as a lane-dense (1, n_blocks) row: a 1-D or (n, 1)
scale block cannot be tiled by the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (interpret_mode, pad_rows, row_blocks,
                                  uniform_from_index)

QBLOCK = 256          # coords per scale
BLOCK_B = 1024        # quant blocks per grid step


def wire_payload_bytes(n: int, *, block: int = QBLOCK) -> int:
    """Exact bytes of the quantized wire payload for an n-coordinate
    vector: one int8 per (block-padded) coordinate plus one f32 scale per
    block — what the FSA all_to_all actually puts on the mesh, and what
    the byte-accounting tests/benchmarks compare against the bf16
    baseline (2n)."""
    padded = -(-n // block) * block
    return padded + 4 * (padded // block)


def _quant_kernel(x_ref, seed_ref, q_ref, scale_ref, *, qblock):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # (bb, qblock)
    scale = jnp.max(jnp.abs(x), axis=1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    y = x / safe[:, None]
    low = jnp.floor(y)
    frac = y - low
    base = i * x.shape[0] * qblock
    idx = (base + jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0) * qblock
           + jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1))
    u = uniform_from_index(idx, seed_ref[0])
    q = low + (u < frac).astype(jnp.float32)
    q_ref[...] = jnp.clip(q, -127, 127).astype(jnp.int8)
    scale_ref[...] = scale[None, :]


def _dequant_kernel(q_ref, scale_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * scale_ref[0][:, None]


def quantize(x, seed, *, block_b: int = BLOCK_B,
             interpret: bool | None = None):
    """x: (n,) float.  Ragged n zero-pads to the next 256 multiple (the
    wire layout `wire_payload_bytes` accounts for; zeros quantize to 0
    deterministically and never move a block scale).  Returns
    (q int8 (padded_n,), scales (padded_n/256,)), matching the oracle's
    padded layout."""
    nb = -(-x.shape[0] // QBLOCK)
    block_b, rows = row_blocks(nb, block_b)
    seed_arr = jnp.asarray([seed], jnp.uint32) if jnp.ndim(seed) == 0 \
        else seed.astype(jnp.uint32)
    q, scale = pl.pallas_call(
        functools.partial(_quant_kernel, qblock=QBLOCK),
        grid=(rows // block_b,),
        in_specs=[pl.BlockSpec((block_b, QBLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=(pl.BlockSpec((block_b, QBLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((1, block_b), lambda i: (0, i))),
        out_shape=(jax.ShapeDtypeStruct((rows, QBLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((1, rows), jnp.float32)),
        interpret=interpret_mode(interpret),
    )(pad_rows(x, rows, QBLOCK), seed_arr)
    return q[:nb].reshape(-1), scale[0, :nb]


def dequantize(q, scale, *, block_b: int = BLOCK_B,
               interpret: bool | None = None):
    n = q.shape[0]
    assert n % QBLOCK == 0, n
    nb = n // QBLOCK
    block_b, rows = row_blocks(nb, block_b)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(rows // block_b,),
        in_specs=[pl.BlockSpec((block_b, QBLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((1, block_b), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block_b, QBLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, QBLOCK), jnp.float32),
        interpret=interpret_mode(interpret),
    )(pad_rows(q, rows, QBLOCK), pad_rows(scale, 1, rows))
    return out[:nb].reshape(n)
