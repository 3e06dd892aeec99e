"""Pallas TPU kernel: fused DSC -> int8 wire step, one VMEM pass.

    v    = (g - s) * mask / p            mask ~ Bernoulli(p)
    q, c = int8_quantize(v)              per-256-block stochastic round
    vhat = q * c                         (in-register dequantize)
    s'   = s + gamma * vhat              shift tracks the WIRE value

This replaces the two-kernel chain the int8+DSC rounds used to run
(`dsc_update` then `quantize` then `dequantize` for the round-trip):
read g, read s, write v, write s', read v, write q/scales, read q/scales,
write vhat — ~7 full HBM sweeps of the n-sized update vector.  The fusion
is exactly 2 f32 reads (g, s) + 1 f32 write (s') + the int8 payload out
(q + one f32 scale per 256 coords): the roofline optimum for the
per-round client hot loop, and the shift state sees precisely what
crosses the wire (the Int8RoundTrip composition of Definition 3.1
omega-compressors, so Theorem 3.2's contraction bookkeeping still holds).

Tiling: flat vector viewed as (n_blocks, 256), padded to whole row
blocks; each grid step handles a (BLOCK_B, 256) tile and writes its
scales into a lane-dense (1, n_blocks) row.  Both RNG draws are counter-based (murmur3 on the
global flat element index), identical to `ref.dsc_quantize_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (interpret_mode, pad_rows, row_blocks,
                                  uniform_from_index)
from repro.kernels.quantize import QBLOCK

BLOCK_B = 1024        # quant blocks per grid step -> (1024, 256) f32 tiles


def _kernel(g_ref, s_ref, seeds_ref, q_ref, scale_ref, s_out_ref, *,
            p, gamma, qblock):
    i = pl.program_id(0)
    g = g_ref[...].astype(jnp.float32)              # (bb, qblock)
    s = s_ref[...]
    base = i * g.shape[0] * qblock
    idx = (base + jax.lax.broadcasted_iota(jnp.uint32, g.shape, 0) * qblock
           + jax.lax.broadcasted_iota(jnp.uint32, g.shape, 1))
    # --- DSC sparsify (Algorithm 1 line 4) -------------------------------
    u_mask = uniform_from_index(idx, seeds_ref[0])
    v = jnp.where(u_mask < p, (g - s) * (1.0 / p), 0.0)
    # --- per-block stochastic int8 ---------------------------------------
    scale = jnp.max(jnp.abs(v), axis=1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    y = v / safe[:, None]
    low = jnp.floor(y)
    u_round = uniform_from_index(idx, seeds_ref[1])
    q = jnp.clip(low + (u_round < (y - low)).astype(jnp.float32),
                 -127, 127)
    # --- shift update against the dequantized wire value -----------------
    vhat = q * scale[:, None]
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale[None, :]
    s_out_ref[...] = s + gamma * vhat


def dsc_quantize(g, s, seed_mask, seed_round, *, p: float, gamma: float,
                 block_b: int = BLOCK_B, interpret: bool | None = None):
    """g: (n,) float; s: (n,) float32; seeds: uint32 scalars.  Ragged n is
    zero-padded internally to whole row blocks (zero diff -> zero v ->
    the padded tail never perturbs scales or shift state).

    Returns (q int8 (padded_n,), scales f32 (padded_n/256,), s' f32 (n,))
    with padded_n the next 256 multiple of n.  q/scales keep that wire
    layout (what `wire_payload_bytes` accounts for); s' is sliced back to
    n."""
    n = g.shape[0]
    nb = -(-n // QBLOCK)
    block_b, rows = row_blocks(nb, block_b)
    seeds = jnp.stack([jnp.asarray(seed_mask, jnp.uint32).reshape(()),
                       jnp.asarray(seed_round, jnp.uint32).reshape(())])
    tile = pl.BlockSpec((block_b, QBLOCK), lambda i: (i, 0))
    q, scale, s_new = pl.pallas_call(
        functools.partial(_kernel, p=p, gamma=gamma, qblock=QBLOCK),
        grid=(rows // block_b,),
        in_specs=[tile, tile, pl.BlockSpec((2,), lambda i: (0,))],
        out_specs=(tile, pl.BlockSpec((1, block_b), lambda i: (0, i)), tile),
        out_shape=(jax.ShapeDtypeStruct((rows, QBLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((1, rows), jnp.float32),
                   jax.ShapeDtypeStruct((rows, QBLOCK), jnp.float32)),
        interpret=interpret_mode(interpret),
    )(pad_rows(g, rows, QBLOCK), pad_rows(s, rows, QBLOCK), seeds)
    return q[:nb].reshape(-1), scale[0, :nb], s_new.reshape(-1)[:n]
