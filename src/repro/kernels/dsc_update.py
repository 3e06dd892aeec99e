"""Pallas TPU kernel: fused DSC client update.

    v  = (g - s) * mask / p     (mask ~ Bernoulli(p), counter-based RNG)
    s' = s + gamma * v

This is the per-round hot loop every FL client runs over its full update
vector (n = model size).  Unfused it is 4 HBM sweeps (read g, read s,
write v, write s') plus a mask read; the fusion does exactly 2 reads +
2 writes with all arithmetic in VMEM — the op is purely memory-bound, so
the fusion is the roofline optimum.

Tiling: the flat vector is viewed as (rows, 1024) with 1024 = 8*128
lanes (f32 VMEM tile is (8, 128)), padded to whole row blocks; each
grid step processes a (BLOCK_ROWS, 1024) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (interpret_mode, pad_rows, row_blocks,
                                  uniform_from_index)

LANES = 1024          # 8 * 128
BLOCK_ROWS = 256      # (256, 1024) f32 tile = 1 MiB in / 2 MiB out of VMEM


def _kernel(g_ref, s_ref, seed_ref, v_ref, s_out_ref, *, p, gamma, lanes):
    i = pl.program_id(0)
    g = g_ref[...]
    s = s_ref[...]
    rows = g.shape[0]
    base = i * rows * lanes
    idx = (base + jax.lax.broadcasted_iota(jnp.uint32, g.shape, 0) * lanes
           + jax.lax.broadcasted_iota(jnp.uint32, g.shape, 1))
    u = uniform_from_index(idx, seed_ref[0])
    diff = g.astype(jnp.float32) - s
    v = jnp.where(u < p, diff * (1.0 / p), 0.0)
    v_ref[...] = v.astype(v_ref.dtype)
    s_out_ref[...] = s + gamma * v


def dsc_update(g, s, seed, *, p: float, gamma: float,
               block_rows: int = BLOCK_ROWS, interpret: bool | None = None):
    """g: (n,) any float dtype; s: (n,) float32; seed: uint32 scalar.
    Ragged n is zero-padded internally to whole (block_rows, 1024) tiles:
    the padded tail has g = s = 0, so v = 0 and s' = 0 there regardless
    of the mask draw, and the first n coordinates match the unpadded
    oracle exactly (the RNG is indexed by the global flat position, which
    padding does not displace).  Returns (v, s'), both length n."""
    n = g.shape[0]
    block_rows, rows = row_blocks(-(-n // LANES), block_rows)
    seed_arr = jnp.asarray([seed], jnp.uint32) if jnp.ndim(seed) == 0 \
        else seed.astype(jnp.uint32)
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    v, s_new = pl.pallas_call(
        functools.partial(_kernel, p=p, gamma=gamma, lanes=LANES),
        grid=(rows // block_rows,),
        in_specs=[tile, tile, pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=(tile, tile),
        out_shape=(jax.ShapeDtypeStruct((rows, LANES), g.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.float32)),
        interpret=interpret_mode(interpret),
    )(pad_rows(g, rows, LANES), pad_rows(s, rows, LANES), seed_arr)
    return v.reshape(-1)[:n], s_new.reshape(-1)[:n]
