"""Shared kernel utilities: backend choice, row tiling, counter-based PRNG.

The DSC / QSGD kernels need per-element random bits *inside* the kernel
(reading a pre-generated mask from HBM would double the memory traffic the
fusion exists to avoid).  We use a counter-based hash (murmur3 finalizer)
keyed on (seed, element index): identical in the Pallas kernel and the
pure-jnp oracle, so correctness tests are exact."""
from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

# Row blocks are multiples of the sublane tiling of every dtype the
# kernels move: 8 rows for 32-bit, 16 for bf16, 32 for int8.
ROW_TILE = 32


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.  An explicit
    ``interpret`` wins; otherwise the default backend decides at call
    time: interpret on the CPU, compile with Mosaic on the TPU, and fail
    on any other backend rather than run a program it was not written
    for."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"the Pallas kernels target the TPU (interpret "
                       f"mode on the CPU); backend {backend!r} is neither")


@functools.lru_cache(maxsize=None)
def log_fallback(path: str, reason: str) -> None:
    """Say once per (path, reason) that a shape gate sent work to the
    plain jnp path instead of a Pallas kernel."""
    logging.getLogger("repro.kernels").warning(
        "%s: %s; running the jnp reference path instead", path, reason)


def row_blocks(rows: int, cap: int) -> tuple[int, int]:
    """(block, padded_rows) for a row-blocked kernel over ``rows`` rows:
    the block is the smallest multiple of ``ROW_TILE`` covering ``rows``,
    capped at ``cap`` rounded down to a tile multiple (at least one
    tile), and the rows pad up to whole blocks.  The RNG is indexed by
    global position, so the block never changes a value."""
    cap = max(ROW_TILE, cap - cap % ROW_TILE)
    block = min(cap, -(-rows // ROW_TILE) * ROW_TILE)
    return block, -(-rows // block) * block


def pad_rows(x, rows: int, width: int):
    """Zero-pad flat ``x`` to ``rows * width`` and view it as rows."""
    return jnp.pad(x, (0, rows * width - x.shape[0])).reshape(rows, width)


def hash_u32(x):
    """murmur3 fmix32 — high-quality 32-bit mixer (expressible in both
    Pallas and plain jnp)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def uniform_from_index(idx, seed):
    """U(0,1) from a global element index and a uint32 seed.  The 24-bit
    draw goes through int32 (exact below 2^24): Mosaic has no
    uint32 -> float32 cast."""
    bits = hash_u32(idx.astype(jnp.uint32) ^ seed.astype(jnp.uint32))
    return ((bits >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))
