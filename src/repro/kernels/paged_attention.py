"""Pallas TPU kernel: paged decode attention through a block table.

The serving hot spot.  The paged KV cache (``repro/serve/cache.py``)
stores keys/values in fixed-size blocks of a global pool; each request
owns a block table mapping its logical pages to pool blocks.  This
kernel computes one decode step of GQA attention for a batch of
requests WITHOUT gathering their K/V into contiguous buffers: the block
table rides in as a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index maps
themselves chase the page indirection — grid step (b, h, p) streams
pool block ``table[b, p]`` of kv-head ``h`` through VMEM.

Grid = (batch, kv_heads, pages); the online-softmax state (running max,
sum, accumulator for the G grouped query heads) lives in VMEM scratch
and accumulates across the page dimension — the flash-attention
recurrence over pages instead of key blocks.  Pages past a request's
context length are masked (their table entries may be stale or 0 — the
allocator's scratch block); sliding windows mask positions below
``ctx - window``.  A fully-masked request (ctx == 0, an inactive
engine slot) produces zeros.

The pool layout is ``(num_blocks, KV, block_size, hd)``; the kernel
views it as ``(num_blocks * KV, block_size, hd)`` so one index-map
expression ``table[b, p] * KV + h`` addresses the (block, kv-head) row.
Head counts are whatever the caller holds — under tensor parallelism
these are the TP-local heads; the kernel never communicates.

``supports()`` gates shapes onto :func:`paged_attention_ref`, the
jnp gather reference — numerically the same computation with the
(B, P*bs) score matrix materialized.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode

NEG_INF = -1e30


def supports(n_heads: int, n_kv_heads: int, head_dim: int) -> bool:
    """Shapes the Pallas kernel handles; anything else takes the naive
    gather path (same contract as ``flash_attention.supports``)."""
    return (n_heads % n_kv_heads == 0 and head_dim % 2 == 0
            and head_dim >= 8)


def _kernel(tbl_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, block_size, window, sm_scale):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = ctx_ref[b]
    q = q_ref[0].astype(jnp.float32) * sm_scale       # (G, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bs, hd)
    v = v_ref[0].astype(jnp.float32)
    s = q @ k.T                                       # (G, bs)
    pos = p * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < ctx
    if window is not None:
        valid &= pos >= ctx - window
    s = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev, acc = m_ref[...], l_ref[...], acc_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    # zero masked lanes explicitly: when every key so far is masked,
    # m_cur == NEG_INF and exp(s - m_cur) would be 1, not 0 — an
    # inactive slot (ctx == 0) must come out all-zero, not mean(v)
    pexp = jnp.where(valid, jnp.exp(s - m_cur[:, None]), 0.0)
    l_cur = l_prev * alpha + pexp.sum(axis=1)
    acc = acc * alpha[:, None] + pexp @ v
    m_ref[...], l_ref[...], acc_ref[...] = m_cur, l_cur, acc

    @pl.when(p == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, context_lens: jax.Array, *,
                    window: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """One decode step of paged GQA attention.

    q:            (B, H, hd)  — the new tokens' query heads
    k_pool/v_pool:(N, KV, bs, hd) — the global block pools
    block_tables: (B, P) int32 — pool block of each request's page p
                  (entries past the request's pages must still be valid
                  pool indices, e.g. 0)
    context_lens: (B,) int32 — valid positions per request INCLUDING the
                  token being decoded (its K/V already written)
    window:       sliding window — keys at ctx-window <= j < ctx attend

    Returns (B, H, hd) in q's dtype.
    """
    B, H, hd = q.shape
    N, KV, bs, _ = k_pool.shape
    P = block_tables.shape[1]
    if H % KV:
        raise ValueError(f"n_heads ({H}) must be a multiple of "
                         f"n_kv_heads ({KV})")
    G = H // KV
    qf = q.reshape(B, KV, G, hd).reshape(B * KV, G, hd)
    kf = k_pool.reshape(N * KV, bs, hd)
    vf = v_pool.reshape(N * KV, bs, hd)
    kernel = functools.partial(_kernel, block_size=bs, window=window,
                               sm_scale=hd ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, P),
        in_specs=[
            pl.BlockSpec((1, G, hd),
                         lambda b, h, p, tbl, ctx: (b * KV + h, 0, 0)),
            pl.BlockSpec((1, bs, hd),
                         lambda b, h, p, tbl, ctx: (tbl[b, p] * KV + h,
                                                    0, 0)),
            pl.BlockSpec((1, bs, hd),
                         lambda b, h, p, tbl, ctx: (tbl[b, p] * KV + h,
                                                    0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, G, hd), lambda b, h, p, tbl, ctx: (b * KV + h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G, hd), jnp.float32),
                        pltpu.VMEM((G,), jnp.float32),
                        pltpu.VMEM((G,), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, hd), q.dtype),
        interpret=interpret_mode(interpret),
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      qf, kf, vf)
    return out.reshape(B, H, hd)


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        block_tables: jax.Array, context_lens: jax.Array, *,
                        window: Optional[int] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """jnp reference / fallback: gather each request's pages from the
    pools, then masked softmax attention.  Same signature and semantics
    as :func:`paged_attention` (``interpret`` accepted and ignored)."""
    del interpret
    B, H, hd = q.shape
    N, KV, bs, _ = k_pool.shape
    P = block_tables.shape[1]
    G = H // KV
    tbl = block_tables.astype(jnp.int32)
    # (B, P, KV, bs, hd) -> (B, KV, P*bs, hd)
    ks = k_pool[tbl].transpose(0, 2, 1, 3, 4).reshape(B, KV, P * bs, hd)
    vs = v_pool[tbl].transpose(0, 2, 1, 3, 4).reshape(B, KV, P * bs, hd)
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bksh->bkgs", qg.astype(jnp.float32),
                   ks.astype(jnp.float32)) * (hd ** -0.5)
    pos = jnp.arange(P * bs)
    valid = pos[None] < context_lens[:, None]
    if window is not None:
        valid &= pos[None] >= context_lens[:, None] - window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    # a fully-masked row (inactive slot) must produce zeros, not mean(v):
    # with m == NEG_INF, exp(s - m) is 1 at masked lanes, so zero them
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - jax.lax.stop_gradient(m)) * valid[:, None, None]
    denom = jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    w = (e / denom).astype(vs.dtype)
    out = jnp.einsum("bkgs,bksh->bkgh", w, vs)
    return out.reshape(B, H, hd).astype(q.dtype)
