"""Building blocks: RMSNorm, RoPE, chunked-causal GQA attention."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


# ------------------------------------------------ tensor-parallel region
# Megatron's f/g conjugate pair as custom-vjp collectives.  A TP region is
#     y = tp_pull(partial(tp_push(x) @ W_col) @ W_row)
# tp_push marks the region entry: the forward is free (x is already
# replicated over the model axis) but each shard's backward contributes
# only ITS columns' share of dL/dx, so the cotangent is psum'd.  tp_pull
# marks the exit: the row-parallel partial products are psum'd forward,
# and the (replicated) cotangent passes through untouched.  Exactly two
# collectives per matmul pair, forward and backward — the naive psum
# transpose rule would instead compound a factor of tp per region.
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_push(x, axis):
    """Enter a TP region: identity forward, psum(cotangent) backward."""
    return x


def _tp_push_fwd(x, axis):
    return x, None


def _tp_push_bwd(axis, _, ct):
    return (jax.lax.psum(ct, axis),)


tp_push.defvjp(_tp_push_fwd, _tp_push_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_pull(x, axis):
    """Exit a TP region: psum(partials) forward, identity backward."""
    return jax.lax.psum(x, axis)


def _tp_pull_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _tp_pull_bwd(axis, _, ct):
    return (ct,)


tp_pull.defvjp(_tp_pull_fwd, _tp_pull_bwd)


# --------------------------------------------- sequence-parallel region
# The psum_scatter/all_gather conjugates of the psum pair above.  Under a
# sequence-parallel plan the activations BETWEEN TP regions are sharded
# along the sequence dim: a region is entered by gathering the full
# sequence (tp_seq_gather: all-gather fwd, reduce-scatter bwd — each
# shard's cotangent is a partial sum over its columns/slice) and exited
# by reduce-scattering the row-parallel partials (tp_seq_scatter:
# psum_scatter fwd, all-gather bwd).  all_reduce == all_gather ∘
# reduce_scatter, so the wire bytes equal the psum pair's — but the
# norm/residual regions in between hold 1/tp of the activations.
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def tp_seq_gather(x, axis, dim):
    """Enter a TP region from sequence shards: all-gather forward,
    psum_scatter(cotangent) backward."""
    return jax.lax.all_gather(x, axis, axis=dim, tiled=True)


def _tp_seq_gather_fwd(x, axis, dim):
    return jax.lax.all_gather(x, axis, axis=dim, tiled=True), None


def _tp_seq_gather_bwd(axis, dim, _, ct):
    return (jax.lax.psum_scatter(ct, axis, scatter_dimension=dim,
                                 tiled=True),)


tp_seq_gather.defvjp(_tp_seq_gather_fwd, _tp_seq_gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def tp_seq_scatter(x, axis, dim):
    """Exit a TP region to sequence shards: psum_scatter(partials)
    forward, all-gather(cotangent) backward."""
    return jax.lax.psum_scatter(x, axis, scatter_dimension=dim, tiled=True)


def _tp_seq_scatter_fwd(x, axis, dim):
    return (jax.lax.psum_scatter(x, axis, scatter_dimension=dim,
                                 tiled=True), None)


def _tp_seq_scatter_bwd(axis, dim, _, ct):
    return (jax.lax.all_gather(ct, axis, axis=dim, tiled=True),)


tp_seq_scatter.defvjp(_tp_seq_scatter_fwd, _tp_seq_scatter_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_psum(x, axis):
    """psum forward AND backward — for reduction statistics whose output
    is consumed on every shard (e.g. the channel-sharded RMS-norm
    variance): every position's cotangent contributes to every
    position's operand, so the backward must itself sum over the axis.
    (Under the manual region's check_vma=False a plain ``jax.lax.psum``
    happens to transpose to psum as well, but spelling the pair out
    keeps the semantics independent of that implementation detail —
    see ``tp_pull`` for the identity-backward exit.)"""
    return jax.lax.psum(x, axis)


def _tp_psum_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _tp_psum_bwd(axis, _, ct):
    return (jax.lax.psum(ct, axis),)


tp_psum.defvjp(_tp_psum_fwd, _tp_psum_bwd)


# ------------------------------------- overlapped (ring) model collectives
# An all-reduce decomposed into 2(n-1) ppermute steps (reduce-scatter ring
# then all-gather ring), double-buffered: the payload is split into TWO
# interleaved chunk rings whose sends are issued back-to-back each step,
# so one ring's DMA overlaps the other ring's add — and, unlike the
# monolithic all-reduce, every step is an independent async send the
# scheduler can overlap with neighbouring matmuls.  Total wire bytes are
# identical to the all-reduce (2(n-1)/n of the payload per link);
# `benchmarks/roofline.py` credits collective-permute bytes as
# overlappable when scoring `terms_s`.
#
# Works inside the fully-manual shard_map train body: the device's ring
# position is recovered without `axis_index` (unsupported there on this
# jax pin) from a one-f32-per-device psum_scatter of an iota.
def _ring_index(axis, n):
    iot = jnp.arange(n, dtype=jnp.float32)
    return (jax.lax.psum_scatter(iot, axis, scatter_dimension=0,
                                 tiled=True) / n)[0].astype(jnp.int32)


def ring_all_reduce(x, axis, n: int, *, buffers: int = 2):
    """psum(x, axis) computed as double-buffered ppermute chunk rings.
    ``n`` is the static size of the mesh axis."""
    if n == 1:
        return x
    sh, dt = x.shape, x.dtype
    flat = x.reshape(-1)
    m = flat.shape[0]
    nchunks = n * buffers
    pad = (-m) % nchunks
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(nchunks, -1)
    idx = _ring_index(axis, n)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(r, j):
        # ring r owns the contiguous row block [r*n, (r+1)*n)
        return jax.lax.dynamic_index_in_dim(chunks, r * n + j % n, 0,
                                            keepdims=False)

    # reduce-scatter phase: after n-1 steps device i holds the fully
    # reduced chunk i of every ring
    accs = [local(r, idx + n - 1) for r in range(buffers)]
    for step in range(n - 1):
        accs = [jax.lax.ppermute(a, axis, perm) for a in accs]
        accs = [a + local(r, idx + n - 2 - step)
                for r, a in enumerate(accs)]
    # all-gather phase: circulate the reduced chunks back around
    out = jnp.zeros_like(chunks)
    for r in range(buffers):
        out = jax.lax.dynamic_update_index_in_dim(out, accs[r],
                                                  r * n + idx, 0)
    bufs = accs
    for step in range(1, n):
        bufs = [jax.lax.ppermute(b, axis, perm) for b in bufs]
        for r in range(buffers):
            out = jax.lax.dynamic_update_index_in_dim(
                out, bufs[r], r * n + (idx - step) % n, 0)
    flat_out = out.reshape(-1)
    if pad:
        flat_out = flat_out[:m]
    return flat_out.reshape(sh).astype(dt)


# Ring-decomposed conjugates of the tp_push/tp_pull/tp_psum trio above —
# same contract, but every model-axis sum is the overlappable ring.  Kept
# as separate custom-vjp functions (``ring`` = static axis size) so the
# default psum pair stays byte-identical for existing configs.
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def tp_push_ring(x, axis, ring):
    return x


def _tp_push_ring_fwd(x, axis, ring):
    return x, None


def _tp_push_ring_bwd(axis, ring, _, ct):
    return (ring_all_reduce(ct, axis, ring),)


tp_push_ring.defvjp(_tp_push_ring_fwd, _tp_push_ring_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def tp_pull_ring(x, axis, ring):
    return ring_all_reduce(x, axis, ring)


def _tp_pull_ring_fwd(x, axis, ring):
    return ring_all_reduce(x, axis, ring), None


def _tp_pull_ring_bwd(axis, ring, _, ct):
    return (ct,)


tp_pull_ring.defvjp(_tp_pull_ring_fwd, _tp_pull_ring_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def tp_psum_ring(x, axis, ring):
    return ring_all_reduce(x, axis, ring)


def _tp_psum_ring_fwd(x, axis, ring):
    return ring_all_reduce(x, axis, ring), None


def _tp_psum_ring_bwd(axis, ring, _, ct):
    return (ring_all_reduce(ct, axis, ring),)


tp_psum_ring.defvjp(_tp_psum_ring_fwd, _tp_psum_ring_bwd)


def tp_enter(x, axis, ring: int = 0):
    """tp_push, or its ring-overlapped variant when ``ring`` (the static
    model-axis size) is nonzero."""
    return tp_push_ring(x, axis, ring) if ring else tp_push(x, axis)


def tp_exit(x, axis, ring: int = 0):
    """tp_pull, or its ring-overlapped variant."""
    return tp_pull_ring(x, axis, ring) if ring else tp_pull(x, axis)


# ------------------------------------------- context-parallel (ring) region
# When Megatron head-sharding can't divide (odd heads, GQA kv < tp) the
# attention region shards the SEQUENCE over the model axis instead.  The
# region is entered by slicing this position's S/n chunk off the
# replicated activations and exited by gathering the chunks back; inside,
# K/V chunks rotate through a ppermute ring with online-softmax
# accumulation (the block recurrence of ``kernels/flash_attention``, one
# ring hop per block row).  The enter/exit conjugates are NOT
# tp_seq_gather/tp_seq_scatter: those assume partial-sum cotangents,
# whereas here the surrounding activations are replicated with
# replicated-complete cotangents — enter's backward ASSEMBLES the
# disjoint chunk cotangents (all-gather, no reduction) and exit's
# backward takes this position's slice of the replicated cotangent.
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def ctx_enter(x, axis, n):
    """Enter a ring region: slice my sequence chunk forward, assemble
    the chunk cotangents (all-gather) backward.  x: (B, S, ...)."""
    c = x.shape[1] // n
    idx = _ring_index(axis, n)
    return jax.lax.dynamic_slice_in_dim(x, idx * c, c, 1)


def _ctx_enter_fwd(x, axis, n):
    c = x.shape[1] // n
    idx = _ring_index(axis, n)
    return jax.lax.dynamic_slice_in_dim(x, idx * c, c, 1), None


def _ctx_enter_bwd(axis, n, _, ct):
    return (jax.lax.all_gather(ct, axis, axis=1, tiled=True),)


ctx_enter.defvjp(_ctx_enter_fwd, _ctx_enter_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def ctx_exit(y, axis, n):
    """Exit a ring region: gather the chunks forward, slice my chunk of
    the (replicated) cotangent backward.  y: (B, S/n, ...)."""
    return jax.lax.all_gather(y, axis, axis=1, tiled=True)


def _ctx_exit_fwd(y, axis, n):
    return jax.lax.all_gather(y, axis, axis=1, tiled=True), None


def _ctx_exit_bwd(axis, n, _, ct):
    c = ct.shape[1] // n
    idx = _ring_index(axis, n)
    return (jax.lax.dynamic_slice_in_dim(ct, idx * c, c, 1),)


ctx_exit.defvjp(_ctx_exit_fwd, _ctx_exit_bwd)


def ring_attention(q, k, v, axis, n, *, window: Optional[int] = None):
    """Causal GQA attention over sequence chunks ring-rotated on ``axis``.

    q: (B, C, H, hd) — this position's query chunk (C = S/n, global
    offset ``ring_index * C``); k/v: (B, C, KV, hd) — this position's
    key/value chunk.  Each of the n-1 ring steps ppermutes the held K/V
    chunk one position forward and folds it into the flash-attention
    online-softmax recurrence (m/l/acc rescaling exactly as in
    ``kernels/flash_attention._fwd_kernel``, with one ring hop playing
    the role of one K-block iteration).  Plain differentiable jnp: AD of
    the unrolled ring transposes each ppermute back around the ring, so
    the backward needs no hand-written collectives.
    """
    B, C, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    idx = _ring_index(axis, n)
    qg = q.reshape(B, C, KV, G, hd)
    scale = hd ** -0.5
    qpos = idx * C + jnp.arange(C)
    perm = [(i, (i + 1) % n) for i in range(n)]

    m = jnp.full((B, KV, G, C), -1e30, jnp.float32)
    l = jnp.zeros((B, KV, G, C), jnp.float32)
    acc = jnp.zeros((B, KV, G, C, hd), jnp.float32)
    kh, vh = k, v
    for t in range(n):
        cidx = (idx - t) % n              # chunk held after t hops
        kpos = cidx * C + jnp.arange(C)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg, kh).astype(jnp.float32)
        s = s * scale
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_cur = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p, vh.astype(jnp.float32))
        m = m_cur
        if t + 1 < n:
            kh = jax.lax.ppermute(kh, axis, perm)
            vh = jax.lax.ppermute(vh, axis, perm)
    out = acc / l[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, hd).astype(q.dtype)


def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def rms_norm_sharded(x, scale, eps, axis, full_dim: int):
    """RMS norm whose normalized dim is sharded over ``axis``: the mean
    of squares is assembled with a (both-ways) psum over the model axis
    — the mixer's only cross-shard dependence, one scalar field per
    (batch, time) position."""
    ss = jnp.sum(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    var = tp_psum(ss, axis) / full_dim
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def rope(x, positions, theta=10000.0):
    """Rotary embedding.  x: (..., S, H, hd), positions: (..., S)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                         # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _attend_block(q, k, v, qpos, kpos, window, scores_f32=True):
    """q: (B, Cq, KV, G, hd); k/v: (B, Skv, KV, hd); returns (B,Cq,KV,G,hd).
    Causal + optional sliding-window masking by absolute positions.
    ``scores_f32=False`` keeps the (chunk x S) score tensor in the compute
    dtype — halves the dominant HBM traffic of materialized attention
    (softmax max-subtraction keeps f16 stable); the Pallas flash kernel
    removes the materialization entirely on real TPUs."""
    scale = q.shape[-1] ** -0.5
    sdt = jnp.float32 if scores_f32 else q.dtype
    neg = jnp.asarray(-1e30 if scores_f32 else -6e4, sdt)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(sdt) * \
        jnp.asarray(scale, sdt)
    mask = kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, neg)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", w, v)


def causal_attention(q, k, v, *, q_offset=0, window: Optional[int] = None,
                     chunk: int = 512, scores_f32: bool = True):
    """Query-chunked causal GQA attention (memory-efficient; each chunk is
    rematerialized in the backward pass).

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd); H = KV * G.
    Query i has absolute position q_offset + i; key j has position j.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    kpos = jnp.arange(k.shape[1])

    if Sq <= chunk:
        qpos = q_offset + jnp.arange(Sq)
        out = _attend_block(qg, k, v, qpos, kpos, window, scores_f32)
        return out.reshape(B, Sq, H, hd)

    n_chunks = -(-Sq // chunk)
    pad = n_chunks * chunk - Sq
    if pad:
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qc = qg.reshape(B, n_chunks, chunk, KV, G, hd).swapaxes(0, 1)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one_chunk(carry, inp):
        ci, qi = inp
        qpos = q_offset + ci * chunk + jnp.arange(chunk)
        return carry, _attend_block(qi, k, v, qpos, kpos, window,
                                    scores_f32)

    _, outs = jax.lax.scan(one_chunk, (),
                           (jnp.arange(n_chunks), qc))
    out = outs.swapaxes(0, 1).reshape(B, n_chunks * chunk, KV, G, hd)
    return out[:, :Sq].reshape(B, Sq, H, hd)


def decode_attention(q, k_cache, v_cache, pos, *, window: Optional[int] = None):
    """Single-token attention against a (possibly ring-buffered) KV cache.

    q: (B, 1, H, hd); k_cache/v_cache: (B, S, KV, hd); pos: scalar int —
    the absolute position of the new token.  With a sliding window the
    cache is a ring buffer of size S=window holding absolute slots
    j mod window; validity is pos-window < j <= pos.
    """
    B, S, KV, hd = k_cache.shape
    H = q.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_cache)
    scores = scores.astype(jnp.float32) * scale
    slot = jnp.arange(S)
    if window is None:
        valid = slot <= pos
    else:
        # ring buffer: slot s holds the largest absolute position p <= pos
        # with p % S == s; valid iff that position has been written
        abs_pos = pos - (pos - slot) % S
        valid = abs_pos >= 0
    scores = jnp.where(valid[None, None, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, -1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v_cache)
    return out.reshape(B, 1, H, hd)
