"""Pallas TPU kernels: blocked online-softmax (flash) causal attention,
forward AND backward.

The transformer hot spot for prefill and training.  Each grid step takes
the query heads of one GQA group that share a kv head (``heads`` of the
G = H // KV of them) and stacks their ``block_q`` rows into one tile of
``heads * block_q`` query rows, so one K/V block feeds every head of the
group and the MXU sees a long row dimension even at head_dim 64.  The
step streams K/V blocks through VMEM keeping running (max, sum,
accumulator) — O(S) memory instead of O(S^2).

Layout inside the kernels: score tiles are (keys, query rows) — keys on
sublanes, the stacked query rows on lanes — so every per-row statistic
(running max and sum, log-sum-exp, delta) is a lane-major (1, rows) row,
the layout it has in HBM, its reductions run over sublanes, and the
dk/dv products need no transpose of a score tile.

Precision: the products take q, k, v and dO as stored and the softmax
weights and dS rounded to that dtype, with f32 accumulation
(``preferred_element_type``); the scale is applied to the f32 scores.
This is the arithmetic of the model's own XLA attention
(``models/layers._attend_block``).  Running max, sum, accumulators,
log-sum-exp and delta stay f32.

Training path (``jax.custom_vjp``): the forward additionally emits the
per-row log-sum-exp; the backward recomputes the score blocks from
(q, k, lse) tile-by-tile — two more blocked kernels (dq and dk/dv), so
the S x S score/probability matrices NEVER touch HBM in either pass.
The dk/dv kernel sums the group's heads in f32 in VMEM and writes dk and
dv once per kv head.

Masking: blocks wholly inside the causal (and sliding-window) band run
unmasked; only blocks the diagonal or the window's edge crosses build
the mask; blocks wholly outside it are skipped.

Tiles (:func:`tiles`) come from (S, d, G, itemsize) against
``WHOLE_SEQ_VMEM``.  Supports self-attention with Sq == Skv, causal
masking, sliding windows, and grouped-query heads (H a multiple of KV).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode

NEG_INF = -1e30
# The scoped VMEM a v5e kernel gets by default.  Each kernel holds one
# whole-sequence operand pair per step: K and V (forward and dq), or the
# q and dO of the step's heads with their f32 log-sum-exp and delta rows
# (dk/dv); plus its (heads x block_q, d) row tiles and f32 (block_k,
# heads x block_q) score tiles.  ``_vmem_bytes`` counts them, lanes
# padded to 128 and rows to 8, every score temporary live at once, so it
# counts more than the compiler allocates: the plans ``tiles`` chooses
# for S 128-16384, d 64-128, 1-32 heads a group, bf16 and f32 all
# compile for a v5e.
# The whole-sequence pair is held once, or twice where it fits, so the
# next group's is fetched while this one runs.
WHOLE_SEQ_VMEM = 16 * 1024 * 1024


class Tiles(NamedTuple):
    """One plan for the three kernels (see :func:`tiles`)."""
    block_q: int      # query rows of one head in a tile
    block_k: int      # key rows in a tile
    heads: int        # heads of a GQA group stacked in a forward / dq tile
    heads_dkv: int    # heads whose whole sequence one dk/dv step holds
    buffers: int      # copies of the whole-sequence operands (1 or 2)


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(S: int, d: int, itemsize: int, t: Tiles) -> int:
    """VMEM the largest of the three kernels holds under plan ``t``."""
    dl, dr = _pad(d, 128), _pad(d, 8)

    def score(heads):              # one f32 (block_k, heads x block_q)
        return t.block_k * _pad(heads * t.block_q, 128) * 4

    def stats(heads, n):           # f32 (heads, 1, n) rows, 8-row tiles
        return heads * 8 * _pad(n, 128) * 4

    rows = t.heads * t.block_q
    kv_whole = t.buffers * 2 * S * dl * itemsize
    row_tile = 2 * rows * dl * itemsize
    acc = 2 * dr * _pad(rows, 128) * 4 + rows * dl * 4
    fwd = (kv_whole + 2 * row_tile + 2 * stats(t.heads, t.block_q)
           + 3 * score(t.heads) + acc)
    dq = (kv_whole + 3 * row_tile + 4 * stats(t.heads, t.block_q)
          + 4 * score(t.heads) + acc)
    dkv = (t.buffers * (2 * t.heads_dkv * S * dl * itemsize
                        + 2 * stats(t.heads_dkv, S))
           + 4 * 2 * t.block_k * dl * itemsize + 4 * t.block_k * dl * 4
           + 4 * score(t.heads_dkv))
    return max(fwd, dq, dkv)


def _block_sizes(S: int) -> list:
    """Block sizes to try along a sequence of S, largest first: one block
    up to 128, else 128, 256 or 512 rows, at most half the sequence so
    that causal masking skips blocks."""
    if S <= 128:
        return [S]
    return [b for b in (512, 256, 128) if S % b == 0 and 2 * b <= S]


def tiles(S: int, d: int, G: int, itemsize: int, block_q=None,
          block_k=None) -> Tiles | None:
    """The plan for sequence length S, head_dim d, G query heads per kv
    head and operands of ``itemsize`` bytes, within ``WHOLE_SEQ_VMEM``:
    the most heads a forward / dq tile stacks, then the longest square
    blocks, then the most heads a dk/dv step holds, then
    double-buffering.  Blocks given are kept (clamped to S).  None where
    no plan tiles S exactly within the budget."""
    if block_q is None and block_k is None:
        blocks = [(b, b) for b in _block_sizes(S)]
    else:
        bq, bk = min(block_q or block_k, S), min(block_k or block_q, S)
        blocks = [(bq, bk)] if S % bq == 0 and S % bk == 0 else []
    groups = [g for g in range(G, 0, -1) if G % g == 0]
    for heads in groups:
        for bq, bk in blocks:
            for heads_dkv in groups:
                for buffers in (2, 1):
                    t = Tiles(bq, bk, heads, heads_dkv, buffers)
                    if _vmem_bytes(S, d, itemsize, t) <= WHOLE_SEQ_VMEM:
                        return t
    return None


# ------------------------------------------------------------- helpers
def _nt(a, b):
    """a (m, d) . b (n, d)^T -> (m, n), f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """a (k, m)^T . b (k, n) -> (m, n), f32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    """a (m, k) . b (k, n) -> (m, n), f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mask(s, qpos0, kpos0, block_q, window):
    """Causal (and window) mask of a (block_k, heads x block_q) score tile
    whose key rows start at absolute position kpos0 and whose column j is
    query position qpos0 + j % block_q (the heads' rows side by side).
    Only causal attention has blocks to mask."""
    kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    qpos = qpos0 + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, s.shape, 1), block_q)
    ok = kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return jnp.where(ok, s, NEG_INF)


def _row(ref, heads, start, size):
    """The (1, heads x size) f32 row of per-query statistics of a
    (heads, 1, n) ref, each head's [start, start + size) side by side."""
    parts = [ref[h, :, pl.ds(start, size)] for h in range(heads)]
    return parts[0] if heads == 1 else jnp.concatenate(parts, axis=1)


def _stack(ref, start, size):
    """(heads, n, d) ref -> the (heads x size, d) tile of rows
    [start, start + size) of every head."""
    x = ref[:, pl.ds(start, size), :]
    return x.reshape(x.shape[0] * size, x.shape[2])


def _loop3(lo, a, b, hi, body, carry):
    """Run body(i, carry, masked) over [lo, hi): masked on [lo, a) and
    [b, hi), unmasked on [a, b); a and b are clamped into order."""
    a = jnp.clip(a, lo, hi)
    b = jnp.clip(b, a, hi)
    carry = jax.lax.fori_loop(lo, a, functools.partial(body, masked=True),
                              carry)
    carry = jax.lax.fori_loop(a, b, functools.partial(body, masked=False),
                              carry)
    return jax.lax.fori_loop(b, hi, functools.partial(body, masked=True),
                             carry)


def _k_range(q0, block_q, block_k, n_kb, causal, window):
    """k-blocks a q-block [q0, q0 + block_q) sees: (lo, a, b, hi) with
    [a, b) wholly inside the band (no mask) and [lo, hi) all it meets."""
    if not causal:
        return 0, 0, n_kb, n_kb
    hi = (q0 + block_q + block_k - 1) // block_k
    b = (q0 + 1) // block_k
    if window is None:
        return 0, 0, b, hi
    lo = jnp.maximum(0, (q0 - window + 1) // block_k)
    a = (q0 + block_q - window + block_k - 1) // block_k
    return lo, a, b, hi


def _q_range(k0, block_q, block_k, n_qb, causal, window):
    """q-blocks a k-block [k0, k0 + block_k) meets, as :func:`_k_range`."""
    if not causal:
        return 0, 0, n_qb, n_qb
    lo = k0 // block_q
    a = (k0 + block_k + block_q - 2) // block_q
    if window is None:
        return lo, a, n_qb, n_qb
    hi = jnp.minimum(n_qb, (k0 + block_k + window - 2) // block_q + 1)
    b = (k0 + window) // block_q
    return lo, a, b, hi


# ------------------------------------------------------------- kernels
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k,
                sm_scale, causal, window, seq_len):
    heads, _, d = q_ref.shape
    q0 = pl.program_id(2) * block_q
    q = q_ref[...].reshape(heads * block_q, d)

    def body(kb, carry, masked):
        acc, m_prev, l_prev = carry                 # (d, rows), (1, rows)
        k0 = pl.multiple_of(kb * block_k, block_k)
        k = k_ref[pl.ds(k0, block_k), :]
        v = v_ref[pl.ds(k0, block_k), :]
        s = _nt(k, q) * sm_scale                    # (bk, rows) f32
        if masked:
            s = _mask(s, q0, k0, block_q, window)
        m_cur = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + p.sum(axis=0, keepdims=True)
        acc = acc * alpha + _tn(v, p.astype(v.dtype))
        return acc, m_cur, l_cur

    rows = heads * block_q
    carry = (jnp.zeros((d, rows), jnp.float32),
             jnp.full((1, rows), NEG_INF, jnp.float32),
             jnp.zeros((1, rows), jnp.float32))
    acc, m, l = _loop3(*_k_range(q0, block_q, block_k, seq_len // block_k,
                                 causal, window), body, carry)
    l_safe = jnp.maximum(l, 1e-30)
    o = (acc / l_safe).T                            # (rows, d)
    o_ref[...] = o.reshape(heads, block_q, d).astype(o_ref.dtype)
    lse = m + jnp.log(l_safe)
    for h in range(heads):
        lse_ref[h] = lse[:, h * block_q:(h + 1) * block_q]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_q, block_k, sm_scale, causal, window, seq_len):
    heads, _, d = q_ref.shape
    q0 = pl.program_id(2) * block_q
    q = q_ref[...].reshape(heads * block_q, d)
    do = do_ref[...].reshape(heads * block_q, d)
    lse = _row(lse_ref, heads, 0, block_q)           # (1, rows)
    delta = _row(delta_ref, heads, 0, block_q)

    def body(kb, dq, masked):                        # dq: (d, rows)
        k0 = pl.multiple_of(kb * block_k, block_k)
        k = k_ref[pl.ds(k0, block_k), :]
        v = v_ref[pl.ds(k0, block_k), :]
        s = _nt(k, q) * sm_scale
        if masked:
            s = _mask(s, q0, k0, block_q, window)
        p = jnp.exp(s - lse)                         # masked -> 0
        ds = p * (_nt(v, do) - delta)
        return dq + _tn(k, ds.astype(k.dtype))

    dq = _loop3(*_k_range(q0, block_q, block_k, seq_len // block_k,
                          causal, window), body,
                jnp.zeros((d, heads * block_q), jnp.float32))
    dq = (dq * sm_scale).T
    dq_ref[...] = dq.reshape(heads, block_q, d).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_k,
                sm_scale, causal, window, seq_len):
    heads = q_ref.shape[0]
    k0 = pl.program_id(1) * block_k
    g = pl.program_id(2)
    k = k_ref[...]                                    # (bk, d)
    v = v_ref[...]

    def body(qb, carry, masked):
        dk, dv = carry
        q0 = pl.multiple_of(qb * block_q, block_q)
        q = _stack(q_ref, q0, block_q)                # (rows, d)
        do = _stack(do_ref, q0, block_q)
        lse = _row(lse_ref, heads, q0, block_q)       # (1, rows)
        delta = _row(delta_ref, heads, q0, block_q)
        s = _nt(k, q) * sm_scale                      # (bk, rows)
        if masked:
            s = _mask(s, q0, k0, block_q, window)
        p = jnp.exp(s - lse)
        dv = dv + _nn(p.astype(do.dtype), do)
        ds = p * (_nt(v, do) - delta)
        return dk + _nn(ds.astype(q.dtype), q), dv

    z = jnp.zeros(dk_acc.shape, jnp.float32)
    dk, dv = _loop3(*_q_range(k0, block_q, block_k, seq_len // block_q,
                              causal, window), body, (z, z))

    @pl.when(g == 0)
    def _():
        dk_acc[...] = dk
        dv_acc[...] = dv

    @pl.when(g > 0)
    def _():
        dk_acc[...] += dk
        dv_acc[...] += dv

    @pl.when(g == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# --------------------------------------------------------------- calls
def _whole(block_shape, index_map, buffers):
    """BlockSpec of an operand a step holds whole along the sequence: its
    block changes only with the kv head, so one copy can do where two
    would not fit."""
    if buffers == 2:
        return pl.BlockSpec(block_shape, index_map)
    return pl.BlockSpec(block_shape, index_map,
                        pipeline_mode=pl.Buffered(1))


def _check(q, k, v):
    B, H, S, d = q.shape
    KV = k.shape[1]
    assert k.shape == v.shape == (B, KV, S, d), (q.shape, k.shape, v.shape)
    assert H % KV == 0, (H, KV)
    return B, H, KV, S, d, H // KV


def _flash_fwd_call(q, k, v, causal, window, t, interpret):
    B, H, KV, S, d, G = _check(q, k, v)
    bq, gq = t.block_q, t.heads
    whole = functools.partial(_whole, (None, S, d),
                              lambda b, g, i: (b, 0, 0), t.buffers)
    rows = pl.BlockSpec((None, gq, bq, d), lambda b, g, i: (b, g, i, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=bq, block_k=t.block_k,
                          sm_scale=d ** -0.5, causal=causal, window=window,
                          seq_len=S),
        grid=(B * KV, G // gq, S // bq),
        in_specs=[rows, whole(), whole()],
        out_specs=(rows, pl.BlockSpec((None, gq, 1, bq),
                                      lambda b, g, i: (b, g, 0, i))),
        out_shape=(jax.ShapeDtypeStruct((B * KV, G, S, d), q.dtype),
                   jax.ShapeDtypeStruct((B * KV, G, 1, S), jnp.float32)),
        interpret=interpret,
    )(q.reshape(B * KV, G, S, d), k.reshape(B * KV, S, d),
      v.reshape(B * KV, S, d))
    return out.reshape(B, H, S, d), lse


def _flash_bwd_call(q, k, v, o, lse, do, causal, window, t, interpret):
    B, H, KV, S, d, G = _check(q, k, v)
    bq, bk, gq, gkv = t.block_q, t.block_k, t.heads, t.heads_dkv
    qg = q.reshape(B * KV, G, S, d)
    kf = k.reshape(B * KV, S, d)
    vf = v.reshape(B * KV, S, d)
    dog = do.reshape(B * KV, G, S, d)
    # delta_i = sum_d do_i * o_i — one cheap fused elementwise reduce
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(B * KV, G, 1, S)
    kw = dict(block_q=bq, block_k=bk, sm_scale=d ** -0.5, causal=causal,
              window=window, seq_len=S)
    whole = functools.partial(_whole, (None, S, d),
                              lambda b, g, i: (b, 0, 0), t.buffers)
    rows = pl.BlockSpec((None, gq, bq, d), lambda b, g, i: (b, g, i, 0))
    stat = pl.BlockSpec((None, gq, 1, bq), lambda b, g, i: (b, g, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(B * KV, G // gq, S // bq),
        in_specs=[rows, whole(), whole(), rows, stat, stat],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, S, d), q.dtype),
        interpret=interpret,
    )(qg, kf, vf, dog, lse, delta)
    # dk/dv per kv head: the grid's last axis walks the group's heads in
    # chunks of gkv, summed in the f32 scratch; written at the last
    seq = functools.partial(_whole, (None, gkv, S, d),
                            lambda b, j, g: (b, g, 0, 0), t.buffers)
    seq_stat = functools.partial(_whole, (None, gkv, 1, S),
                                 lambda b, j, g: (b, g, 0, 0), t.buffers)
    kv_block = pl.BlockSpec((None, bk, d), lambda b, j, g: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(B * KV, S // bk, G // gkv),
        in_specs=[seq(), kv_block, kv_block, seq(), seq_stat(), seq_stat()],
        out_specs=(kv_block, kv_block),
        out_shape=(jax.ShapeDtypeStruct((B * KV, S, d), k.dtype),
                   jax.ShapeDtypeStruct((B * KV, S, d), v.dtype)),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(qg, kf, vf, dog, lse, delta)
    return (dq.reshape(B, H, S, d), dk.reshape(B, KV, S, d),
            dv.reshape(B, KV, S, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg):
    out, _ = _flash_fwd_call(q, k, v, *cfg)
    return out


def _flash_vjp_fwd(q, k, v, cfg):
    out, lse = _flash_fwd_call(q, k, v, *cfg)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(cfg, res, do):
    q, k, v, out, lse = res
    return _flash_bwd_call(q, k, v, out, lse, do, *cfg)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None):
    """q: (B, H, S, d); k, v: (B, KV, S, d) with H % KV == 0 (GQA).
    Returns (B, H, S, d).  Blocks not given come from :func:`tiles`;
    given ones clamp to S and must tile it.  Differentiable (custom VJP,
    blocked recompute backward)."""
    if window is not None:
        assert causal, "sliding window implies causal masking"
    B, H, S, d = q.shape
    t = tiles(S, d, H // k.shape[1], q.dtype.itemsize, block_q, block_k)
    assert t is not None, ("no flash-attention tiles for", q.shape, k.shape,
                           q.dtype, block_q, block_k)
    cfg = (bool(causal), None if window is None else int(window), t,
           interpret_mode(interpret))
    return _flash(q, k, v, cfg)


def supports(S: int, d: int, itemsize: int = 4, block_q: int | None = None,
             block_k: int | None = None) -> bool:
    """Shape gate for the training integration: some plan of
    :func:`tiles` tiles S exactly and fits ``WHOLE_SEQ_VMEM``.  One head
    a step is always a plan, so the group size does not decide it."""
    return tiles(S, d, 1, itemsize, block_q, block_k) is not None
