"""Pallas TPU kernels: blocked online-softmax (flash) causal attention,
forward AND backward.

The transformer hot spot for prefill and training.  Grid =
(batch*heads, q_blocks); each grid step streams K/V blocks through VMEM
keeping running (max, sum, accumulator) — O(S) memory instead of O(S^2),
MXU-aligned (BLOCK_Q x BLOCK_K x d matmuls with d a multiple of 128
ideally).

Training path (``jax.custom_vjp``): the forward additionally emits the
per-row log-sum-exp; the backward recomputes the score blocks from
(q, k, lse) tile-by-tile — two more blocked kernels (dq and dk/dv), so
the S x S score/probability matrices NEVER touch HBM in either pass.
HBM traffic per head: 3 reads + 1 write forward, ~5 reads + 3 writes
backward, all O(S*d) — versus O(S^2) materialized scores under the
blanket-remat chunked path.

Supports self-attention with Sq == Skv, causal masking, sliding
windows, and grouped-query heads (H a multiple of KV; K/V blocks are
indexed through the query-head -> kv-head map, no materialized repeat).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import interpret_mode

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30
# Of the 16 MiB of scoped VMEM a v5e kernel gets by default; the rest
# holds the per-block tiles and f32 score intermediates.  Rows pad to
# 128 lanes, so at bf16 and d <= 128 this admits S <= 8192, and the
# chip's compiler refuses the dq pass at S = 16384 (16.19 MiB).
WHOLE_SEQ_VMEM = 12 * 1024 * 1024


def _mask(s, qpos0, kpos0, block_q, block_k, causal, window):
    """Apply causal/window masking to a (block_q, block_k) score tile
    whose rows start at absolute position qpos0, columns at kpos0."""
    if not causal and window is None:
        return s
    qpos = qpos0 + jax.lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 0)
    kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 1)
    ok = kpos <= qpos if causal else jnp.full_like(qpos, True, jnp.bool_)
    if window is not None:
        ok &= kpos > qpos - window
    return jnp.where(ok, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k,
                sm_scale, causal, window, seq_len):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale       # (bq, d)
    q_offset = qi * block_q
    n_kb = seq_len // block_k

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T                                   # (bq, bk)
        s = _mask(s, q_offset, kb * block_k, block_q, block_k,
                  causal, window)
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + p.sum(axis=1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_cur, l_cur

    d = q.shape[-1]
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    if causal:
        # only k-blocks up to (and including) the diagonal contribute
        hi = (q_offset + block_q + block_k - 1) // block_k
    else:
        hi = n_kb
    if window is not None:
        lo = jnp.maximum(0, (q_offset - window + 1) // block_k)
    else:
        lo = 0
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l_safe))[None, :]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_q, block_k, sm_scale, causal, window, seq_len):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                # (bq,)
    delta = delta_ref[0, 0]
    q_offset = qi * block_q

    def body(kb, dq):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T
        s = _mask(s, q_offset, kb * block_k, block_q, block_k,
                  causal, window)
        p = jnp.exp(s - lse[:, None])                  # masked -> exp(-inf)=0
        dp = do @ v.T
        ds = p * (dp - delta[:, None])
        return dq + ds @ k

    if causal:
        hi = (q_offset + block_q + block_k - 1) // block_k
    else:
        hi = seq_len // block_k
    if window is not None:
        lo = jnp.maximum(0, (q_offset - window + 1) // block_k)
    else:
        lo = 0
    d = q.shape[-1]
    dq = jax.lax.fori_loop(lo, hi, body, jnp.zeros((block_q, d),
                                                   jnp.float32))
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, block_q, block_k, sm_scale, causal,
                window, seq_len):
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                   # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    k_offset = ki * block_k

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32) \
            * sm_scale
        do = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        q0 = pl.multiple_of(qb * block_q, block_q)
        lse = lse_ref[0, 0, pl.ds(q0, block_q)]
        delta = delta_ref[0, 0, pl.ds(q0, block_q)]
        s = q @ k.T                                    # (bq, bk)
        s = _mask(s, qb * block_q, k_offset, block_q, block_k,
                  causal, window)
        p = jnp.exp(s - lse[:, None])
        dv = dv + p.T @ do
        dp = do @ v.T
        ds = p * (dp - delta[:, None])
        return dk + ds.T @ q, dv

    n_qb = seq_len // block_q
    if causal:
        # only q-blocks at/after the diagonal see this k-block
        lo = k_offset // block_q
    else:
        lo = 0
    if window is not None:
        # query rows with qpos < kpos + window: last such block
        hi = jnp.minimum(n_qb,
                         (k_offset + block_k - 1 + window - 1) // block_q + 1)
    else:
        hi = n_qb
    d = k.shape[-1]
    z = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, hi, body, (z, z))
    # q was pre-scaled, so ds.T @ q already carries one sm_scale factor
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _kv_index(b, H, KV):
    """Query-head grid index -> kv-head row in the flattened (B*KV, S, d)
    K/V arrays (GQA: G = H // KV query heads share one kv head)."""
    G = H // KV
    return (b // H) * KV + (b % H) // G


def _check(q, k, v, block_q, block_k):
    B, H, S, d = q.shape
    KV = k.shape[1]
    assert k.shape == v.shape == (B, KV, S, d), (q.shape, k.shape, v.shape)
    assert H % KV == 0, (H, KV)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    return B, H, KV, S, d, block_q, block_k


def _flash_fwd_call(q, k, v, causal, window, block_q, block_k, interpret):
    B, H, KV, S, d, block_q, block_k = _check(q, k, v, block_q, block_k)
    sm_scale = d ** -0.5
    qf = q.reshape(B * H, S, d)
    kf = k.reshape(B * KV, S, d)
    vf = v.reshape(B * KV, S, d)
    kv_map = functools.partial(_kv_index, H=H, KV=KV)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          sm_scale=sm_scale, causal=causal, window=window,
                          seq_len=S),
        grid=(B * H, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, d), lambda b, i, _m=kv_map: (_m(b), 0, 0)),
            pl.BlockSpec((1, S, d), lambda b, i, _m=kv_map: (_m(b), 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i))),
        out_shape=(jax.ShapeDtypeStruct((B * H, S, d), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, d), lse


def _flash_bwd_call(q, k, v, o, lse, do, causal, window, block_q, block_k,
                    interpret):
    B, H, KV, S, d, block_q, block_k = _check(q, k, v, block_q, block_k)
    sm_scale = d ** -0.5
    qf = q.reshape(B * H, S, d)
    kf = k.reshape(B * KV, S, d)
    vf = v.reshape(B * KV, S, d)
    dof = do.reshape(B * H, S, d)
    # delta_i = sum_d do_i * o_i — one cheap fused elementwise reduce
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(B * H, 1, S)
    kv_map = functools.partial(_kv_index, H=H, KV=KV)
    kw = dict(block_q=block_q, block_k=block_k, sm_scale=sm_scale,
              causal=causal, window=window, seq_len=S)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(B * H, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, d), lambda b, i, _m=kv_map: (_m(b), 0, 0)),
            pl.BlockSpec((1, S, d), lambda b, i, _m=kv_map: (_m(b), 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)
    # dk/dv per QUERY head (grid b spans B*H; K/V blocks via the GQA map);
    # group contributions are summed after the kernel — a fused reduce
    # over G, still O(S*d) traffic
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(B * H, S // block_k),
        in_specs=[
            pl.BlockSpec((1, S, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, j, _m=kv_map: (_m(b), j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, j, _m=kv_map: (_m(b), j, 0)),
            pl.BlockSpec((1, S, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda b, j: (b, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
                   pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0))),
        out_shape=(jax.ShapeDtypeStruct((B * H, S, d), jnp.float32),
                   jax.ShapeDtypeStruct((B * H, S, d), jnp.float32)),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)
    G = H // KV
    dk = dk_h.reshape(B, KV, G, S, d).sum(2).astype(k.dtype)
    dv = dv_h.reshape(B, KV, G, S, d).sum(2).astype(v.dtype)
    return dq.reshape(B, H, S, d), dk, dv


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
                    block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                    interpret: bool | None = None):
    """q: (B, H, S, d); k, v: (B, KV, S, d) with H % KV == 0 (GQA).
    Returns (B, H, S, d).  S % block == 0 after blocks clamp to S.
    Differentiable (custom VJP, blocked recompute backward)."""
    if window is not None:
        assert causal, "sliding window implies causal masking"
    cfg = (bool(causal), None if window is None else int(window),
           int(block_q), int(block_k), interpret_mode(interpret))
    return _flash(q, k, v, cfg)


def supports(S: int, d: int, itemsize: int = 4, block_q: int = BLOCK_Q,
             block_k: int = BLOCK_K) -> bool:
    """Shape gate for the training integration: the kernels need the
    (possibly clamped) blocks to tile S exactly, and the two
    whole-sequence operands each pass holds in VMEM (K/V forward and for
    dq, q/dO for dk/dv), double-buffered, to fit ``WHOLE_SEQ_VMEM``."""
    bq, bk = min(block_q, S), min(block_k, S)
    resident = 2 * 2 * S * max(d, 128) * itemsize
    return S % bq == 0 and S % bk == 0 and resident <= WHOLE_SEQ_VMEM
