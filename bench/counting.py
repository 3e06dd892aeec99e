"""Operations and bytes the work needs, computed from shapes.

These are the yardstick's own counts; a roofline share divides the least
time they allow by a measured device time, so they count only what the
work needs (no padding, no recomputation beyond what one call itself
computes) and a share cannot pass 100 % unless the time misses part of
the work.
"""
from __future__ import annotations

from bench.reference import model

QBLOCK = 256


def matmul_params(conf: dict) -> int:
    """Weights that enter a matrix product once per token: the blocks'
    projections and the head (the tied embedding counts once, as the
    head; the embedding lookup is no product)."""
    d = model(conf).dims(conf)
    D, Q, KV = d["D"], d["H"] * d["hd"], d["KV"] * d["hd"]
    per_layer = 2 * D * Q + 2 * D * KV + 3 * D * d["F"]
    return d["L"] * per_layer + D * d["V"]


def train_flops_per_token(conf: dict, seq_len: int) -> float:
    """Model FLOPs of forward + backward per token: 6 N for the products
    with weights, and 6 L S q_dim for causal attention (QK^T and PV,
    2 S q_dim each at full length, halved by the causal mask, times 3 for
    the backward).  Recomputation is not counted."""
    d = model(conf).dims(conf)
    return (6 * matmul_params(conf)
            + 6 * d["L"] * seq_len * d["H"] * d["hd"])


def flash_call(kind: str, B: int, H: int, KV: int, S: int, d: int,
               itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one call of the flash kernel needs, causal half.
    ``fwd``: QK^T and PV; reads q, k, v, writes o and the f32 log-sum-exp.
    ``dq``: QK^T, dO V^T and dS K; reads q, k, v, dO, lse, delta, writes dq.
    ``dkv``: QK^T, P^T dO, dO V^T and dS^T Q; reads q, k, v, dO, lse,
    delta, writes f32 dk and dv per query head."""
    mm = B * H * S * S * d          # one (S x S x d) product, causal half
    q = B * H * S * d * itemsize
    kv = B * KV * S * d * itemsize
    rows = B * H * S * 4            # one f32 per query row
    if kind == "fwd":
        return 2 * mm, q + 2 * kv + q + rows
    if kind == "dq":
        return 3 * mm, 2 * q + 2 * kv + 2 * rows + q
    if kind == "dkv":
        return 4 * mm, 2 * q + 2 * kv + 2 * rows + 2 * B * H * S * d * 4
    raise ValueError(kind)


def dsc_quantize_bytes(n: int) -> float:
    """The fused DSC + int8 pass over n coordinates: reads g and s (f32),
    writes s' (f32), the int8 payload and one f32 scale per 256."""
    return 13 * n + 4 * -(-n // QBLOCK)


def dequantize_bytes(n: int) -> float:
    """Reads n int8 values and their scales, writes n f32."""
    return 5 * n + 4 * -(-n // QBLOCK)


def segmented_leaf_sizes(conf: dict, clients: int) -> list:
    """Sizes of the leaves the FSA wire segments over ``clients``
    aggregators: those with a dimension that ``clients`` divides."""
    import jax
    import math
    shapes = jax.tree.leaves(model(conf).layout(conf),
                             is_leaf=lambda x: isinstance(x, tuple))
    return [math.prod(s) for s in shapes
            if any(d >= clients and d % clients == 0 for d in s)]


def kernel_share(ctx: dict, kernel: str, bytes_of) -> float | None:
    """Roofline share (%) of a memory-bound wire kernel that makes one
    pass over every segmented leaf per round."""
    t, cell = ctx["trace"], ctx["cell"]
    spent = sum(t["op_seconds"].get(op, 0.0)
                for op, k in ctx["kernels"].items() if k == kernel)
    if not spent:
        return None
    need = ctx["rounds"] * sum(bytes_of(n) for n in segmented_leaf_sizes(
        cell.conf, cell.traffic["clients"]))
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / spent
