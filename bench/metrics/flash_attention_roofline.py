"""Roofline share of the flash-attention kernels (forward, dq, dk/dv):
the least time their calls need, from the FLOPs and HBM bytes of each
call's shapes (causal half), over the device time the calls took."""
from bench import counting
from bench.reference import dense

KINDS = {"flash_attention._fwd_kernel": "fwd", "flash_attention._dq_kernel": "dq",
         "flash_attention._dkv_kernel": "dkv"}


def read(ctx):
    cell, t, pk = ctx["cell"], ctx["trace"], ctx["peaks"]
    d = dense.dims(cell.conf)
    least = spent = 0.0
    for op, kernel in ctx["kernels"].items():
        if kernel not in KINDS or op not in t["op_seconds"]:
            continue
        flops, nbytes = counting.flash_call(
            KINDS[kernel], cell.traffic["rows_per_client"], d["H"], d["KV"],
            cell.traffic["seq_len"], d["hd"])
        least += t["op_calls"][op] * max(flops / pk["bf16_flops"],
                                         nbytes / pk["hbm_bytes_per_s"])
        spent += t["op_seconds"][op]
    return 100.0 * least / spent if spent else None
