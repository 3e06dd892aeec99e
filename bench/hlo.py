"""Bytes a device sends over the mesh in one compiled round, read from the
TPU program's optimized HLO text.

Each collective instruction's payload is the larger of its operand and
result sizes (the whole logical tensor), weighted as a ring over its
replica group of n devices moves it: (n - 1) / n for all-gather,
reduce-scatter and all-to-all, 2 (n - 1) / n for all-reduce, 1 for a
collective-permute (the arithmetic of ``benchmarks/roofline.py``).  Each
instruction counts once, so a collective inside a loop body would be
undercounted; the round's client-axis collectives sit outside its layer
loop.
"""
from __future__ import annotations

import re

BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
         "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
KINDS = ("all-gather", "reduce-scatter", "all-to-all", "all-reduce",
         "collective-permute")
_INSTR = re.compile(r"=\s*(\(?[^=]*?\)?)\s+(" + "|".join(KINDS)
                    + r")(-start)?\((.*)$")
_SHAPE = re.compile(r"\b(" + "|".join(BYTES) + r")\[([0-9,]*)\]")


def _bytes(text: str) -> list:
    out = []
    for dt, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append(n * BYTES[dt])
    return out


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\{\{([0-9,]*)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """{kind: ring-weighted bytes one device sends} over every collective
    instruction of the program."""
    out = {k: 0.0 for k in KINDS}
    for line in hlo_text.splitlines():
        m = _INSTR.search(line)
        if not m:
            continue
        result, kind, _, rest = m.groups()
        operands = rest.split("),")[0]
        payload = max(_bytes(result) + _bytes(operands) + [0])
        n = _group_size(line)
        if kind == "collective-permute":
            weight = 1.0
        elif kind == "all-reduce":
            weight = 2 * (n - 1) / n
        else:
            weight = (n - 1) / n
        out[kind] += weight * payload
    return out


_BODY = re.compile(r'"body":"([^"]*)"')
_WORD = re.compile(rb"[A-Za-z_][A-Za-z0-9_.]{2,}")


def pallas_kernels(hlo_text: str) -> dict:
    """{HLO instruction name: "<source file stem>.<kernel function>"} for
    every Pallas kernel (``tpu_custom_call``) of the program, read from the
    names the serialized Mosaic body carries."""
    import base64
    out = {}
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line or "=" not in line:
            continue
        name = line.split("=")[0].split()[-1].lstrip("%")
        body = _BODY.search(line)
        words = ([w.decode() for w in _WORD.findall(base64.b64decode(body.group(1)))]
                 if body else [])
        # the body names the kernel function last (earlier names are
        # source locations of the kernels it was derived from)
        fn = ([w for w in words if w.endswith("kernel") and "." not in w]
              or ["kernel"])[-1]
        src = next((w[:-3] for w in words if w.endswith(".py")), "pallas")
        out[name] = f"{src}.{fn}"
    return out
