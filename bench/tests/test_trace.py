"""The trace reduction on a small trace recorded on a TPU v5e: a jitted
step holding the Pallas dequantize kernel and a matmul, run twice inside
a ``bench.window`` span."""
from pathlib import Path

import pytest

from bench import hlo, trace

DATA = Path(__file__).resolve().parent / "data" / "trace"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(DATA), n_devices=1)


def test_window_busy_and_ops(reduced):
    assert reduced["window_s"] == pytest.approx(0.00227417, rel=1e-6)
    assert 0 < reduced["busy_s"] <= sum(reduced["op_seconds"].values())
    assert reduced["busy_s"] < reduced["window_s"]
    assert set(reduced["op_seconds"]) == {"copy-start", "reshape.2", "copy-done",
                                          "step.1", "convolution_reduce_fusion"}
    assert reduced["collective_s"] == reduced["exposed_collective_s"] == 0


def test_idle_gaps_named_by_host_span(reduced):
    gaps = reduced["idle_gaps"]
    assert gaps and all(name.startswith("bench.") for name, _ in gaps)
    assert gaps[0][1] == max(g for _, g in gaps)
    assert sum(g for _, g in gaps) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_kernel_found_by_instruction_name(reduced):
    kernels = hlo.pallas_kernels((DATA / "small.hlo").read_text())
    assert kernels == {"step.1": "quantize._dequant_kernel"}
    assert reduced["op_calls"]["step.1"] == 1


def test_op_name_of_hlo_event():
    assert trace.op_name("%fusion.3 = bf16[8]{0:T(128)} fusion(bf16[8] %p), "
                         "kind=kLoop") == ("fusion.3", "fusion")
    assert trace.op_name("%while.9 = (s32[]{:T(128)}, bf16[2]{0}) while((s32[], "
                         "bf16[2]) %t), condition=%c") == ("while.9", "while")
    assert trace.op_name("%all-to-all.1 = s8[4,8]{1,0} all-to-all(s8[4,8] %q)"
                         )[1] == "all-to-all"


def test_interval_arithmetic():
    a = trace._union([[0, 4], [2, 6], [8, 9]])
    assert a == [[0, 6], [8, 9]]
    assert trace._subtract(a, [[1, 2], [5, 8.5]]) == [[0, 1], [2, 5], [8.5, 9]]
