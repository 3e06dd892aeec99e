"""The counting functions against the arithmetic written out in PERF.md."""
import json
from pathlib import Path

from bench import counting

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_qwen2_matmul_params_and_flops():
    c = conf("qwen2-0.5b")
    per_layer = 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864
    assert per_layer == 14_909_440
    assert counting.matmul_params(c) == 24 * per_layer + 151_936 * 896
    assert counting.matmul_params(c) == 493_961_216
    assert counting.train_flops_per_token(c, 1024) == (
        6 * 493_961_216 + 6 * 24 * 1024 * 896) == 3_095_887_872


def test_phi3_matmul_params_and_flops():
    c = conf("phi3-mini-4k")
    assert counting.matmul_params(c) == 4 * (4 * 3072 ** 2 + 3 * 3072 * 8192) \
        + 32_064 * 3072 == 551_485_440
    assert counting.train_flops_per_token(c, 1024) == 3_384_410_112


def test_wire_bytes_per_coordinate():
    n = 256 * 1000
    assert counting.dsc_quantize_bytes(n) / n == 13 + 4 / 256
    assert counting.dequantize_bytes(n) / n == 5 + 4 / 256


def test_flash_call_counts():
    B, H, KV, S, d = 8, 14, 2, 1024, 64
    mm = B * H * S * S * d                      # one causal (S x S x d) product
    fwd = counting.flash_call("fwd", B, H, KV, S, d)
    assert fwd[0] == 2 * mm
    assert fwd[1] == 2 * B * H * S * d * 2 + 2 * B * KV * S * d * 2 + 4 * B * H * S
    assert counting.flash_call("dq", B, H, KV, S, d)[0] == 3 * mm
    assert counting.flash_call("dkv", B, H, KV, S, d)[0] == 4 * mm


def test_segmented_leaves_cover_the_model():
    c = conf("qwen2-0.5b")
    # every qwen2 leaf has a dimension 4 divides, so the wire carries all
    # 494,032,768 weights: the products', the norms' and the biases'
    total = 493_961_216 + (2 * 24 + 1) * 896 + 24 * (896 + 2 * 128)
    assert total == 494_032_768
    assert sum(counting.segmented_leaf_sizes(c, 1)) == total
    assert sum(counting.segmented_leaf_sizes(c, 4)) == total
