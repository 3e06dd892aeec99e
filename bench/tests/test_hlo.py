"""Collective byte counting and kernel naming on HLO text."""
import base64

from bench import hlo

LINES = """
  %all-to-all.1 = s8[4,1000,128]{2,1,0} all-to-all(s8[4,1000,128]{2,1,0} %fusion.3), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}
  %all-gather-start.2 = (bf16[10,896]{1,0}, bf16[40,896]{1,0}) all-gather-start(bf16[10,896]{1,0} %p.1), channel_id=3, replica_groups=[1,4]<=[4], dimensions={0}
  %all-gather-done.2 = bf16[40,896]{1,0} all-gather-done((bf16[10,896]{1,0}, bf16[40,896]{1,0}) %all-gather-start.2)
  %all-reduce.7 = f32[] all-reduce(f32[] %x), channel_id=4, replica_groups={{0,1,2,3}}, to_apply=%add
"""


def test_collective_bytes_ring_weighted():
    out = hlo.collective_bytes(LINES)
    assert out["all-to-all"] == 4 * 1000 * 128 * 3 / 4
    assert out["all-gather"] == 40 * 896 * 2 * 3 / 4
    assert out["all-reduce"] == 4 * 2 * 3 / 4
    assert out["reduce-scatter"] == 0


def test_pallas_kernel_named_last():
    body = base64.b64encode(b"\x00_fwd_kernel\x00repro\x00flash_attention.py\x00"
                            b"_dq_kernel\x00").decode()
    line = (f'  ROOT %checkpoint.23 = f32[2] custom-call(f32[2] %a), '
            f'custom_call_target="tpu_custom_call", '
            f'backend_config={{"custom_call_config":{{"body":"{body}"}}}}')
    assert hlo.pallas_kernels(line) == {"checkpoint.23": "flash_attention._dq_kernel"}
