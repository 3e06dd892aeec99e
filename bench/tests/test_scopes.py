"""The split of device time by phase of the ERIS round and by part of the
model (``bench/scopes.py``): the classifier on hand-written op_names, and
the join on a small scoped trace recorded on a TPU v5e: two rounds of the
smoke-size qwen2 round (fused DSC + int8 wire, adam, 8 x 128 tokens, one
chip) inside a ``bench.window`` span, the profiler tracing host
annotations only, with the compiled round's text; both gzipped."""
import gzip
import shutil
from pathlib import Path

import pytest

from bench import hlo, scopes, trace

# beside data/trace, whose test loads the one trace found under it
DATA = Path(__file__).resolve().parent / "data" / "trace_scoped"
J = "jit(step)/shard_map/"


@pytest.mark.parametrize("op_name,kind,want", [
    (J + "eris.client/jvp()/while/body/closed_call/model.mlp/dot_general",
     "fusion", "client_fwd"),
    (J + "eris.client/transpose(jvp())/while/body/closed_call/checkpoint/"
     "model.attention/dot_general", "convolution", "client_bwd"),
    (J + "eris.client/transpose(jvp(model.head_loss))/dot_general",
     "fusion", "client_bwd"),
    (J + "eris.client/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/model.attention/pallas_call", "custom-call",
     "client_recompute"),
    (J + "eris.compress/pallas_call", "custom-call", "compress"),
    (J + "eris.exchange/all_to_all", "all-to-all", "exchange"),
    (J + "eris.aggregate/reduce_sum", "fusion", "aggregate"),
    (J + "eris.update/add", "fusion", "update"),
    ("jit(step)/shard_map", "all-gather", "exchange"),
    ("jit(step)/shard_map", "all-gather-start", "exchange"),
    ("jit(step)/shard_map", "copy", "unscoped"),
    (J + "model.attention/sin", "fusion", "unscoped"),
    ("", "copy-start", "unscoped"),
    ("jit(step)/eris.clientele/add", "fusion", "unscoped"),
])
def test_phase_of_op_name(op_name, kind, want):
    assert scopes.phase(op_name, kind) == want


@pytest.mark.parametrize("op_name,want", [
    (J + "eris.client/jvp()/while/body/closed_call/model.mlp/mul", "mlp"),
    (J + "eris.client/transpose(jvp(model.head_loss))/exp", "head_loss"),
    (J + "eris.client/jvp(model.embed)/gather", "embed"),
    (J + "eris.client/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/model.attention/pallas_call", "attention"),
    (J + "model.attention/sin", "attention"),
    (J + "eris.update/add", None),
])
def test_model_part_of_op_name(op_name, want):
    assert scopes.part(op_name) == want


HLO = """HloModule jit_step

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/eris.update/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation
  ROOT %all-gather.3 = f32[32]{0} all-gather(%fusion.2), replica_groups=[1,4]<=[4], dimensions={0}, metadata={op_name="jit(step)/shard_map"}
}
"""


RESHAPED = """HloModule jit_step

ENTRY %main (a: f32[8], b: f32[8]) -> (f32[8], f32[8]) {
  %a = f32[8]{0} parameter(0), metadata={op_name="params_stored[\'w\']"}
  %b = f32[8]{0} parameter(1), metadata={op_name="b"}
  %copy.1 = f32[8]{0:T(8)} copy(%a), metadata={op_name="params_stored[\'w\']"}
  %add.2 = f32[8]{0} add(%copy.1, %b), metadata={op_name="jit(step)/eris.update/add"}
  %copy.3 = f32[8]{0} copy(%add.2)
  %mul.4 = f32[8]{0} multiply(%b, %b), metadata={op_name="jit(step)/eris.clients/mul"}
  %copy.5 = f32[8]{0} copy(%mul.4)
  %sub.6 = f32[8]{0} subtract(%copy.5, %add.2), metadata={op_name="jit(step)/eris.update/sub"}
  ROOT %tuple.7 = (f32[8]{0}, f32[8]{0}) tuple(%copy.3, %sub.6)
}
"""


def test_compiler_made_op_goes_with_what_it_works_for():
    """A layout copy of a parameter goes with the scoped op that reads it,
    a copy into the outputs with the scoped op it reads; an op of the
    program under a scope the reader does not know stays unscoped, and
    so does nothing else."""
    instr = scopes.instructions(RESHAPED)
    assert instr["copy.1"][1] == "jit(step)/eris.update/add"
    assert instr["copy.3"][1] == "jit(step)/eris.update/add"
    kind, op_name = instr["mul.4"]
    assert scopes.phase(op_name, kind) == "unscoped"
    assert instr["copy.5"][1] == "jit(step)/eris.update/sub"


def test_fusion_without_metadata_takes_its_root():
    instr = scopes.instructions(HLO)
    assert instr["fusion.2"] == ("fusion", "jit(step)/eris.update/mul")
    assert instr["all-gather.3"] == ("all-gather", "jit(step)/shard_map")
    ctx = {"hlo": HLO, "rounds": 2,
           "trace": {"op_seconds": {"fusion.2": 0.004, "all-gather.3": 0.002,
                                    "unknown.9": 0.001}}}
    assert scopes.round_ms(ctx, "update") == pytest.approx(2.0)
    assert scopes.round_ms(ctx, "exchange") == pytest.approx(1.0)
    assert scopes.round_ms(ctx, "unscoped") == pytest.approx(0.5)
    assert scopes.round_ms(ctx, "compress") is None
    assert scopes.client_ms(ctx, "mlp") is None


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    d = tmp_path_factory.mktemp("scoped")
    with gzip.open(DATA / "scoped.xplane.pb.gz") as f, \
            open(d / "scoped.xplane.pb", "wb") as out:
        shutil.copyfileobj(f, out)
    text = gzip.decompress((DATA / "scoped.hlo.gz").read_bytes()).decode()
    return {"hlo": text, "rounds": 2,
            "trace": trace.reduce(trace.load(d), n_devices=1)}


def test_each_op_in_exactly_one_phase(scoped):
    ops = scoped["trace"]["op_seconds"]
    split = scopes.split(scoped)
    assert sum(n for _, n in split["phases"].values()) == len(ops)
    assert sum(sec for sec, _ in split["phases"].values()) == pytest.approx(
        sum(ops.values()), rel=1e-9)
    instr = scopes.instructions(scoped["hlo"])
    assert all(op in instr for op in ops)


def test_round_phases_read(scoped):
    """Every phase of a one-chip round reads a number, the exchange none
    (no collective among one device), and the unscoped rest is under 2 %
    of the busy time."""
    for name in ("client_fwd", "client_recompute", "client_bwd", "compress",
                 "aggregate", "update"):
        assert scopes.round_ms(scoped, name) > 0, name
    assert scopes.round_ms(scoped, "exchange") is None
    busy_ms = 1000.0 * scoped["trace"]["busy_s"] / scoped["rounds"]
    assert (scopes.round_ms(scoped, "unscoped") or 0.0) < 0.02 * busy_ms
    for name in ("embed", "attention", "mlp", "head_loss"):
        assert scopes.client_ms(scoped, name) > 0, name


def test_kernels_land_in_their_phase(scoped):
    instr = scopes.instructions(scoped["hlo"])
    ops = scoped["trace"]["op_seconds"]
    found = {}
    for op, kernel in hlo.pallas_kernels(scoped["hlo"]).items():
        if op in ops:
            kind, op_name = instr[op]
            found.setdefault(kernel, set()).add(
                (scopes.phase(op_name, kind), scopes.part(op_name)))
    assert found["dsc_quantize._kernel"] == {("compress", None)}
    assert found["quantize._dequant_kernel"] == {("aggregate", None)}
    assert found["flash_attention._fwd_kernel"] == {
        ("client_fwd", "attention"), ("client_recompute", "attention")}
    assert found["flash_attention._dq_kernel"] == {("client_bwd", "attention")}
    assert found["flash_attention._dkv_kernel"] == {
        ("client_bwd", "attention")}
    flash = sum(ops[op] for op, k in hlo.pallas_kernels(scoped["hlo"]).items()
                if k.startswith("flash_attention.") and op in ops)
    assert 1000.0 * flash / scoped["rounds"] <= scopes.client_ms(
        scoped, "attention")
