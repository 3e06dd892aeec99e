"""Tensor-parallelism (model axis) contracts.

Two layers of coverage for the family-generic manual-collective TP
(``models/shard_plan``):

  * property tests (no devices): ``TPSpec`` maps EVERY entry of
    ``transformer.param_spec`` with tree congruence, shard dims divide,
    plan fallbacks (GQA kv < tp, indivisible experts/heads) and the
    composite model x client store spec — across all five families;
  * sharded-vs-replicated parity (subprocess, 4 host devices):
    ``loss_fn(tp=None)`` against the 2-way and 4-way TP lowering under a
    manual shard_map — loss AND gradients to fp32 tolerance.  One
    subprocess sweeps the dense-family plan variants (col/row/vocab/
    partial kinds), a second sweeps the family plans of ISSUE 4:
    expert-parallel MoE (token all_to_all dispatch), head-sharded mLSTM,
    channel-sharded hybrid mamba, and sequence-parallel dense (incl.
    the replicated-attention fallback inside a seq plan).
"""
import dataclasses
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import SUBPROC_ENV
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.dist import sharding as sh
from repro.models import shard_plan as sp
from repro.models import transformer as tr


def _smoke(arch: str):
    return get_config(arch).smoke()


# ------------------------------------------------------------ TPSpec map
@pytest.mark.parametrize("arch", ["qwen2-0.5b", "olmoe-1b-7b", "xlstm-350m",
                                  "hymba-1.5b", "internvl2-26b"])
@pytest.mark.parametrize("tp", [1, 2, 4])
def test_tp_specs_cover_param_tree(arch, tp):
    """Every param leaf gets a TPSpec (congruent trees), every sharded
    dim divides, and tp == 1 replicates everything."""
    cfg = _smoke(arch)
    specs = sh.tp_specs(cfg, tp)
    params = jax.eval_shape(lambda k: tr.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_structure(specs)
            == jax.tree_util.tree_structure(params))
    plan = tr.tp_plan(cfg, tp)
    for p, s in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(specs)):
        assert isinstance(s, sh.TPSpec)
        if s.dim >= 0:
            assert p.shape[s.dim] % tp == 0, (p.shape, s)
            assert s.kind in ("col", "row", "vocab", "expert")
        else:
            assert s.kind in ("replicate", "partial")
    if tp == 1:
        assert not plan.active
        assert all(s.kind == "replicate"
                   for s in jax.tree_util.tree_leaves(specs))


def test_tp_plan_fallbacks():
    cfg = _smoke("qwen2-0.5b")          # heads=4, kv=2, d_ff=512, V=512
    assert tr.tp_plan(cfg, 2) == tr.TPPlan(2, attn=True, ffn=True,
                                           vocab=True)
    p4 = tr.tp_plan(cfg, 4)
    assert not p4.attn                  # kv=2 cannot split 4 ways
    assert p4.ffn and p4.vocab and p4.active
    assert not tr.tp_plan(cfg, 1).active
    p3 = tr.tp_plan(cfg, 3)
    assert not (p3.attn or p3.ffn or p3.vocab)  # nothing divides by 3
    assert p3.ctx == 3 and p3.active    # ...but the ctx ring shards the
    # sequence at ANY size (weights replicated; the runtime still falls
    # back per-trace when S itself doesn't divide)
    qk = dataclasses.replace(cfg, qk_norm=True)
    specs = sh.tp_specs(qk, 2)
    assert specs["blocks"]["q_norm"].kind == "partial"
    # at tp=4 attention head-sharding falls back, but the ctx ring
    # sequence-shards the region, so its grads are still slice-partial
    assert sh.tp_specs(qk, 4)["blocks"]["q_norm"].kind == "partial"


def test_family_plans():
    """ISSUE 4: every family in the zoo gets an active model-axis plan
    (at a divisible size) — moe/ssm/hybrid no longer replicate."""
    moe = _smoke("olmoe-1b-7b")         # smoke: 4 experts, heads=4, kv=2
    p = tr.tp_plan(moe, 2)
    assert p.moe and p.vocab and p.attn and p.active
    p4 = tr.tp_plan(moe, 4)
    assert p4.moe and not p4.attn       # kv=2: attention falls back
    specs = sh.tp_specs(moe, 2)
    assert specs["blocks"]["w_gate"] == sh.TPSpec(1, "expert")
    assert specs["blocks"]["router"].kind == "partial"

    ssm = _smoke("xlstm-350m")          # 4 mLSTM heads, gated 2*D proj
    p = tr.tp_plan(ssm, 4)
    assert p.mixer and p.ffn and p.vocab and p.active
    specs = sh.tp_specs(ssm, 4)
    assert specs["blocks"]["xq"] == sh.TPSpec(2, "col")
    assert specs["blocks"]["xo"] == sh.TPSpec(1, "row")
    assert specs["blocks"]["b_i"] == sh.TPSpec(1, "col")
    assert specs["blocks"]["p_down"] == sh.TPSpec(1, "row")

    hyb = _smoke("hymba-1.5b")          # channel-sharded mamba branch
    p = tr.tp_plan(hyb, 2)
    assert p.mixer and p.ffn and p.attn
    specs = sh.tp_specs(hyb, 2)
    assert specs["blocks"]["m_dt"] == sh.TPSpec(2, "col")
    assert specs["blocks"]["m_out"] == sh.TPSpec(1, "row")
    assert specs["blocks"]["m_in"].kind == "partial"
    assert specs["blocks"]["m_bc"].kind == "partial"
    # indivisible experts/heads fall back to replication of that region
    odd = dataclasses.replace(moe, n_experts=3)
    assert not tr.tp_plan(odd, 2).moe


def test_seq_plan_gating_and_partial_kinds():
    """A seq plan needs ffn+vocab; block/final norms (and, under the
    GQA attention fallback, the attention leaves) become partial-grad."""
    cfg = dataclasses.replace(_smoke("qwen2-0.5b"), seq_parallel=True)
    p2 = tr.tp_plan(cfg, 2)
    assert p2.seq and p2.attn
    specs = sh.tp_specs(cfg, 2)
    assert specs["blocks"]["ln1"].kind == "partial"
    assert specs["ln_f"].kind == "partial"
    assert specs["blocks"]["wq"] == sh.TPSpec(2, "col")
    p4 = tr.tp_plan(cfg, 4)             # kv=2: attention replicates...
    assert p4.seq and not p4.attn
    specs4 = sh.tp_specs(cfg, 4)
    # ...but its grads only cover this position's sequence slice
    assert specs4["blocks"]["wq"].kind == "partial"
    assert specs4["blocks"]["wo"].kind == "partial"
    # without a shardable vocab (or ffn) the seq request is refused
    odd_v = dataclasses.replace(cfg, vocab=511)
    assert not tr.tp_plan(odd_v, 2).seq
    # and without the knob nothing changes
    off = dataclasses.replace(cfg, seq_parallel=False)
    assert not tr.tp_plan(off, 2).seq
    assert sh.tp_specs(off, 2)["blocks"]["ln1"].kind == "replicate"


def test_param_roles_cover_every_family():
    """The role table names every block leaf of every family's
    param_spec (the metadata tp_specs derives placements from)."""
    for arch in ["qwen2-0.5b", "olmoe-1b-7b", "xlstm-350m", "hymba-1.5b"]:
        cfg = _smoke(arch)
        roles = sp.PARAM_ROLES[cfg.family]
        for name in tr.param_spec(cfg)["blocks"]:
            if name in ("ln1", "ln2"):
                continue                # norm scales: seq-partial rule
            assert name in roles, (cfg.family, name)


@given(pre=st.integers(1, 3), mid=st.integers(1, 4), post=st.integers(1, 3),
       dim=st.integers(0, 2), tp=st.sampled_from([1, 2, 4]))
@settings(max_examples=25, deadline=None)
def test_tp_split_merge_roundtrip(pre, mid, post, dim, tp):
    shape = [3 * pre, 4 * mid, 5 * post]
    shape[dim] *= tp
    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    spec = sh.TPSpec(dim, "col")
    shards = sh.tp_split_leaf(x, spec, tp)
    assert shards.shape == (tp, *sh.tp_local_shape(tuple(shape), spec, tp))
    np.testing.assert_array_equal(sh.tp_merge_leaf(shards, spec), x)
    # replicated leaves: stacked copies, merge = shard 0
    rep = sh.TPSpec()
    np.testing.assert_array_equal(
        sh.tp_merge_leaf(sh.tp_split_leaf(x, rep, tp), rep), x)


def test_composite_store_spec():
    from jax.sharding import PartitionSpec as P
    # distinct dims: one axis each
    assert sh.composite_store_spec(2, 1, "data") == P(None, "data", "model")
    # same dim: model-major contiguous blocks, client-segmented within
    assert sh.composite_store_spec(1, 1, ("pod", "data")) == \
        P(None, ("model", "pod", "data"))
    assert sh.composite_store_spec(-1, 0, "data") == P("data")
    assert sh.composite_store_spec(0, -1, "data") == P("model")
    assert sh.composite_store_spec(-1, -1, "data") == P()


def test_store_layout_is_model_and_client_sharded():
    """The 'store' layout of a TP-able config shards FFN/vocab leaves
    over BOTH meshes and keeps every leaf's spec consistent with its
    TP-local scatter dim."""
    cfg = _smoke("qwen2-0.5b")
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # build specs AS IF the mesh were (2 data, 2 model) — spec math only
    specs = sh.tp_specs(cfg, 2)
    assert specs["embed"] == sh.TPSpec(0, "vocab")
    assert specs["blocks"]["w_down"] == sh.TPSpec(1, "row")
    assert specs["blocks"]["wo"] == sh.TPSpec(1, "row")
    # on the real (trivial) mesh the composite reduces to the FSA layout
    from jax.sharding import PartitionSpec as P
    store = sh.store_specs(cfg, mesh)
    for s in jax.tree_util.tree_leaves(
            store, is_leaf=lambda x: isinstance(x, P)):
        flat = []
        for part in tuple(s):
            flat.extend(part if isinstance(part, tuple) else [part])
        assert "model" not in flat, s


# ----------------------------------------- sharded-vs-replicated parity
_PARITY_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.configs import get_config
    from repro.dist import sharding as sh
    from repro.models import transformer as tr

    KEY = jax.random.PRNGKey(0)

    def run_case(name, tp, cfg, use_mask=False):
        params = tr.init_params(KEY, cfg)
        toks = jax.random.randint(jax.random.fold_in(KEY, 1), (2, 16),
                                  0, cfg.vocab)
        batch = {"tokens": toks}
        if use_mask:
            batch["loss_mask"] = (jax.random.uniform(
                jax.random.fold_in(KEY, 2), (2, 16)) > 0.3).astype(
                jnp.float32)

        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: tr.loss_fn(p, cfg, batch))(params)

        mesh = Mesh(np.array(jax.devices()[:tp]), ("model",))
        specs = sh.tp_specs(cfg, tp)
        plan = tr.tp_plan(cfg, tp)
        pspec = jax.tree.map(
            lambda s: P(*([None] * s.dim + ["model"])) if s.dim >= 0
            else P(), specs)

        def body(params, midx):
            tp_rt = tr.TPRuntime("model", tp, midx[0], plan)
            loss, grads = jax.value_and_grad(
                lambda p: tr.loss_fn(p, cfg, batch, tp=tp_rt))(params)
            grads = sh.tp_grad_sync(grads, specs, "model")
            return loss, grads

        fn = sh.shard_map(body, mesh, in_specs=(pspec, P("model")),
                          out_specs=(P(), pspec))
        with mesh:
            loss, grads = jax.jit(fn)(params,
                                      jnp.arange(tp, dtype=jnp.int32))
        errs = {"loss": abs(float(loss) - float(ref_loss))}
        worst = 0.0       # per-leaf max abs error, scaled by the leaf's
        for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
            # scale floor: leaves whose true grad is pure f32 noise
            # (e.g. mLSTM gate biases at init, ~1e-8) stay comparable
            worst = max(worst, float(
                np.max(np.abs(g - r)) / max(np.max(np.abs(r)), 1e-4)))
        errs["grad_relerr"] = worst
        return errs
""")

PARITY_TP_SCRIPT = _PARITY_PRELUDE + textwrap.dedent("""
    # minimal TP-able config: the wiring is identical per layer, so one
    # layer at small width keeps the subprocess fast-tier-cheap while
    # exercising every collective placement
    BASE = dataclasses.replace(
        get_config("qwen2-0.5b").smoke(), n_layers=1, d_model=128,
        head_dim=32, d_ff=256, vocab=256, attn_chunk=16)

    CASES = [
        ("tp2_full", 2, {}),                       # attn+ffn+vocab all TP
        ("tp4_gqa_fallback", 4, {}),               # kv=2: attn replicated
        ("tp2_qknorm_untied", 2,                   # partial grads + lm_head
         dict(qk_norm=True, tie_embeddings=False, loss_fp32_logits=False)),
        ("tp4_masked", 4, {"_mask": True}),
    ]

    out = {}
    for name, tp, opts in CASES:
        opts = dict(opts)
        use_mask = opts.pop("_mask", False)
        out[name] = run_case(name, tp, dataclasses.replace(BASE, **opts),
                             use_mask)
    print("TPPARITY" + json.dumps(out))
""")


PARITY_FAMILY_SCRIPT = _PARITY_PRELUDE + textwrap.dedent("""
    def small(arch, **kw):
        return dataclasses.replace(get_config(arch).smoke(), n_layers=1,
                                   **kw)

    CASES = [
        # expert-parallel MoE: group-sharded tokens, all_to_all
        # dispatch/combine, replicated router w/ partial grads; tp4 also
        # exercises the GQA attention fallback alongside expert sharding
        ("moe_tp2", 2, small("olmoe-1b-7b", moe_group_size=8)),
        ("moe_tp4", 4, small("olmoe-1b-7b", moe_group_size=8)),
        # head-sharded mLSTM mixer + gated in-block projection pair
        ("ssm_tp2", 2, small("xlstm-350m")),
        ("ssm_tp4", 4, small("xlstm-350m")),
        # hybrid: attention (tp2) / fallback (tp4) + channel-sharded
        # mamba branch (m_in/m_bc partial, psum'd m_ln statistics) + ffn
        ("hybrid_tp2", 2, small("hymba-1.5b")),
        ("hybrid_tp4", 4, small("hymba-1.5b")),
        # sequence parallelism: psum_scatter/all_gather conjugates; tp4
        # runs the replicated-attention region inside the seq plan
        ("seq_tp2", 2, small("qwen2-0.5b", seq_parallel=True)),
        ("seq_tp4", 4, small("qwen2-0.5b", seq_parallel=True)),
    ]

    out = {}
    for name, tp, cfg in CASES:
        plan = tr.tp_plan(cfg, tp)
        assert plan.active, (name, plan)
        if name.startswith("moe"):
            assert plan.moe, plan
        if name.startswith(("ssm", "hybrid")):
            assert plan.mixer, plan
        if name.startswith("seq"):
            assert plan.seq, plan
        out[name] = run_case(name, tp, cfg)
    print("TPPARITY" + json.dumps(out))
""")


def _run_parity_script(script: str) -> dict:
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=900,
                       env=SUBPROC_ENV)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("TPPARITY")][-1]
    return json.loads(line[len("TPPARITY"):])


def test_tp_loss_and_grads_match_replicated():
    """Dense-family plan variants: loss_fn under 2-way and 4-way TP
    reproduces the replicated loss AND gradients to fp32 tolerance
    (full TP, GQA attention fallback, qk-norm partial grads, untied
    unembed, masked loss)."""
    out = _run_parity_script(PARITY_TP_SCRIPT)
    assert set(out) == {"tp2_full", "tp4_gqa_fallback",
                        "tp2_qknorm_untied", "tp4_masked"}
    for name, errs in out.items():
        assert errs["loss"] < 1e-5, (name, errs)
        assert errs["grad_relerr"] < 1e-3, (name, errs)


def test_family_plans_match_replicated():
    """ISSUE 4 acceptance: sharded-vs-replicated parity of loss AND
    grads at 2- and 4-way for an expert-parallel MoE config, a
    head-sharded SSM config, a channel-sharded hybrid config, and a
    dense config with sequence parallelism enabled."""
    out = _run_parity_script(PARITY_FAMILY_SCRIPT)
    assert set(out) == {"moe_tp2", "moe_tp4", "ssm_tp2", "ssm_tp4",
                        "hybrid_tp2", "hybrid_tp4", "seq_tp2", "seq_tp4"}
    for name, errs in out.items():
        assert errs["loss"] < 1e-5, (name, errs)
        assert errs["grad_relerr"] < 1e-3, (name, errs)
