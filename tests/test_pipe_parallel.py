"""Pipeline (pipe axis) + ring-attention (ctx) contracts.

Two layers of coverage, mirroring tests/test_tp.py's split:

  * property tests (no devices): the ``PipelinePlan`` builder fallbacks,
    the 1F1B schedule enumerated by ``shard_plan.pipeline_schedule`` —
    every (stage, microbatch) cell exactly once per direction in a
    legal interleaved order — and the bubble-fraction bookkeeping the
    roofline consumes;
  * sharded-vs-replicated parity (subprocess, 8 host devices): the
    microbatched 1F1B ``pipeline_loss_fn`` under a manual shard_map
    over (pipe, model) against the replicated ``loss_fn`` — loss AND
    per-leaf gradients to fp32 tolerance — across pp={2,4} x tp x
    microbatch counts, including an indivisible-heads GQA config whose
    attention runs the ctx ppermute ring instead of the replicated
    fallback; plus the integrated ``make_train_step`` path (sharded
    loss + grad-norm vs replicated autodiff) with the composite
    client x pipe x model mesh.
"""
import json
import subprocess
import sys
import textwrap

import pytest

from conftest import SUBPROC_ENV
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.dist import sharding as sh
from repro.models import shard_plan as sp


# ------------------------------------------------------------ plan builder
def test_pipeline_plan_fallbacks():
    cfg = get_config("qwen2-0.5b").smoke()          # 2 layers
    assert not sp.build_pipeline_plan(cfg, 1, 4).active
    assert sp.build_pipeline_plan(cfg, 2, 4).active
    # 2 layers don't split into 4 contiguous stages -> inactive
    assert not sp.build_pipeline_plan(cfg, 4, 4).active
    with pytest.raises(ValueError, match="microbatches"):
        sp.build_pipeline_plan(cfg, 2, 0)


def test_pipeline_plan_geometry():
    cfg = get_config("qwen3-32b")                   # 64 layers
    plan = sp.build_pipeline_plan(cfg, 4, 8)
    assert plan.active and plan.layers_per_stage == 16
    assert plan.bubble_fraction == pytest.approx(3 / 11)
    assert sp.build_pipeline_plan(cfg, 1, 1).bubble_fraction == 0.0


def test_pipe_dims_mark_only_block_leaves():
    import jax
    from repro.models import transformer as tr
    cfg = get_config("qwen2-0.5b").smoke()
    pdims = sh.pipe_dims(cfg, 2)
    params = jax.eval_shape(lambda k: tr.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_structure(pdims)
            == jax.tree_util.tree_structure(params))
    flat = dict(zip([jax.tree_util.keystr(k) for k, _ in
                     jax.tree_util.tree_flatten_with_path(pdims)[0]],
                    jax.tree_util.tree_leaves(pdims)))
    for key, pd in flat.items():
        assert pd == (0 if "blocks" in key else -1), (key, pd)
    # pp == 1: nothing is pipe-sliced
    assert all(pd == -1 for pd in jax.tree_util.tree_leaves(
        sh.pipe_dims(cfg, 1)))


# --------------------------------------------------------- 1F1B schedule
@settings(max_examples=80, deadline=None)
@given(p=st.integers(1, 6), m=st.integers(1, 8))
def test_1f1b_schedule_legal_and_complete(p, m):
    """Every (stage, microbatch) cell appears exactly once per direction,
    in an order satisfying the pipeline's data dependencies:

      F(s, i) after F(s-1, i)   (activations flow down the stages)
      B(s, i) after B(s+1, i)   (cotangents flow back up)
      B(s, i) after F(s, i)     (a stage backs up only what it ran)
      per-stage F's and B's each in increasing microbatch order
    """
    order = sp.pipeline_schedule(p, m)
    assert len(order) == 2 * p * m
    pos = {}
    for t, (s, i, d) in enumerate(order):
        assert (s, i, d) not in pos, "duplicate cell"
        pos[(s, i, d)] = t
    for s in range(p):
        for i in range(m):
            assert (s, i, "F") in pos and (s, i, "B") in pos
            assert pos[(s, i, "B")] > pos[(s, i, "F")]
            if s > 0:
                assert pos[(s, i, "F")] > pos[(s - 1, i, "F")]
                assert pos[(s - 1, i, "B")] > pos[(s, i, "B")]
            if i > 0:
                assert pos[(s, i, "F")] > pos[(s, i - 1, "F")]
                assert pos[(s, i, "B")] > pos[(s, i - 1, "B")]


def test_1f1b_wavefront_matches_bubble_accounting():
    """The schedule's forward wavefront spans exactly m + p - 1 ticks —
    the denominator of ``PipelinePlan.bubble_fraction``."""
    for p, m in [(2, 2), (4, 8), (3, 5)]:
        order = sp.pipeline_schedule(p, m)
        # stage s's first forward is at wavefront tick s, its last at
        # s + m - 1; the global forward span is m + p - 1 ticks
        f_events = [(s, i) for s, i, d in order if d == "F"]
        by_stage = {}
        for s, i in f_events:
            by_stage.setdefault(s, []).append(i)
        assert all(v == sorted(v) for v in by_stage.values())
        assert len(by_stage) == p and all(len(v) == m
                                          for v in by_stage.values())


# ------------------------------------------------- subprocess parity
_PIPE_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.configs import get_config
    from repro.dist import sharding as sh
    from repro.models import shard_plan as sp
    from repro.models import transformer as tr

    def run_case(name, tp, pipe, mb, cfg):
        B, S = 8, 32
        toks = jax.random.randint(jax.random.fold_in(
            jax.random.PRNGKey(7), hash(name) % 1000), (B, S), 0, cfg.vocab)
        batch = {"tokens": toks}
        params = tr.init_params(jax.random.PRNGKey(0), cfg)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: tr.loss_fn(p, cfg, batch))(params)

        plan = tr.tp_plan(cfg, tp)
        pplan = sp.build_pipeline_plan(cfg, pipe, mb)
        assert pplan.active, (name, pplan)
        specs = sh.tp_specs(cfg, tp)
        pdims = sh.pipe_dims(cfg, pipe)

        def one(s, pd):
            hi = max(s.dim, pd)
            if hi < 0:
                return P()
            parts = [None] * (hi + 1)
            if pd >= 0:
                parts[pd] = "pipe"
            if s.dim >= 0:
                parts[s.dim] = "model"
            return P(*parts)

        pspec = jax.tree.map(one, specs, pdims)
        devs = np.array(jax.devices())[:pipe * tp]
        mesh = Mesh(devs.reshape(pipe, tp), ("pipe", "model"))

        def body(params, pidx, midx):
            tp_rt = (tr.TPRuntime("model", tp, midx[0], plan)
                     if plan.active else None)
            pipe_rt = sp.PipeRuntime("pipe", pipe, pidx[0], pplan)
            loss, grads = jax.value_and_grad(
                lambda p: tr.pipeline_loss_fn(p, cfg, batch, tp=tp_rt,
                                              pipe=pipe_rt))(params)
            if tp_rt is not None:
                grads = sh.tp_grad_sync(grads, specs, "model")
            grads = sh.pipe_grad_sync(grads, pdims, "pipe")
            return loss, grads

        fn = sh.shard_map(body, mesh,
                          in_specs=(pspec, P("pipe"), P("model")),
                          out_specs=(P(), pspec))
        with mesh:
            loss, grads = jax.jit(fn)(
                params, jnp.arange(pipe, dtype=jnp.int32),
                jnp.arange(tp, dtype=jnp.int32))
        errs = {"loss": abs(float(loss) - float(ref_loss)),
                "ring": plan.ctx > 1}
        worst = 0.0
        for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
            worst = max(worst, float(
                np.max(np.abs(g - r)) / max(np.max(np.abs(r)), 1e-4)))
        errs["grad_relerr"] = worst
        return errs
""")

PIPE_PARITY_SCRIPT = _PIPE_PRELUDE + textwrap.dedent("""
    # 4 layers so pp={2,4} both split into equal contiguous stages;
    # small width keeps the 8-device host subprocess fast-tier-cheap
    BASE = dataclasses.replace(
        get_config("qwen2-0.5b").smoke(), n_layers=4, d_model=128,
        head_dim=32, d_ff=256, vocab=256, attn_chunk=16)

    CASES = [
        ("pp2", 1, 2, 2, {}),            # pure pipeline, 2 microbatches
        ("pp2_tp2", 2, 2, 2, {}),        # pipe x model composite
        ("pp2_tp2_mb4", 2, 2, 4, {}),    # deeper 1F1B wavefront
        ("pp4_mb4", 1, 4, 4, {}),        # 4 stages, 1 layer each
        # GQA kv=2 < tp=4: heads don't divide, so attention runs the
        # ctx ppermute ring (online-softmax K/V rotation) INSIDE the
        # pipeline instead of falling back to replicated attention
        ("pp2_tp4_ring_gqa", 4, 2, 2, {}),
    ]

    out = {}
    for name, tp, pipe, mb, opts in CASES:
        cfg = dataclasses.replace(BASE, **opts)
        out[name] = run_case(name, tp, pipe, mb, cfg)
    assert out["pp2_tp4_ring_gqa"]["ring"]
    print("PPPARITY" + json.dumps(out))
""")

PIPE_TRAIN_STEP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch import train as lt
    from repro.models import transformer as tr
    from repro.optim import adam

    cfg = get_config("qwen2-0.5b").smoke()          # 2 layers -> pp=2
    out = {}
    for name, model, pipe, mb in [("client_pp2_tp2_mb4", 2, 2, 4),
                                  ("client_pp2_ring_gqa", 4, 2, 2)]:
        mesh = make_host_mesh(data=None, model=model, pipe=pipe)
        settings = lt.TrainSettings(grad_dtype="float32", microbatches=mb)
        opt = adam(1e-2)
        step, shardings = lt.make_train_step(cfg, mesh, opt, settings)
        params = tr.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab)
        ref_loss, ref_gr = jax.value_and_grad(
            lambda p: tr.loss_fn(p, cfg, {"tokens": toks}))(params)
        gn_ref = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in jax.tree.leaves(ref_gr)))
        with mesh:
            params_s = jax.device_put(params, shardings["store"])
            opt_state = opt.init(params_s)
            dsc_ref = lt.init_dsc_state(cfg, mesh, settings)
            _, _, _, m = jax.jit(step)(params_s, opt_state, dsc_ref,
                                       {"tokens": toks},
                                       jax.random.PRNGKey(2))
        out[name] = {
            "loss": abs(float(m["loss"]) - float(ref_loss)),
            "gnorm_relerr": abs(float(m["grad_norm"]) - float(gn_ref))
            / float(gn_ref)}
    print("PPPARITY" + json.dumps(out))
""")


def _run_parity_script(script: str) -> dict:
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=900,
                       env=SUBPROC_ENV)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("PPPARITY")][-1]
    return json.loads(line[len("PPPARITY"):])


def test_pipeline_loss_and_grads_match_replicated():
    """ISSUE 9 acceptance: the microbatched 1F1B pipeline body under a
    manual (pipe, model) shard_map reproduces the replicated loss AND
    per-leaf gradients to fp32 tolerance at pp={2,4} x tp x microbatch
    counts — including the GQA config whose attention rides the ctx
    ppermute ring instead of the replicated fallback."""
    out = _run_parity_script(PIPE_PARITY_SCRIPT)
    assert set(out) == {"pp2", "pp2_tp2", "pp2_tp2_mb4", "pp4_mb4",
                        "pp2_tp4_ring_gqa"}
    for name, errs in out.items():
        assert errs["loss"] < 1e-5, (name, errs)
        assert errs["grad_relerr"] < 1e-3, (name, errs)


def test_pipeline_train_step_matches_replicated():
    """The full train step (client x pipe x model mesh, FSA optimizer
    path, bucketed grad-norm) agrees with replicated autodiff on loss
    and gradient norm."""
    out = _run_parity_script(PIPE_TRAIN_STEP_SCRIPT)
    for name, errs in out.items():
        assert errs["loss"] < 1e-5, (name, errs)
        assert errs["gnorm_relerr"] < 1e-3, (name, errs)
