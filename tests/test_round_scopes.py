"""Named scopes of the ERIS round and host spans of ``train()``.

Every phase of the round (``launch.train.SCOPE_*``) and every part of the
model (``models.transformer.SCOPE_*``) must reach the compiled round's
instruction metadata, where a device trace's ops are looked up by name.
The round is compiled at the smoke config on one and on four host
devices (each in a subprocess with its own device count), over the fused
DSC + int8 wire, the unfused DSC + int8 chain and the bf16
``psum_scatter`` wire."""
import json
import subprocess
import sys
import textwrap

import pytest

from conftest import SUBPROC_ENV

WIRES = ("fused", "chain", "bf16")

SCRIPT = textwrap.dedent("""
    import os, sys
    n = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    import json, re
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch import train as lt
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tr
    from repro.optim import adam

    SCOPES = [lt.SCOPE_CLIENT, lt.SCOPE_COMPRESS, lt.SCOPE_EXCHANGE,
              lt.SCOPE_AGGREGATE, lt.SCOPE_UPDATE, tr.SCOPE_EMBED,
              tr.SCOPE_ATTENTION, tr.SCOPE_MLP, tr.SCOPE_HEAD_LOSS]
    ERIS = [s for s in SCOPES if s.startswith("eris.")]
    WIRES = {"fused": lt.TrainSettings(use_dsc=True, int8_wire=True),
             "chain": lt.TrainSettings(use_dsc=True, int8_wire=True,
                                       fused_wire=False),
             "bf16": lt.TrainSettings()}
    COLLECTIVE = re.compile(r"\\s(all-to-all|reduce-scatter)(-start)?\\(")
    cfg = get_config("qwen2-0.5b").smoke()
    mesh = make_host_mesh(data=n, model=1)
    opt = adam(1e-3)
    key = jax.random.PRNGKey(0)
    out = {}
    for wire, settings in WIRES.items():
        step, shardings = lt.make_train_step(cfg, mesh, opt, settings)
        with mesh:
            params = jax.device_put(tr.init_params(key, cfg),
                                    shardings["store"])
            dsc = lt.init_dsc_state(cfg, mesh, settings)
            batch = {"tokens": jnp.zeros((2 * n, 32), jnp.int32)}
            text = jax.jit(step).lower(params, opt.init(params), dsc, batch,
                                       key).compile().as_text()
        # a scope is one level of an op_name's path, which a
        # transformation may wrap: ".../transpose(jvp(model.mlp))/..."
        def scopes(op_name):
            return [s for s in re.split(r"[/()]", op_name) if s in SCOPES]
        names = re.findall(r'op_name="([^"]*)"', text)
        found = sorted({s for m in names for s in scopes(m)})
        collectives = []
        for line in text.splitlines():
            c = COLLECTIVE.search(line)
            if c:
                m = re.search(r'op_name="([^"]*)"', line)
                inner = [s for s in scopes(m.group(1) if m else "")
                         if s in ERIS]
                collectives.append([c.group(1),
                                    inner[-1] if inner else None])
        out[wire] = {"scopes": found, "collectives": collectives}
    print(json.dumps(out))
""")


def _scopes_of_rounds(n_devices: int) -> dict:
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(n_devices)],
                       capture_output=True, text=True, timeout=900,
                       env=SUBPROC_ENV)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def rounds():
    return {n: _scopes_of_rounds(n) for n in (1, 4)}


PHASES = ["eris.client", "eris.compress", "eris.exchange", "eris.aggregate",
          "eris.update"]
PARTS = ["model.embed", "model.attention", "model.mlp", "model.head_loss"]


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("wire", WIRES)
def test_every_scope_reaches_the_compiled_round(rounds, n_devices, wire):
    """Each phase and each model part names some instruction of the
    compiled round; on four devices every all-to-all and reduce-scatter is
    part of the exchange.  On one device the compiler drops an int8
    all_to_all among one device, so the exchange is asked for on four."""
    got = rounds[n_devices][wire]
    want = PHASES + PARTS
    if n_devices == 1:
        want = [s for s in want if s != "eris.exchange"]
    assert set(want) <= set(got["scopes"]), got["scopes"]
    if n_devices == 4:
        kinds = {"bf16": "reduce-scatter"}.get(wire, "all-to-all")
        assert any(k == kinds for k, _ in got["collectives"])
        assert all(scope == "eris.exchange"
                   for _, scope in got["collectives"]), got["collectives"]


def test_train_host_spans_share_the_trace_clock(tmp_path):
    """``train(..., profile_dir=)`` traces the steps after the first: one
    ``eris.round`` step span each, holding its ``eris.read_loss`` span and
    the ops the backend ran for that step, on the profiler's one clock."""
    from pathlib import Path

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import ProfileData
    from jax.sharding import AxisType, Mesh

    from repro.configs import get_config
    from repro.launch.train import (SPAN_READ_LOSS, SPAN_ROUND,
                                    TrainSettings, train)
    from repro.optim import adam

    cfg = get_config("qwen2-0.5b").smoke()
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    run = train(cfg, mesh, adam(1e-3), TrainSettings(),
                {"tokens": jnp.zeros((2, 32), jnp.int32)}, 3,
                profile_dir=str(tmp_path))
    assert len(run.losses) == 3
    files = sorted(Path(tmp_path).rglob("*.xplane.pb"))
    assert len(files) == 1
    spans, ops = {SPAN_ROUND: [], SPAN_READ_LOSS: []}, []
    for plane in ProfileData.from_file(str(files[0])).planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name in spans:
                    step = dict(ev.stats).get("step_num")
                    spans[ev.name].append((iv, step))
                elif line.name.startswith("tf_XLA"):
                    ops.append(iv)
    rounds = sorted(spans[SPAN_ROUND])
    assert [step for _, step in rounds] == [1, 2]
    assert len(spans[SPAN_READ_LOSS]) == 2
    for (r0, r1), _ in rounds:
        assert any(r0 <= s and e <= r1 for (s, e), _ in spans[SPAN_READ_LOSS])
        assert any(r0 <= s < r1 for s, _ in ops)
