"""``chip_smoke.py`` at smoke size on the CPU.

The script itself refuses to run without a TPU; these tests drive its
phases on the reduced same-family config so a change that breaks the
bring-up path fails here before it costs a chip run.  The kernels run
in interpret mode, so the ``tpu_custom_call`` checks are the chip's.
"""
import importlib.util
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from conftest import SUBPROC_ENV

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_smoke()
CFG = get_config(cs.ARCH).smoke()


@pytest.mark.parametrize("wire", ["f32", "dsc_int8"])
def test_train_phase_loss_falls(wire):
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import TrainSettings
    settings = TrainSettings(grad_dtype="float32",
                             int8_wire=wire == "dsc_int8",
                             use_dsc=wire == "dsc_int8")
    run = cs.train_phase(CFG, make_host_mesh(data=1), settings, wire,
                         cs.token_batch(CFG, 4, 64), steps=3, lr=1e-2)
    assert len(run.losses) == 3


def test_serve_phase_batched_matches_alone():
    from repro.models import transformer as tr
    from repro.serve import ServeSettings
    import jax
    params = tr.init_params(jax.random.PRNGKey(0), CFG)
    settings = ServeSettings(max_concurrency=cs.N_REQUESTS, block_size=8,
                             num_blocks=32, max_model_len=40,
                             prefill_bucket=32, max_new_tokens=4,
                             cache_dtype="float32", decode_kernel="pallas")
    engine = cs.serve_phase(CFG, params, settings)
    assert engine.stats()["tokens_out"] == (cs.N_REQUESTS + 2) * 4
    # the engine's own decode step lowers; on the CPU the kernel runs in
    # interpret mode, inlined rather than a Mosaic call
    assert "tpu_custom_call" not in engine.lower_decode().as_text()


FSA_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import importlib.util, sys
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  "chip_smoke.py")
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    cfg = get_config(cs.ARCH).smoke()
    text = cs.fsa_rounds(cfg, make_host_mesh(data=4),
                         cs.token_batch(cfg, 8, 32))
    print("FSA_OK", len(text) > 0)
""")


def test_fsa_rounds_on_four_devices():
    """The four-chip phase on four virtual CPU devices: the f32 round
    matches one-device FedAvg, the int8 round stays within its bound,
    and every segment and shift state sits on its own device."""
    r = subprocess.run([sys.executable, "-c", FSA_SCRIPT], cwd=ROOT,
                       capture_output=True, text=True, timeout=600,
                       env=SUBPROC_ENV)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FSA_OK True" in r.stdout
    worst = [json.loads(line.split(": ", 1)[1])
             for line in r.stdout.splitlines()
             if line.startswith("observation fsa/f32_vs_fedavg")]
    assert worst and worst[0]["worst_err_over_bound"] <= 1.0


def test_refuses_without_a_tpu(tmp_path):
    """No TPU: a non-zero exit and no result line, from the checkout and
    from a directory holding the script alone."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text((ROOT / "chip_smoke.py").read_text())
    for script in (ROOT / "chip_smoke.py", alone):
        r = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                           capture_output=True, text=True, timeout=300,
                           env=SUBPROC_ENV)
        assert r.returncode != 0
        assert '"ok"' not in r.stdout


def test_compile_cache_honours_the_environment(monkeypatch):
    """An entry point keeps its compile cache where
    JAX_COMPILATION_CACHE_DIR says, else at the fixed checkout path."""
    import jax
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()

