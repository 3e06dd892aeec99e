"""A run of the harness with the timed path broken underneath comes out
not correct, and a sound one correct; the control (the reference in the
cell's lower precision put in the program's place) fails the comparison.
At the tiny size the CPU holds, with the limits of bench/tests/data/limits."""
import time
from pathlib import Path

import jax
import pytest

from bench import compare, harness
from bench.reference import dense, model
from bench.reference import eris as ref_eris

DATA = Path(__file__).resolve().parent / "data"


def cell(name):
    return harness.load_cell(name, DATA / "tiny.json", DATA / "traffic",
                             DATA / "limits")


def run(name, fault=None, seed=2 ** 33 + 7):
    c = cell(name)
    return harness.run_cell(c, seed, 0.2, False, jax.devices()[:c.chips],
                            time.perf_counter(), fault=fault)


@pytest.mark.parametrize("name,fault", [
    ("tiny-dense.dsc-int8", None),
    ("tiny-dense.dsc-int8", "frozen"),
    ("tiny-dense.dsc-int8", "stale_shift"),
    ("tiny-dense.dsc-int8", "half_batch"),
    ("tiny-dense.bf16", None),
    ("tiny-dense.bf16", "frozen"),
    ("tiny-dense.bf16", "half_batch"),
    ("tiny-dense.dsc-int8.4chip", None),
    ("tiny-dense.dsc-int8.4chip", "half_batch"),
    ("tiny-dense.dsc-int8.4chip", "no_exchange"),
])
def test_fault_makes_run_incorrect(name, fault):
    out = run(name, fault)
    assert out.correct == (fault is None), out.checks
    assert out.attempted > 3 and out.failed == 0


@pytest.mark.parametrize("name,fault", [
    ("tiny-dense.dsc-int8", None),
    ("tiny-dense.bf16", None),
    ("tiny-dense.dsc-int8.4chip", None),
    ("tiny-dense.dsc-int8", "stale_shift"),
    ("tiny-dense.dsc-int8", "frozen"),
])
def test_control_fails_the_comparison(name, fault):
    """The control, or with ``fault`` the reference with that fault
    planted, in the program's place."""
    c = cell(name)
    key, steps = harness.seed_key(11), c.traffic["check_steps"]
    devices = jax.devices()[:c.chips]
    ref = ref_eris.run(c.conf, c.traffic, key, steps=steps, devices=devices)
    precision = c.limits["control"] if fault is None else "float32"
    control = ref_eris.run(c.conf, c.traffic, key, steps=steps, devices=devices,
                           precision=precision, stream=2, fault=fault)
    ok, checks = compare.decide(compare.numbers(control, ref), c.limits)
    assert not ok, checks


def test_configuration_names_its_reference():
    assert model(cell("tiny-dense.bf16").conf) is dense
    with pytest.raises(ModuleNotFoundError):
        model({"reference": "no_such_model"})


def test_seed_key_takes_large_seeds():
    a, b = harness.seed_key(2 ** 31 + 5), harness.seed_key(5)
    assert (a != b).any()
    assert (harness.seed_key(2 ** 40) == harness.seed_key(2 ** 40)).all()
