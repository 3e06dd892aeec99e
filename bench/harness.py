"""One run of one training cell: set-up, the first rounds that the
comparison reads, the measured window (or a traced one), and the check.

The entry the window drives is the compiled round of
``repro.launch.train.make_train_step``, placed and called as
``repro.launch.train.train`` does: weights in the store layout, the DSC
state of ``init_dsc_state``, the state donated, every round's loss read
on the host (in the window, ``AHEAD_S`` seconds of rounds after it was
sent, see ``drive``).  Set-up builds that one object, drives it
through the first ``check_steps`` rounds, whose readings the comparison
uses, and hands the same object to the window.  Every round takes the
next batch of a pool drawn on the device from the seed.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import compare, tokens  # noqa: E402
from bench.reference import eris as ref_eris  # noqa: E402

# ModelConfig field of each published config.json key the harness maps
HF_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
           "intermediate_size": "d_ff", "vocab_size": "vocab",
           "head_dim": "head_dim", "rope_theta": "rope_theta",
           "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
           "torch_dtype": "dtype"}

# seconds of rounds kept queued on the device ahead of the loss being read
AHEAD_S = 5.0


# ------------------------------------------------------------------ cells
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: str
    conf: dict
    traffic: dict
    limits: Optional[dict]
    per_layer: list


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json",
              traffic_dir: Path = BENCH / "traffic",
              limits_dir: Path = BENCH / "limits") -> Cell:
    """The workload ``name`` of ``bench_file`` with its configuration,
    traffic, limits and the per-layer metrics it reports."""
    spec = json.loads(Path(bench_file).read_text())
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    conf = json.loads((ROOT / cfg["file"]).read_text())
    traffic = json.loads((Path(traffic_dir) / f"{w['traffic']}.json").read_text())
    lim_file = Path(limits_dir) / f"{name}.json"
    limits = json.loads(lim_file.read_text()) if lim_file.exists() else None

    def applies(m):
        return name in m.get("workloads", [name])
    e2e = {m["name"] for m in spec["end_to_end"] if applies(m)}
    per_layer = [m for m in spec["per_layer"]
                 if applies(m) and m["moves"] in e2e]
    return Cell(name, w["chips"], w["config"], conf, traffic, limits,
                per_layer)


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number: the low 31 bits seed it, the rest are
    folded in."""
    seed %= 1 << 62
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


# ---------------------------------------------------------------- program
def model_config(cell: Cell):
    from repro.models.config import ModelConfig
    kw = {v: cell.conf[k] for k, v in HF_KEYS.items() if k in cell.conf}
    return ModelConfig(name=cell.config, **kw, **cell.conf["program"])


def make_mesh(devices):
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(devices).reshape(len(devices), 1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


@contextlib.contextmanager
def _exchange_left_out():
    """Trace the round with the client-axis all_to_all replaced by the
    identity: every aggregator keeps its own client's rows."""
    real = jax.lax.all_to_all
    jax.lax.all_to_all = lambda x, *a, **k: x
    try:
        yield
    finally:
        jax.lax.all_to_all = real


class Trainer:
    """The compiled round with its state and its feed.  ``fault`` plants
    one fault in the timed path (for the tests of the comparison):
    ``frozen`` (the round returns its state unchanged), ``stale_shift``
    (the DSC shift state is never updated), ``half_batch`` (each client
    trains on the first half of its rows) or ``no_exchange`` (the int8
    exchange between chips left out)."""

    def __init__(self, cell: Cell, key: jax.Array, devices,
                 fault: Optional[str] = None, log=lambda s: None):
        from repro.launch.train import TrainSettings, make_train_step
        from repro.optim import adam
        from bench.reference import model
        o = cell.traffic["optimizer"]
        if o["name"] != "adam":
            raise ValueError(f"{cell.name}: the round is built with adam, "
                             f"not {o['name']!r}")
        self.cell, self.fault, self.log = cell, fault, log
        self.cfg = model_config(cell)
        self.mesh = make_mesh(devices)
        self.opt = adam(o["lr"], o["b1"], o["b2"], o["eps"])
        self.settings = TrainSettings(**cell.traffic["wire"])
        with self.mesh:
            step, shardings = make_train_step(self.cfg, self.mesh, self.opt,
                                              self.settings)
        log("round built")
        self.store = shardings["store"]
        self.init = functools.partial(model(cell.conf).init, conf=cell.conf)
        shapes = lambda f: jax.tree.map(
            lambda x: (x.shape, x.dtype), jax.eval_shape(f, key))
        if shapes(self.init) != shapes(functools.partial(_program_init,
                                                         cfg=self.cfg)):
            raise SystemExit("the reference's weight layout differs from "
                             "the trainer's parameter tree")
        self.reset(key)
        log("state made")
        donate = {"frozen": (), "stale_shift": (0, 1)}.get(fault, (0, 1, 2))
        lowering = (_exchange_left_out() if fault == "no_exchange"
                    else contextlib.nullcontext())
        t0 = time.perf_counter()
        with self.mesh, lowering:
            self.compiled = jax.jit(step, donate_argnums=donate).lower(
                self.params, self.opt_state, self.dsc, self.batches[0],
                self.keys[0]).compile()
        self.compile_s = time.perf_counter() - t0
        log(f"round compiled ({self.compile_s:.1f} s)")

    def reset(self, key: jax.Array) -> None:
        """Weights, optimizer and DSC state, batch pool and round keys made
        anew from ``key``, on the device, in the layouts the round takes."""
        from repro.dist import sharding as sh
        from repro.launch.train import init_dsc_state
        tr = self.cell.traffic
        self.k_w, k_d = jax.random.split(key)
        with self.mesh:
            self.params = jax.jit(self.init, out_shardings=self.store)(self.k_w)
            self.opt_state = self.opt.init(self.params)
            self.dsc = init_dsc_state(self.cfg, self.mesh, self.settings)
            pool = jax.jit(functools.partial(
                tokens.pool, traffic=tr, vocab=self.cfg.vocab,
                rounds=tr["pool_rounds"]))(k_d)
            if self.fault == "half_batch":
                K, r = tr["clients"], tr["rows_per_client"]
                pool = pool.reshape(pool.shape[0], K, r, -1)[:, :, : r // 2]
                pool = pool.reshape(pool.shape[0], K * (r // 2), -1)
            bsh = sh.batch_shardings(self.cfg, self.mesh, {"tokens": pool[0]})
            self.batches = [jax.device_put({"tokens": pool[i]}, bsh)
                            for i in range(pool.shape[0])]
            keys = jax.random.split(jax.random.fold_in(key, 0x7EED),
                                    pool.shape[0])
            self.keys = [keys[i] for i in range(pool.shape[0])]
        self.i = 0

    def dispatch(self) -> jax.Array:
        """Send one round through the compiled step; returns its loss on
        the device, not yet read."""
        with jax.profiler.TraceAnnotation("bench.next_batch"):
            j = self.i % len(self.batches)
            batch, key = self.batches[j], self.keys[j]
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            params, opt_state, dsc, m = self.compiled(
                self.params, self.opt_state, self.dsc, batch, key)
        if self.fault != "frozen":
            self.params, self.opt_state = params, opt_state
        if self.fault not in ("frozen", "stale_shift"):
            self.dsc = dsc
        self.i += 1
        return m["loss"]

    def round(self) -> float:
        """One round; returns its loss, read on the host as ``train()``
        reads it, and keeps the round's time in ``round_s``."""
        t0 = time.perf_counter()
        loss = self.dispatch()
        with jax.profiler.TraceAnnotation("bench.read_loss"):
            loss = float(loss)
        self.round_s = time.perf_counter() - t0
        return loss

    def first_rounds(self, steps: int) -> ref_eris.Readings:
        """Drive the first ``steps`` rounds and read what the comparison
        needs: each loss, the first update the optimizer got (its first
        moment after one round over 1 - b1), the weights' change and the
        DSC shift state."""
        b1 = self.cell.traffic["optimizer"]["b1"]
        losses = [self.round()]
        self.log("first round read")
        mu = self.opt_state.mu
        update = {k: n / (1 - b1) for k, n in ref_eris.leaf_norms(mu).items()}
        self.log("first update read")
        losses += [self.round() for _ in range(steps - 1)]
        self.log(f"round {steps} read")
        change = ref_eris.change_norms(self.params, self.cell.conf, self.k_w)
        shift = None
        if self.settings.use_dsc:
            shift = ref_eris.shift_readings(
                ref_eris.leaf_norms(self.dsc["s_clients"]),
                ref_eris.leaf_norms(self.dsc["s_agg"]))
        return ref_eris.Readings(losses, update, change, shift=shift)

    def free(self) -> None:
        for name in ("params", "opt_state", "dsc", "batches"):
            setattr(self, name, None)
        gc.collect()


def _program_init(key, cfg):
    from repro.models import transformer as tr
    return tr.init_params(key, cfg)


# -------------------------------------------------------------------- run
@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    metrics: dict
    device: dict
    checks: list
    breakdown: Optional[dict] = None


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, fault: Optional[str] = None,
             log=lambda s: None) -> Outcome:
    """Set-up, the first rounds, the window (or a traced one) and the
    comparison, on ``devices``.  ``t_start`` is the process's start on
    the clock of ``time.perf_counter``."""
    from bench import peaks as peak_table
    tr = cell.traffic
    key = seed_key(seed)

    def stamp(what):
        log(f"{time.perf_counter() - t_start:8.2f} s  {what}")
    stamp("start of set-up")
    trainer = Trainer(cell, key, devices, fault=fault, log=stamp)
    prog = trainer.first_rounds(tr["check_steps"])
    stamp("first rounds read")
    losses = list(prog.losses)
    metrics, breakdown, info = {}, None, device_info(devices)
    setup_s = time.perf_counter() - t_start
    if trace:
        ctx = traced_window(trainer, cell, devices)
        losses += ctx["losses"]
        metrics = read_per_layer(cell, ctx, peak_table.peaks(info["kind"]))
        breakdown = ctx["breakdown"]
        info.update(busy_s=ctx["trace"]["busy_s"], window_s=ctx["trace"]["window_s"])
    else:
        done_losses, window_s = drive(trainer, seconds=seconds)
        losses += done_losses
        done = len(done_losses)
        metrics = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "train_tokens_per_s": {
                "value": done * tokens.round_tokens(tr) / window_s / len(devices),
                "unit": "tokens/s"}}
        log(f"{done} rounds in {window_s:.3f} s "
            f"(a checked round {trainer.round_s:.3f} s)")
    stamp("end of window")
    info["memory_peak_bytes"] = memory_peak(devices)
    trainer.free()
    del trainer
    reference = ref_eris.run(cell.conf, tr, key, steps=tr["check_steps"],
                             devices=devices, log=stamp)
    stamp("reference done")
    values = compare.numbers(prog, reference)
    ok, checks = compare.decide(values, cell.limits)
    failed = sum(not math.isfinite(x) for x in losses)
    return Outcome(ok and failed == 0, len(losses), failed, metrics, info,
                   checks, breakdown)


def drive(trainer: Trainer, seconds: float = math.inf,
          rounds: Optional[int] = None) -> tuple[list, float]:
    """Rounds sent until ``seconds`` have passed or ``rounds`` are sent,
    each loss read once the rounds of the next ``AHEAD_S`` seconds are
    queued behind it, so that a host which stands still for less than that
    leaves the chip fed.  Then nothing more is sent, every round sent is
    waited for, and only after that wait is the clock read.  Returns the
    losses of all rounds sent and the seconds from the first send to that
    reading."""
    ahead = max(1, math.ceil(AHEAD_S / trainer.round_s))
    pending, losses = collections.deque(), []
    t0 = time.perf_counter()
    while ((rounds is None or len(losses) + len(pending) < rounds)
           and time.perf_counter() - t0 < seconds):
        pending.append(trainer.dispatch())
        if len(pending) > ahead:
            with jax.profiler.TraceAnnotation("bench.read_loss"):
                losses.append(float(pending.popleft()))
    with jax.profiler.TraceAnnotation("bench.drain"):
        losses += [float(x) for x in pending]
    return losses, time.perf_counter() - t0


def traced_window(trainer: Trainer, cell: Cell, devices) -> dict:
    """``trace_rounds`` rounds under the profiler; returns the trace's
    reduction and what the per-layer readers need."""
    from bench import hlo as hlo_lib, trace as trace_lib
    out = OUT / "trace" / cell.name
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        losses, window_s = drive(trainer, rounds=cell.traffic["trace_rounds"])
    jax.profiler.stop_trace()
    red = trace_lib.reduce(trace_lib.load(out), n_devices=len(devices))
    hlo_text = trainer.compiled.as_text()
    return {"losses": losses, "rounds": len(losses), "host_window_s": window_s,
            "trace": red, "hlo": hlo_text,
            "kernels": hlo_lib.pallas_kernels(hlo_text),
            "chips": len(devices), "cell": cell,
            "breakdown": {"device_ops": red["top_ops"],
                          "idle_gaps": red["idle_gaps"]}}


def read_per_layer(cell: Cell, ctx: dict, peaks: dict) -> dict:
    """Each per-layer metric of the cell, read by ``bench/metrics/<name>.py``;
    a reader that finds nothing returns None and the metric is left out."""
    ctx = dict(ctx, peaks=peaks)
    out = {}
    for m in cell.per_layer:
        path = BENCH / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
