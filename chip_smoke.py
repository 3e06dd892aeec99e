#!/usr/bin/env python3
"""Bring-up smoke run of the ERIS trainer and server on a TPU, at
qwen2-0.5b's full width, through the entry points a user calls.

    python3 chip_smoke.py             # one chip: train, then serve
    python3 chip_smoke.py --chips 4   # four chips: one FSA round per wire

One chip.  qwen2-0.5b with nothing cut (24 layers, d_model 896, vocab
151936, random weights from a seed) trains through
``repro.launch.train.train`` (``make_host_mesh``, ``make_train_step``,
``init_dsc_state``) on batch 8 x seq 1024 with flash attention: a few
steps on the f32 wire, then a few on the DSC + int8 wire (the fused
``dsc_quantize`` kernel).  The loss must be finite and fall, and each
compiled step must hold the Pallas kernels (``tpu_custom_call``).  The
trained params then go into a ``ServeEngine``, which answers 4 requests
of 8 tokens through the Pallas paged-attention kernel; two of them are
served again alone and must come out token-identical.

Four chips.  The FSA round exists only across chips: 4 clients and 4
aggregators on a data=4 mesh run one round each on the f32
``psum_scatter`` wire, the int8 ``all_to_all`` wire and the fused DSC +
int8 wire, with f32 weights and plain SGD.  The f32 round must match a
one-device FedAvg step on the same four client batches (Theorem B.1)
parameter by parameter; the int8 round must stay within its
quantization bound of the f32 round; every aggregator's parameter
segment and every client's shift state must sit on its own device.

Lines before the last are observations (host wall clock and compile
seconds), not metrics.  The last line is the JSON result.  Without a
TPU the script exits non-zero and prints no result.
"""
import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
BATCH, SEQ = 8, 1024
TRAIN_STEPS = 6
TRAIN_LR = 1e-3          # adam, the one-chip training runs
ROUND_LR = 1.0           # sgd, the four-chip rounds: the update is the
                         # aggregated gradient itself
N_REQUESTS, GEN = 4, 8
PARITY_TOL = 1e-3        # f32 round vs FedAvg, relative to the update


def observe(phase: str, **values) -> None:
    print(f"observation {phase}: {json.dumps(values)}", flush=True)


def device_info(chips: int) -> dict:
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    print(f"devices: {info}", flush=True)
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{info['platform']!r}); there is no CPU fallback")
    if info["count"] != chips:
        sys.exit(f"chip_smoke: asked for {chips} chip(s), JAX sees "
                 f"{info['count']}")
    return info


def token_batch(cfg, batch: int, seq: int, seed: int = 0) -> dict:
    import jax
    from repro.data import lm_token_batches
    return {"tokens": lm_token_batches(jax.random.PRNGKey(seed), 1, batch,
                                       seq, cfg.vocab)[0]}


def require_kernels(text: str, what: str) -> None:
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{what}: the compiled program holds no "
                             f"Pallas kernel (tpu_custom_call)")


# ------------------------------------------------------------------ phases
def train_phase(cfg, mesh, settings, name: str, batch, steps: int,
                lr: float = TRAIN_LR):
    """A few adam steps on one batch; the loss must be finite and fall.
    Returns the :class:`repro.launch.train.TrainRun`."""
    from repro.launch.train import train
    from repro.optim import adam
    run = train(cfg, mesh, adam(lr), settings, batch, steps,
                log=lambda line: print(f"train/{name} {line}", flush=True))
    observe(f"train/{name}", compile_s=run.compile_s, losses=run.losses)
    if not all(math.isfinite(x) for x in run.losses):
        raise AssertionError(f"train/{name}: loss not finite: {run.losses}")
    if not run.losses[-1] < run.losses[0]:
        raise AssertionError(f"train/{name}: loss did not fall: "
                             f"{run.losses}")
    return run


def serve_phase(cfg, params, settings, n_requests: int = N_REQUESTS):
    """Serve ``n_requests`` random prompts batched, then the first two
    alone; the streams must agree token for token.  Returns the engine."""
    import numpy as np
    from repro.serve import SamplingParams, ServeEngine
    engine = ServeEngine(cfg, params, settings)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(5, 25)))
               .tolist() for _ in range(n_requests)]
    sampling = [SamplingParams() if i % 2 == 0 else
                SamplingParams(temperature=0.8, top_k=20, top_p=0.95)
                for i in range(n_requests)]
    t0 = time.perf_counter()
    for i, prompt in enumerate(prompts):
        engine.submit(prompt, sampling=sampling[i], seed=i)
    batched = engine.run()
    batched_s = time.perf_counter() - t0
    gen = settings.max_new_tokens
    if len(batched) != n_requests or any(len(o.tokens) != gen
                                         for o in batched):
        raise AssertionError(f"serve: expected {n_requests} x {gen} "
                             f"tokens, got {[o.tokens for o in batched]}")
    t0 = time.perf_counter()
    for i in (0, 1):
        alone = engine.run([prompts[i]], sampling=sampling[i], seed=i)[0]
        if alone.tokens != batched[i].tokens:
            raise AssertionError(
                f"serve: request {i} batched {batched[i].tokens} != "
                f"alone {alone.tokens}")
    observe("serve", batched_s=batched_s,
            alone_s=time.perf_counter() - t0,
            tokens=[o.tokens for o in batched])
    return engine


def fedavg_reference(cfg, opt, batch, n_client: int, key):
    """One FedAvg step on one device: every client's gradient on its own
    rows of ``batch``, averaged, then the optimizer.  Returns (params0,
    params1, gmax) on the host, where gmax holds per leaf the largest
    |gradient| of any one client (what bounds the int8 wire's error)."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tr
    params = tr.init_params(key, cfg)
    grad = jax.jit(jax.grad(lambda p, b: tr.loss_fn(p, cfg, b)))
    rows = batch["tokens"].shape[0] // n_client
    total = gmax = None
    for k in range(n_client):
        g = grad(params, {"tokens": batch["tokens"][k * rows:(k + 1) * rows]})
        m = jax.tree.map(lambda x: jnp.max(jnp.abs(x)), g)
        total = g if total is None else jax.tree.map(jnp.add, total, g)
        gmax = m if gmax is None else jax.tree.map(jnp.maximum, gmax, m)

    @jax.jit
    def apply(params, total):
        mean = jax.tree.map(lambda t: t / n_client, total)
        delta, _ = opt.update(mean, opt.init(params), params)
        return jax.tree.map(jnp.add, params, delta)

    new = apply(params, total)
    return jax.device_get(params), jax.device_get(new), jax.device_get(gmax)


def check_own_devices(tree, dims, mesh, what: str) -> None:
    """Leaf by leaf: the shard holding segment a of the client-sharded
    dim sits on the mesh's device a."""
    import jax
    devices = list(mesh.devices.reshape(-1))
    for (path, leaf), dim in zip(jax.tree_util.tree_leaves_with_path(tree),
                                 jax.tree.leaves(dims)):
        if dim < 0:
            continue
        seg = leaf.shape[dim] // len(devices)
        owners = {(s.index[dim].start or 0) // seg: s.device
                  for s in leaf.addressable_shards}
        if owners != dict(enumerate(devices)):
            raise AssertionError(f"{what} {jax.tree_util.keystr(path)}: "
                                 f"segments on {owners}")


def bound_ratios(got, want, bound) -> dict:
    """Per leaf: max |got - want| over that leaf's ``bound``."""
    import jax
    import numpy as np
    ratios = jax.tree.map(
        lambda a, b, t: float(np.max(np.abs(a.astype(np.float64) - b))) / t,
        got, want, bound)
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(ratios)}


def fsa_rounds(cfg, mesh, batch):
    """One round on each wire over the mesh's client axis; returns the
    compiled text of the int8 round for the kernel check."""
    import jax
    import numpy as np
    from repro.dist import sharding as sh
    from repro.launch.train import TrainSettings, train
    from repro.optim import sgd
    cfg = dataclasses.replace(cfg, dtype="float32")
    opt = sgd(ROUND_LR)
    key = jax.random.PRNGKey(0)
    n_client = sh.client_count(mesh)
    dims = sh.fsa_scatter_dims(cfg, mesh)
    wires = {"f32": TrainSettings(grad_dtype="float32"),
             "int8": TrainSettings(grad_dtype="float32", int8_wire=True),
             "dsc_int8": TrainSettings(grad_dtype="float32", int8_wire=True,
                                       use_dsc=True)}
    # true f32 products on both sides: the comparison is of the
    # aggregation, not of the matmul unit's default bf16 passes
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        p0, p_ref, gmax = fedavg_reference(cfg, opt, batch, n_client, key)
        observe("fsa/fedavg_reference", wall_s=time.perf_counter() - t0)
        rounds = {}
        for name, settings in wires.items():
            run = train(cfg, mesh, opt, settings, batch, 1, key)
            observe(f"fsa/{name}", compile_s=run.compile_s,
                    loss=run.losses)
            if not math.isfinite(run.losses[0]):
                raise AssertionError(f"fsa/{name}: loss {run.losses}")
            check_own_devices(run.params, dims, mesh, f"fsa/{name} params")
            rounds[name] = run
    if not all(np.isfinite(x).all() for x in jax.tree.leaves(
            jax.device_get(rounds["dsc_int8"].params))):
        raise AssertionError("fsa/dsc_int8: params not finite")
    check_own_devices(rounds["dsc_int8"].dsc_ref["s_clients"],
                      jax.tree.map(lambda _: 0, gmax), mesh,
                      "fsa/dsc_int8 s_clients")
    f32 = jax.device_get(rounds["f32"].params)
    int8 = jax.device_get(rounds["int8"].params)
    # slack of two f32 ulps of the largest weight, for the rounding of
    # p0 + update itself
    ulps = jax.tree.map(
        lambda p: 2 * float(np.finfo(np.float32).eps * np.abs(p).max()), p0)
    # Theorem B.1: FSA's reduce-scatter + shard-local update is FedAvg
    parity = bound_ratios(f32, p_ref, jax.tree.map(
        lambda a, b, u: PARITY_TOL * float(np.abs(a - b).max()) + u,
        p_ref, p0, ulps))
    observe("fsa/f32_vs_fedavg", worst_err_over_bound=max(parity.values()))
    if max(parity.values()) > 1.0:
        raise AssertionError(f"fsa/f32 differs from FedAvg: {parity}")
    # stochastic int8: each client's error is below its block scale
    # max|block| / 127, so the mean's is below lr * gmax / 127
    quant = bound_ratios(int8, f32, jax.tree.map(
        lambda g, u: (1 + PARITY_TOL) * ROUND_LR * float(g) / 127 + u,
        gmax, ulps))
    observe("fsa/int8_vs_f32", worst_err_over_bound=max(quant.values()))
    if max(quant.values()) > 1.0:
        raise AssertionError(f"fsa/int8 outside its quantization bound: "
                             f"{quant}")
    return rounds["int8"].compiled.as_text()


# -------------------------------------------------------------------- main
def one_chip(cfg, mesh) -> None:
    from repro.launch.train import TrainSettings
    from repro.serve import ServeSettings
    batch = token_batch(cfg, BATCH, SEQ)
    f32 = train_phase(cfg, mesh, TrainSettings(grad_dtype="float32"),
                      "f32", batch, TRAIN_STEPS)
    require_kernels(f32.compiled.as_text(), "train/f32")
    del f32
    dsc = train_phase(cfg, mesh,
                      TrainSettings(grad_dtype="float32", int8_wire=True,
                                    use_dsc=True),
                      "dsc_int8", batch, TRAIN_STEPS)
    require_kernels(dsc.compiled.as_text(), "train/dsc_int8")
    engine = serve_phase(cfg, dsc.params, ServeSettings(
        max_concurrency=N_REQUESTS, block_size=16, num_blocks=64,
        max_model_len=64, prefill_bucket=32, max_new_tokens=GEN,
        decode_kernel="pallas"))
    require_kernels(engine.lower_decode().as_text(), "serve/decode")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the FSA rounds across four chips")
    args = ap.parse_args(argv)
    info = device_info(args.chips)
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    enable_compile_cache()
    cfg = get_config(ARCH)
    mesh = make_host_mesh(data=args.chips)
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(cfg, mesh)
    else:
        require_kernels(fsa_rounds(cfg, mesh, token_batch(cfg, BATCH, SEQ)),
                        "fsa/int8")
    observe("total", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
