#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \\
        [--control-seeds 1,2,3 --controls int8] [--self-seeds 1,2] \\
        [--fault half_batch=1,2,3 --fault stale_shift=1] [--out FILE]

For each ``--seeds`` seed: the program's first rounds (the compiled round
the window drives, reset to that seed) against the plain reference, as a
run of ``run.py`` compares them.  For each ``--control-seeds`` seed: the
control, the reference computed in each ``--controls`` precision, against
the reference.  For each ``--self-seeds`` seed: the reference against
itself with other draws.  For each ``--fault NAME=SEEDS``: the fault
planted in the reference, on those seeds, against the reference.  The
reference of a seed is computed once.  Prints one JSON line per reading
(and appends it to ``--out``).  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default="float8",
                    help="precisions of the control, comma-separated")
    ap.add_argument("--self-seeds", default="")
    ap.add_argument("--fault", action="append", default=[],
                    metavar="NAME=SEEDS")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    from bench import compare, harness
    from bench.reference import eris as ref_eris
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    devices = jax.devices()[:cell.chips]
    steps = cell.traffic["check_steps"]
    refs = {}

    def ref_run(seed, **kw):
        return ref_eris.run(cell.conf, cell.traffic, harness.seed_key(seed),
                            steps=steps, devices=devices, **kw)

    def reference(seed):
        if seed not in refs:
            refs[seed] = ref_run(seed)
        return refs[seed]

    def emit(kind, seed, prog, ref, t0):
        rec = {"workload": cell.name, "kind": kind, "seed": seed,
               "numbers": compare.numbers(prog, ref),
               "losses": prog.losses, "ref_losses": ref.losses,
               "update": prog.update, "ref_update": ref.update,
               "change": prog.change, "ref_change": ref.change,
               "shift": prog.shift, "ref_shift": ref.shift,
               "ref_grad": ref.grad, "seconds": time.perf_counter() - t0}
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    trainer = None
    for seed in _seeds(args.seeds):
        t0 = time.perf_counter()
        key = harness.seed_key(seed)
        if trainer is None:
            trainer = harness.Trainer(cell, key, devices)
        else:
            trainer.reset(key)
        prog = trainer.first_rounds(steps)
        trainer.free()
        emit("program", seed, prog, reference(seed), t0)
    del trainer
    for seed in _seeds(args.self_seeds):
        t0 = time.perf_counter()
        emit("reference", seed, ref_run(seed, stream=2), reference(seed), t0)
    for seed in _seeds(args.control_seeds):
        for precision in args.controls.split(","):
            t0 = time.perf_counter()
            emit(f"control_{precision}", seed,
                 ref_run(seed, precision=precision, stream=2),
                 reference(seed), t0)
    for spec in args.fault:
        fault, _, seeds = spec.partition("=")
        for seed in _seeds(seeds):
            t0 = time.perf_counter()
            emit(fault, seed, ref_run(seed, stream=2, fault=fault),
                 reference(seed), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
