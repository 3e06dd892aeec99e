#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The workload names a configuration and a
traffic mix in BENCHMARK.json.  ``--trace 0`` measures the window and
prints the cell's end-to-end metrics; ``--trace 1`` traces a few rounds
and prints its per-layer metrics.  Both then check the first rounds
against the plain reference.  The last line of standard output is one
JSON object; the last lines of standard error give each number compared
beside its limit.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime logs under /tmp unless told otherwise; a run writes
# nothing outside its checkout and its own temporary directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    if cell.limits is None:
        sys.exit(f"no limits for {cell.name} in bench/limits")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"JAX found no TPU (platform {devices[0].platform!r}); "
                 f"there is no CPU fallback")
    if len(devices) < cell.chips:
        sys.exit(f"{cell.name} needs {cell.chips} chips, JAX sees {len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices[:cell.chips], T_START,
                           log=lambda s: print(s, file=sys.stderr, flush=True))
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": out.metrics,
              "device": out.device}
    if out.breakdown is not None:
        result["breakdown"] = out.breakdown
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in out.checks}
    for n, v, lim in out.checks:
        print(f"check {n} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
