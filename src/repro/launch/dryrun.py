"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

Proves the distribution config is coherent without real hardware:
``.lower().compile()`` must succeed on the 16x16 single-pod mesh and the
2x16x16 multi-pod mesh; records memory_analysis / cost_analysis /
collective bytes (parsed from HLO) into a JSON artifact per combo.

Usage:
    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k \
        [--multi-pod] [--dsc] [--out experiments/dryrun]
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder CPU devices —
# set before ANY other import; jax locks the device count on first init.
# It is a CPU lowering tool: pinning the platform keeps it (and every
# child that launch/sweep.py starts) off an attached TPU.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
from pathlib import Path # noqa: E402

import jax               # noqa: E402
import numpy as np       # noqa: E402


# --------------------------------------------------------------- dry run
def run_one(arch: str, shape_name: str, multi_pod: bool,
            use_dsc: bool = False, fsa: bool = True,
            grad_dtype: str = "float16", int8_wire: bool = False,
            save_hlo: bool = False, out_dir: str = "experiments/dryrun",
            tag: str = "", opt: str = "", pp: int = 1,
            microbatches: int = 1) -> dict:
    import dataclasses
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES
    from repro.launch import train as train_lib
    from repro.launch import serve as serve_lib
    from repro.models import shard_plan as sp_lib

    cfg = get_config(arch)
    # XLA *CPU* aborts on bf16 all-reduce (AllReducePromotion pass bug).
    # float16 has the same byte width, so every roofline quantity (bytes,
    # collective payloads, memory) is identical; real TPU runs use bf16.
    if cfg.dtype == "bfloat16":
        cfg = dataclasses.replace(cfg, dtype="float16")
    # perf-iteration knobs: --opt k=v,k=v (ModelConfig field overrides)
    if opt:
        kw = {}
        for item in opt.split(","):
            k, v = item.split("=")
            kw[k] = {"true": True, "false": False}.get(
                v.lower(), int(v) if v.isdigit() else v)
        cfg = dataclasses.replace(cfg, **kw)
    mesh = make_production_mesh(multi_pod=multi_pod, pipe=pp)
    shape = SHAPES[shape_name]
    n_dev = int(np.prod(mesh.devices.shape))
    t0 = time.time()
    if shape.kind == "train":
        settings = train_lib.TrainSettings(use_dsc=use_dsc, fsa=fsa,
                                           grad_dtype=grad_dtype,
                                           int8_wire=int8_wire,
                                           microbatches=microbatches)
        lowered = train_lib.lower_train_step(cfg, mesh, shape_name, settings)
    else:
        lowered = serve_lib.lower_step(cfg, mesh, shape_name)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_size = sizes.get("model", 1)
    pipe_size = sizes.get("pipe", 1)
    from repro.launch import hlo_analysis
    deep = hlo_analysis.analyze(hlo, model_axis_size=int(model_size),
                                pipe_axis_size=int(pipe_size))

    from repro.models.transformer import (active_param_count, param_count,
                                          tp_plan)
    plan = tp_plan(cfg, int(model_size))
    from repro.dist import sharding as sh_lib
    n_tp_sharded = sum(s.dim >= 0 for s in jax.tree_util.tree_leaves(
        sh_lib.tp_specs(cfg, int(model_size))))
    pipe_plan = sp_lib.build_pipeline_plan(cfg, int(pipe_size),
                                           microbatches)
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(d) for d in mesh.devices.shape),
        "devices": n_dev, "kind": shape.kind,
        "fsa": fsa, "use_dsc": use_dsc, "grad_dtype": grad_dtype,
        "int8_wire": int8_wire,
        "wire_dtype": deep["collective_bytes"].get("wire_dtype", ""),
        "tp": {"size": int(model_size), "attn": plan.attn,
               "ffn": plan.ffn, "vocab": plan.vocab, "moe": plan.moe,
               "mixer": plan.mixer, "seq": plan.seq,
               "ctx": plan.ctx, "seq_ce": plan.seq_ce,
               "sharded_leaves": int(n_tp_sharded)} if shape.kind == "train"
        else {"size": int(model_size)},
        "pp": {"size": int(pipe_size),
               "microbatches": int(microbatches),
               "layers_per_stage": pipe_plan.layers_per_stage,
               "bubble_fraction": pipe_plan.bubble_fraction},
        "param_bytes_per_device": sh_lib.param_bytes_per_device(cfg, mesh),
        "tag": tag,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "params": param_count(cfg),
        "active_params": active_param_count(cfg),
        # trip-count-aware HLO analysis (per device)
        "flops_per_device": deep["flops"],
        "bytes_accessed_per_device": deep["traffic_bytes"],
        "collective_bytes_per_device": deep["collective_bytes"],
        # raw XLA numbers (loop bodies counted once) for reference
        "xla_cost_flops": float(cost.get("flops", 0.0)),
        "xla_cost_bytes": float(cost.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
        },
    }
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    suffix = ("_mp" if multi_pod else "") \
        + (f"_pp{pp}" if pp > 1 else "") + (f"_{tag}" if tag else "")
    fname = out / f"{arch.replace('.', '_')}__{shape_name}{suffix}.json"
    fname.write_text(json.dumps(record, indent=1))
    if save_hlo:
        (out / (fname.stem + ".hlo.txt")).write_text(hlo)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dsc", action="store_true")
    ap.add_argument("--no-fsa", action="store_true",
                    help="FedAvg baseline layout (replicated optimizer)")
    ap.add_argument("--grad-dtype", default="float16")
    ap.add_argument("--int8-wire", action="store_true",
                    help="int8 blocks + f32 scales as the FSA wire format")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--opt", default="",
                    help="ModelConfig overrides, e.g. "
                         "seq_parallel=true,vocab=50176")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipe axis size (carved out of the data dim)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="1F1B microbatch count (train shapes)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    rec = run_one(args.arch, args.shape, args.multi_pod, args.dsc,
                  fsa=not args.no_fsa, grad_dtype=args.grad_dtype,
                  int8_wire=args.int8_wire,
                  save_hlo=args.save_hlo, out_dir=args.out, tag=args.tag,
                  opt=args.opt, pp=args.pp, microbatches=args.microbatches)
    mem_gib = rec["memory"]["peak_bytes"] / 2**30
    print(f"OK {rec['arch']} {rec['shape']} mesh={rec['mesh']} "
          f"compile={rec['compile_s']}s peak={mem_gib:.2f}GiB/dev "
          f"flops/dev={rec['flops_per_device']:.3e} "
          f"coll={ {k: f'{v:.2e}' for k, v in rec['collective_bytes_per_device'].items() if isinstance(v, float) and v} }")


def _self_test():
    """Quick sanity of the record fields on the smallest arch."""
    rec = run_one("qwen2-0.5b", "train_4k", multi_pod=False)
    assert rec["flops_per_device"] > 0
    print(rec)


if __name__ == "__main__":
    main()
