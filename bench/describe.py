#!/usr/bin/env python3
"""Compile a cell's round for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/describe.py --workload <name>

Builds the round the cell's window drives at its real size, compiles it
for one chip (or a 2x2 host for a four-chip cell) of a described
``v5e:2x2`` topology, and prints the compiled program's memory analysis,
whether it holds Pallas kernels, and the ring-weighted bytes per device
of its collectives.  Nothing runs, so it gives no time.
"""
import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--hlo", default=None, help="write the program text here")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from bench import harness, hlo
    from repro.dist import sharding as sh
    from repro.launch.train import (TrainSettings, abstract_train_state,
                                    dsc_spec_tree, make_train_step)
    from repro.optim import adam
    jax.config.update("jax_enable_compilation_cache", False)
    # the kernels choose interpret mode from the default backend, which is
    # the CPU here: compile them for the chip instead
    import importlib
    for mod in ("flash_attention", "quantize", "dsc_quantize", "dsc_update"):
        m = importlib.import_module(f"repro.kernels.{mod}")
        m.interpret_mode = lambda interpret=None: bool(interpret)
    cell = harness.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = harness.make_mesh(topo.devices[:cell.chips])
    cfg = harness.model_config(cell)
    o = cell.traffic["optimizer"]
    opt = adam(o["lr"], o["b1"], o["b2"], o["eps"])
    settings = TrainSettings(**cell.traffic["wire"])
    step, shardings = make_train_step(cfg, mesh, opt, settings)
    params, opt_state, dsc = abstract_train_state(cfg, mesh, opt, settings)
    tr = cell.traffic
    batch = {"tokens": jax.ShapeDtypeStruct(
        (tr["clients"] * tr["rows_per_client"], tr["seq_len"]), jnp.int32)}

    def put(tree, shard):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                              sharding=s),
                            tree, shard)
    dsc_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          dsc_spec_tree(cfg, mesh, settings),
                          is_leaf=lambda x: isinstance(x, P))
    args_ = (put(params, shardings["store"]),
             put(opt_state, sh.opt_state_shardings(cfg, mesh, opt, params)),
             put(dsc, dsc_sh),
             put(batch, sh.batch_shardings(cfg, mesh, batch)),
             jax.ShapeDtypeStruct((2,), jnp.uint32,
                                  sharding=NamedSharding(mesh, P())))
    with mesh:
        compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(*args_).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 1 << 30
    out = {"workload": cell.name,
           "arguments_gib": mem.argument_size_in_bytes / gib,
           "outputs_gib": mem.output_size_in_bytes / gib,
           "aliased_gib": mem.alias_size_in_bytes / gib,
           "temporaries_gib": mem.temp_size_in_bytes / gib,
           "pallas_kernels": hlo.pallas_kernels(text),
           "collective_bytes": hlo.collective_bytes(text)}
    if args.hlo:
        Path(args.hlo).write_text(text)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
