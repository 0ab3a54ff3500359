#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile a train cell's step
program at its real size for a DESCRIBED v5e:2x2 topology, here, without a
chip, and print the compiler's memory account (the first ``hbm_compiled_gb``
prediction). It proves compilation and fit only: never a time, never
numerics. Run it from the sandbox (``JAX_PLATFORMS=cpu``), never on the chip:

    python3 benchmark/tools/aot_compile.py --workload bert-base.train-mlm-s128
"""
import argparse
import json
import os
import sys

#: what lets the sandbox's libtpu describe a v5e it is not attached to
DESCRIBED_V5E = {"JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled",
                 "TPU_SKIP_MDS_QUERY": "1",
                 "TPU_ACCELERATOR_TYPE": "v5litepod-4",
                 "TPU_WORKER_HOSTNAMES": "localhost", "TPU_WORKER_ID": "0"}

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, memory  # noqa: E402
from benchmark.harness.datasets import field_shapes  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows-per-chip", type=int, default=None,
                    help="try another batch than the traffic file's")
    args = ap.parse_args()
    for name, value in DESCRIBED_V5E.items():  # before jax is imported
        os.environ.setdefault(name, value)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed import spmd, topology

    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, args.workload)
    traffic = cells.load_json("traffic", cell["traffic"])
    sizes = cells.config_sizes(bench, cell["config"])
    config = cells.load_module("configs", cell["config"])
    shapes = field_shapes(traffic)
    chips = cell["chips"]
    rows = (args.rows_per_chip or traffic["rows_per_chip"]) * chips

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = topology.build_mesh(dp=chips, devices=list(topo.devices)[:chips])
    built = config.build_train(0, sizes, shapes)
    layer, opt = built["layer"], built["optimizer"]
    layer.train()
    step_fn, _ = spmd.build_train_step(
        layer, built["loss_fn"], opt, mesh=mesh,
        amp_level=built["amp_level"], donate=True)

    repl = NamedSharding(mesh, P())
    batch = NamedSharding(mesh, P("dp")) if chips > 1 else repl

    def sds(a, sharding=repl):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sharding)

    params0, buffers0 = layer.functional_state()
    params = {n: sds(a) for n, a in params0.items()}
    opt_state = {n: tuple(sds(s) for s in opt._init_state(a))
                 for n, a in params0.items()}
    buffers = {n: sds(jnp.asarray(a)) for n, a in buffers0.items()}
    by_name = {f["name"]: f for f in traffic["fields"]}
    width = sum(int(np.prod(by_name[n]["shape"][-1:]) or 1)
                for n in traffic["pack"])
    first = by_name[traffic["pack"][0]]
    x_shape = (rows,) + tuple(first["shape"][:-1]) + (width,)
    label = by_name[traffic["label"]]
    x = jax.ShapeDtypeStruct(x_shape, np.dtype(first["dtype"]),
                             sharding=batch)
    y = jax.ShapeDtypeStruct((rows,) + tuple(label["shape"]),
                             np.dtype(label["dtype"]), sharding=batch)
    key = sds(jax.random.PRNGKey(0))
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=repl)

    compiled = step_fn.jitted.lower(params, opt_state, buffers, x, y, key,
                                    lr).compile()
    rec = memory.program_memory("train_step", compiled)
    text = compiled.as_text()
    rec.update(workload=cell["name"], rows=rows, chips=chips,
               compiled_for="v5e:2x2 (described, not attached)",
               all_reduce_ops=text.count(" all-reduce("),
               all_reduce_start_ops=text.count(" all-reduce-start("),
               hbm_compiled_gb=rec["footprint_bytes"] / 1e9)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
