"""Model-scale multi-process worker (reference: test_dist_base.py:682
runs dist_transformer at model scale across trainer processes): a tiny
Llama with REAL tensor-parallel shardings trains on a dp=2 x mp=2 mesh
spanning FOUR single-device processes. Rank 0 writes the loss sequence
to argv[1] for the 1-proc oracle comparison.

Why one device per process (the seed's 2-proc x 4-device layout aborted
~50% of runs): gloo's TCP pairs mis-frame when two different collectives
of one clique are in flight on the same pair at once ("op.preamble.length
<= op.nbytes", gloo/transport/tcp/pair.cc) — and XLA emits whole-mesh
cliques (the loss/grad all-reduces span the whole mesh), so any process
holding >= 2 devices has that many unsynchronized participant threads,
each pipelining its own op stream onto the shared pairs. With exactly
one device per process the op order per process is sequential and
identical across ranks (same SPMD program), and TCP preserves per-pair
order, so no message can be matched against the wrong buffer. The
legacy (non-thunk) CPU runtime keeps even a single device from
overlapping two collectives, and launch_collective(transient_retries=..)
in the test remains a bounded backstop.
"""
import json
import os
import sys

# one virtual CPU device per rank, BEFORE any jax backend touch
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=1 "
                           "--xla_cpu_use_thunk_runtime=false")
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

# all ranks compile the SAME SPMD program: without the shared persistent
# cache every rank pays the full XLA compile on every run
configure_compile_cache()
from paddle_tpu import optimizer  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402
from paddle_tpu.distributed import spmd, topology  # noqa: E402
from paddle_tpu.text.models import LlamaModel  # noqa: E402


def main():
    out_path = sys.argv[1]
    dist.init_parallel_env()
    rank, world = dist.get_rank(), dist.get_world_size()
    assert world == 4 and len(jax.devices()) == 4

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = topology.build_mesh(dp=2, mp=2)  # spans all 4 processes
    topology.set_global_mesh(mesh)
    paddle.seed(21)
    model = LlamaModel(vocab_size=64, hidden_size=32, num_layers=2,
                       num_heads=4, intermediate_size=64, num_kv_heads=2,
                       max_seq_len=32, tensor_parallel=True)
    opt = optimizer.AdamW(1e-3, parameters=model.parameters())

    def lm_loss(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None],
                                             axis=-1))

    step, init = spmd.build_train_step(model, lm_loss, opt, mesh=mesh)
    params, st = init()
    assert any("mp" in str(a.sharding.spec) for a in params.values())

    rng = np.random.RandomState(0)
    ids = rng.randint(0, 64, (8, 16)).astype(np.int32)
    lbl = rng.randint(0, 64, (8, 16)).astype(np.int32)
    # Each dp shard is replicated over its mp pair, so consecutive rank
    # pairs address the SAME batch rows — shard_batch's local-slice
    # contract (process axis == batch axis) does not apply. Every rank
    # materializes the full deterministic batch and the callback serves
    # the rows its device addresses.
    batch_sharding = NamedSharding(mesh, P("dp"))
    ids_g = jax.make_array_from_callback(ids.shape, batch_sharding,
                                         lambda idx: ids[idx])
    lbl_g = jax.make_array_from_callback(lbl.shape, batch_sharding,
                                         lambda idx: lbl[idx])

    losses = []
    for i in range(3):
        loss, params, st = step(params, st, ids_g, lbl_g,
                                key=jax.random.PRNGKey(0))
        losses.append(float(jax.device_get(loss)))
    if rank == 0:
        with open(out_path, "w") as f:
            json.dump(losses, f)
    print(f"rank {rank} llama losses {losses}", flush=True)


if __name__ == "__main__":
    main()
