"""ZeRO-3 memory behavior (VERDICT r2 #7): params at rest AND in flight
must not materialize the full parameter set; optimizer-state host
offload. Reference: fleet/meta_optimizers/sharding_optimizer.py:180 +
sharding/offload_helper.py."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed import spmd, topology

N_LAYERS = 8
DIM = 256


def _build(stage, offload=False, recompute=False):
    import jax.numpy as jnp

    mesh = topology.build_mesh(dp=1, sharding=8)
    topology.set_global_mesh(mesh)
    paddle.seed(1)
    m = nn.Sequential(*[nn.Linear(DIM, DIM) for _ in range(N_LAYERS)])
    opt = optimizer.Adam(1e-3, parameters=m.parameters())
    step, init = spmd.build_train_step(
        m, lambda o, t: jnp.mean((o - t) ** 2), opt, mesh=mesh,
        sharding_stage=stage, offload=offload, recompute=recompute)
    return step, init


def _host_kind():
    # where spmd keeps offloaded optimizer state between steps
    return spmd._HOST_MEMORY_KIND


def _data():
    x = np.random.RandomState(0).rand(8, DIM).astype(np.float32)
    y = np.random.RandomState(1).rand(8, DIM).astype(np.float32)
    return x, y


class TestZero3Memory:
    def test_parity_with_stage0(self):
        x, y = _data()
        traj = {}
        for stage, kw in [(0, {}), (3, {"recompute": True}),
                          (3, {"recompute": True, "offload": True})]:
            step, init = _build(stage, **kw)
            params, st = init()
            losses = []
            for _ in range(3):
                loss, params, st = step(params, st, x, y)
                losses.append(float(loss))
            traj[(stage, tuple(kw))] = losses
        base = traj[(0, ())]
        for k, v in traj.items():
            np.testing.assert_allclose(v, base, rtol=2e-4, atol=1e-6,
                                       err_msg=str(k))

    def test_params_at_rest_sharded(self):
        step, init = _build(3)
        params, _ = init()
        full = DIM * DIM
        for n, p in params.items():
            if p.ndim == 2:
                shard = p.addressable_shards[0].data.size
                assert shard == full // 8, (n, shard)

    def test_fsdp_scan_parity(self):
        import jax.numpy as jnp

        mesh = topology.build_mesh(dp=1, sharding=8)
        topology.set_global_mesh(mesh)
        x, y = _data()
        step0, init0 = _build(0)
        paddle.seed(1)
        m = nn.Sequential(*[nn.Linear(DIM, DIM) for _ in range(N_LAYERS)])
        opt = optimizer.Adam(1e-3, parameters=m.parameters())
        stepf, initf = spmd.build_fsdp_train_step(
            m, lambda o, t: jnp.mean((o - t) ** 2), opt, mesh=mesh)
        p0, s0 = init0()
        pf, sf = initf()
        for _ in range(3):
            l0, p0, s0 = step0(p0, s0, x, y)
            lf, pf, sf = stepf(pf, sf, x, y)
        np.testing.assert_allclose(float(lf), float(l0), rtol=2e-4)
        assert any(n.startswith("trunk.") for n in pf)
        stacked = pf["trunk.weight"]
        assert stacked.shape[0] == N_LAYERS

    def test_peak_transient_below_full_params(self):
        """The FSDP scan trunk must gather ONE layer at a time: peak
        per-device temp memory stays far below the full parameter
        footprint (the r2 implementation gathered everything up front)."""
        import jax
        import jax.numpy as jnp

        mesh = topology.build_mesh(dp=1, sharding=8)
        topology.set_global_mesh(mesh)
        x, y = _data()
        paddle.seed(1)
        m = nn.Sequential(*[nn.Linear(DIM, DIM) for _ in range(N_LAYERS)])
        opt = optimizer.Adam(1e-3, parameters=m.parameters())
        step, init = spmd.build_fsdp_train_step(
            m, lambda o, t: jnp.mean((o - t) ** 2), opt, mesh=mesh)
        params, st = init()
        lowered = step.jitted.lower(params, st, x, y, jax.random.PRNGKey(0),
                                    np.float32(1e-3))
        ma = lowered.compile().memory_analysis()
        if ma is None:
            pytest.skip("backend exposes no memory analysis")
        full_param_bytes = N_LAYERS * (DIM * DIM + DIM) * 4
        assert ma.temp_size_in_bytes < full_param_bytes, (
            f"peak temp {ma.temp_size_in_bytes}B >= full params "
            f"{full_param_bytes}B — the scan is gathering the whole trunk")
        # at rest: sharded args are 1/8 of (params + 2x adam states)
        assert ma.argument_size_in_bytes < full_param_bytes

    def test_offload_state_lives_on_host(self):
        x, y = _data()
        step, init = _build(3, offload=True)
        params, st = init()
        for n, tup in st.items():
            for a in tup:
                if a.ndim:
                    assert a.sharding.memory_kind == _host_kind(), n
        loss, params, st = step(params, st, x, y)
        for n, tup in st.items():
            for a in tup:
                if a.ndim:
                    assert a.sharding.memory_kind == _host_kind(), n

    def test_offload_via_strategy(self):
        import jax.numpy as jnp
        from paddle_tpu.distributed.fleet import DistributedStrategy

        mesh = topology.build_mesh(dp=1, sharding=8)
        topology.set_global_mesh(mesh)
        paddle.seed(1)
        m = nn.Sequential(nn.Linear(DIM, DIM))
        opt = optimizer.Adam(1e-3, parameters=m.parameters())
        s = DistributedStrategy()
        s.sharding = True
        s.sharding_configs = {"stage": 3, "offload": True}
        step, init = spmd.build_train_step(
            m, lambda o, t: jnp.mean((o - t) ** 2), opt, mesh=mesh,
            strategy=s)
        params, st = init()
        a = next(iter(st.values()))[0]
        assert a.sharding.memory_kind == _host_kind()
