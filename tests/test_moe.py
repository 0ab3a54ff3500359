"""MoE + expert parallelism over the 'ep' mesh axis."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed import spmd, topology
from paddle_tpu.incubate.moe import MoELayer


class TestMoELayer:
    def test_topk_gating_math(self):
        """With a forced one-hot gate, MoE output equals that single
        expert's FFN."""
        import jax
        import jax.numpy as jnp

        paddle.seed(0)
        moe = MoELayer(8, 16, num_experts=4, top_k=1)
        # rig the gate toward expert 2
        gw = np.zeros((8, 4), np.float32)
        gw[:, 2] = 5.0
        moe.gate.weight.set_value(gw)
        moe.gate.bias.set_value(np.array([0, 0, 50.0, 0], np.float32))
        x = np.random.RandomState(0).rand(2, 3, 8).astype(np.float32)
        out = np.asarray(moe(paddle.to_tensor(x))._value)
        w_up = np.asarray(moe.w_up._value)[2]
        w_down = np.asarray(moe.w_down._value)[2]
        ref = np.asarray(jax.nn.gelu(jnp.asarray(x @ w_up))) @ w_down
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
        assert moe.aux_loss is not None

    def test_trains_with_ep_sharding(self):
        import jax.numpy as jnp

        mesh = topology.build_mesh(dp=2, ep=4)
        topology.set_global_mesh(mesh)
        paddle.seed(1)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.inp = nn.Linear(8, 8)
                self.moe = MoELayer(8, 16, num_experts=4, top_k=2)
                self.out = nn.Linear(8, 4)

            def forward(self, x):
                h = self.inp(x)
                h = h + self.moe(h)
                return self.out(h)

        net = Net()
        opt = optimizer.Adam(5e-3, parameters=net.parameters())

        def loss_fn(out, y):
            return jnp.mean((out - y) ** 2)

        step, init = spmd.build_train_step(net, loss_fn, opt, mesh=mesh)
        params, st = init()
        # expert weights sharded over ep
        w = params["moe.w_up"]
        assert w.sharding.spec == spmd.P("ep")
        assert w.addressable_shards[0].data.shape[0] == 1  # 4 experts / 4
        x = np.random.RandomState(0).rand(8, 3, 8).astype(np.float32)
        y = np.random.RandomState(1).rand(8, 3, 4).astype(np.float32)
        losses = []
        for _ in range(12):
            loss, params, st = step(params, st, x, y)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, losses[::4]

    def test_ep_matches_single_device(self):
        """ep-sharded training == unsharded training (expert-parallel
        parity, the dp-vs-single oracle applied to 'ep')."""
        import jax.numpy as jnp

        def build_and_train(ep):
            import jax

            mesh = topology.build_mesh(dp=1, ep=ep,
                                       devices=jax.devices()[:ep])
            topology.set_global_mesh(mesh)
            paddle.seed(3)
            net = MoELayer(8, 16, num_experts=4, top_k=2)
            opt = optimizer.SGD(0.1, parameters=net.parameters())
            step, init = spmd.build_train_step(
                net, lambda o, t: jnp.mean((o - t) ** 2), opt, mesh=mesh)
            params, st = init()
            x = np.random.RandomState(0).rand(4, 3, 8).astype(np.float32)
            y = np.random.RandomState(1).rand(4, 3, 8).astype(np.float32)
            out = []
            for _ in range(3):
                loss, params, st = step(params, st, x, y)
                out.append(float(loss))
            return out

        ref = build_and_train(1)
        ep4 = build_and_train(4)
        np.testing.assert_allclose(ep4, ref, rtol=2e-5, atol=1e-7)


class TestMoEReviewRegressions:
    def test_uniform_probs_select_exactly_topk(self):
        import jax
        import jax.numpy as jnp

        paddle.seed(0)
        moe = MoELayer(8, 16, num_experts=4, top_k=1)
        moe.gate.weight.set_value(np.zeros((8, 4), np.float32))
        moe.gate.bias.set_value(np.zeros(4, np.float32))
        x = np.zeros((1, 1, 8), np.float32)  # padding token, uniform gate
        out = np.asarray(moe(paddle.to_tensor(x))._value)
        # exactly ONE expert (index 0 wins ties), gate weight renorms to 1
        w_up = np.asarray(moe.w_up._value)[0]
        w_down = np.asarray(moe.w_down._value)[0]
        ref = np.asarray(jax.nn.gelu(jnp.asarray(x @ w_up))) @ w_down
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_aux_loss_joins_compiled_objective_and_leaves_no_tracer(self):
        import jax.numpy as jnp

        mesh = topology.build_mesh(dp=1, ep=4)
        topology.set_global_mesh(mesh)
        paddle.seed(2)
        moe = MoELayer(8, 16, num_experts=4, top_k=2, aux_weight=0.5)
        opt = optimizer.SGD(0.1, parameters=moe.parameters())
        step, init = spmd.build_train_step(
            moe, lambda o, t: jnp.mean((o - t) ** 2), opt, mesh=mesh)
        params, st = init()
        x = np.random.RandomState(0).rand(4, 3, 8).astype(np.float32)
        loss_w, _, _ = step(params, st, x, x)
        # aux cleared: no leaked tracer on the layer
        assert moe.aux_loss is None
        # aux actually contributes: same model with aux_weight=0 gives a
        # strictly smaller compiled loss
        paddle.seed(2)
        moe0 = MoELayer(8, 16, num_experts=4, top_k=2, aux_weight=0.0)
        opt0 = optimizer.SGD(0.1, parameters=moe0.parameters())
        step0, init0 = spmd.build_train_step(
            moe0, lambda o, t: jnp.mean((o - t) ** 2), opt0, mesh=mesh)
        p0, s0 = init0()
        loss_0, _, _ = step0(p0, s0, x, x)
        assert float(loss_w) > float(loss_0) + 1e-4, (float(loss_w),
                                                      float(loss_0))


class TestAuxLossRouting:
    """emit_aux_loss context routing (regression: traced aux_loss tracers
    must never escape onto the mutable Layer)."""

    def test_eager_stores_concrete_value(self):
        paddle.seed(0)
        moe = MoELayer(8, 16, num_experts=4, top_k=2)
        x = paddle.to_tensor(np.random.RandomState(0)
                             .rand(2, 3, 8).astype(np.float32))
        moe(x)
        assert moe.aux_loss is not None
        assert float(moe.aux_loss.numpy()) >= 0.0

    def test_inference_trace_leaves_no_tracer(self):
        import jax

        paddle.seed(0)
        moe = MoELayer(8, 16, num_experts=4, top_k=2)
        moe.eval()
        from paddle_tpu.core import dispatch
        from paddle_tpu.core.tensor import Tensor

        params, _ = moe.functional_state()
        names = list(params)

        def fwd(plist, x):
            saved = {n: p._value for n, p in moe.named_parameters()}
            try:
                with dispatch.trace_mode():
                    moe.load_functional_state(dict(zip(names, plist)))
                    return moe(Tensor(x, stop_gradient=True))._value
            finally:
                moe.load_functional_state(saved)

        x = np.random.RandomState(0).rand(2, 3, 8).astype(np.float32)
        jax.make_jaxpr(fwd)([params[n] for n in names], x)
        # a bare trace drops the aux loss instead of leaking a tracer
        assert moe.aux_loss is None
        moe(paddle.to_tensor(x))  # and eager use afterwards still works

    def test_direct_assignment_contract_still_collected(self):
        """Layers that set self.aux_loss directly (without emit_aux_loss)
        keep working: the term joins the compiled loss and no tracer
        stays on the layer (regression for the collector refactor)."""
        import jax.numpy as jnp
        from paddle_tpu.distributed import spmd, topology

        class DirectAux(paddle.nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = paddle.nn.Linear(4, 4)
                self.aux_loss = None

            def forward(self, x):
                out = self.fc(x)
                self.aux_loss = (out * out).mean() * 0.1
                return out

        mesh = topology.build_mesh(dp=1)
        topology.set_global_mesh(mesh)
        paddle.seed(3)
        net = DirectAux()
        opt = optimizer.SGD(0.0, parameters=net.parameters())  # lr 0: pure read
        step, init = spmd.build_train_step(
            net, lambda o, t: jnp.mean((o - t) ** 2), opt, mesh=mesh)
        params, st = init()
        x = np.random.RandomState(0).rand(8, 4).astype(np.float32)
        loss, _, _ = step(params, st, x, np.zeros_like(x))
        assert net.aux_loss is None  # cleared, no escaped tracer
        # compare against the same model run eagerly: loss must include aux
        out = net(paddle.to_tensor(x))
        base = float(((out - paddle.to_tensor(np.zeros_like(x))) ** 2)
                     .mean().numpy())
        aux = float(net.aux_loss.numpy())
        np.testing.assert_allclose(float(loss), base + aux, rtol=1e-5)


class TestCapacityDispatch:
    """GShard capacity-factor sparse dispatch (green-field; matches the
    GShard top-2 formulation: per-expert capacity C, drop-overflow)."""

    def _twins(self, cf):
        paddle.seed(9)
        cap = MoELayer(8, 16, num_experts=8, top_k=2, capacity_factor=cf,
                       dispatch_mode="capacity")
        dense = MoELayer(8, 16, num_experts=8, top_k=2,
                         dispatch_mode="dense")
        dense.set_state_dict(cap.state_dict())
        return cap, dense

    def test_auto_mode_resolves_from_experts_and_mesh(self):
        """``auto`` is decided by what the layer can observe: below 8
        experts dense; from 8 on the dropless sorted path where no 'ep'
        axis shards the experts, capacity where one does."""
        auto = MoELayer(8, 16, num_experts=8, top_k=2)
        assert auto.dispatch_mode == "auto"
        assert auto.resolved_mode() == "sorted"
        assert MoELayer(8, 16, num_experts=4).resolved_mode() == "dense"
        with topology.tracing_for(topology.build_mesh(dp=2, ep=4)):
            assert auto.resolved_mode() == "capacity"
        with topology.tracing_for(topology.build_mesh(dp=8)):
            assert auto.resolved_mode() == "sorted"
        cap, _ = self._twins(2.0)
        assert cap.resolved_mode() == "capacity"

    def test_matches_dense_when_nothing_drops(self):
        cap, dense = self._twins(8.0)  # C >= N: no token can overflow
        x = np.random.RandomState(0).rand(2, 6, 8).astype(np.float32)
        o_cap = np.asarray(cap(paddle.to_tensor(x))._value)
        o_dense = np.asarray(dense(paddle.to_tensor(x))._value)
        np.testing.assert_allclose(o_cap, o_dense, rtol=1e-4, atol=1e-5)

    def test_tight_capacity_drops_overflow(self):
        cap, dense = self._twins(0.1)  # C=1: most tokens overflow
        x = np.random.RandomState(0).rand(2, 6, 8).astype(np.float32)
        o_t = np.asarray(cap(paddle.to_tensor(x))._value)
        o_d = np.asarray(dense(paddle.to_tensor(x))._value)
        assert np.isfinite(o_t).all()
        assert np.abs(o_t).sum() < np.abs(o_d).sum()

    def test_trains_ep_sharded_and_hlo_has_expert_collective(self):
        import jax
        import jax.numpy as jnp

        mesh = topology.build_mesh(dp=2, ep=4)
        topology.set_global_mesh(mesh)
        paddle.seed(10)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.moe = MoELayer(8, 16, num_experts=8, top_k=2,
                                    dispatch_mode="capacity",
                                    capacity_factor=2.0)

            def forward(self, x):
                return x + self.moe(x)

        net = Net()
        opt = optimizer.Adam(5e-3, parameters=net.parameters())
        step, init = spmd.build_train_step(
            net, lambda o, t: jnp.mean((o - t) ** 2), opt, mesh=mesh)
        params, st = init()
        assert params["moe.w_up"].sharding.spec == spmd.P("ep")
        x = np.random.RandomState(0).rand(8, 4, 8).astype(np.float32)
        y = np.random.RandomState(1).rand(8, 4, 8).astype(np.float32)
        losses = []
        for _ in range(12):
            loss, params, st = step(params, st, x, y)
            losses.append(float(loss))
        # random targets + residual path: modest but monotone progress
        assert losses[-1] < losses[0] * 0.85, losses[::4]
        # the compiled step must move tokens across the ep axis (XLA
        # picks the shuffle primitive for the einsum formulation)
        import re

        text = step.jitted.lower(params, st, {}, x, y,
                                 jax.random.PRNGKey(0),
                                 5e-3).compile().as_text()
        colls = re.findall(r"all-to-all|all-reduce|collective-permute|"
                           r"all-gather|reduce-scatter", text)
        assert colls, "no cross-partition collective in the MoE step"

    def test_alltoall_mode_parity_and_hlo(self):
        """Explicit GShard a2a dispatch: parity with dense when nothing
        drops + literal all-to-all ops in the compiled train step."""
        import re

        import jax
        import jax.numpy as jnp

        mesh = topology.build_mesh(dp=1, ep=4,
                                   devices=jax.devices()[:4])
        topology.set_global_mesh(mesh)
        paddle.seed(3)
        a2a = MoELayer(8, 16, num_experts=8, top_k=2,
                       dispatch_mode="alltoall", capacity_factor=8.0)
        dense = MoELayer(8, 16, num_experts=8, top_k=2,
                         dispatch_mode="dense")
        dense.set_state_dict(a2a.state_dict())
        x = np.random.RandomState(0).rand(4, 6, 8).astype(np.float32)
        o_a = np.asarray(a2a(paddle.to_tensor(x))._value)
        o_d = np.asarray(dense(paddle.to_tensor(x))._value)
        np.testing.assert_allclose(o_a, o_d, rtol=1e-4, atol=1e-5)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.moe = a2a

            def forward(self, x):
                return x + self.moe(x)

        net = Net()
        opt = optimizer.Adam(5e-3, parameters=net.parameters())
        step, init = spmd.build_train_step(
            net, lambda o, t: jnp.mean((o - t) ** 2), opt, mesh=mesh)
        params, st = init()
        text = step.jitted.lower(params, st, {}, x, x,
                                 jax.random.PRNGKey(0),
                                 5e-3).compile().as_text()
        assert re.search(r"all-to-all", text), \
            "a2a mode must compile to literal all-to-all collectives"
        loss, params, st = step(params, st, x, x)
        assert np.isfinite(float(loss))

    def test_alltoall_rejects_bad_config(self):
        import jax

        mesh = topology.build_mesh(dp=1, ep=4,
                                   devices=jax.devices()[:4])
        topology.set_global_mesh(mesh)
        paddle.seed(4)
        moe = MoELayer(8, 16, num_experts=6, top_k=2,
                       dispatch_mode="alltoall")
        x = paddle.to_tensor(
            np.random.RandomState(0).rand(4, 2, 8).astype(np.float32))
        with pytest.raises(ValueError, match="divide"):
            moe(x)
        moe8 = MoELayer(8, 16, num_experts=8, top_k=2,
                        dispatch_mode="alltoall")
        bad_batch = paddle.to_tensor(
            np.random.RandomState(0).rand(3, 2, 8).astype(np.float32))
        with pytest.raises(ValueError, match="divisible"):
            moe8(bad_batch)


#: an expert gemm's operand against the grouped matmul's tile (bf16: 1,024;
#: float32: 512): narrower (JoyAI's 768, Qwen3-Next's 512), the tile, a
#: multiple of it (2,048), and wider without being one — LFM2's 1,792 = 7 x
#: 256 and Kimi-Linear's 2,304 = 9 x 256
GMM_OPERANDS = (512, 768, 1024, 1792, 2048, 2304)


@pytest.mark.parametrize("itemsize", [2, 4], ids=["bf16", "f32"])
@pytest.mark.parametrize("operand", GMM_OPERANDS)
def test_gmm_tiling_divides_every_operand(operand, itemsize):
    """The grouped matmul's tiles, forward ([2048 -> operand], [operand ->
    2048]) and as the backward's calls see them (k and n swapped): every
    answer divides its operand — no tile is masked or part empty —, is the
    operand itself or a multiple of the 128 lanes, and never passes the
    table's tile; the row tile divides the rows."""
    from paddle_tpu.incubate.moe import _GMM_TILING, _gmm_tiling

    rows, hidden = 65536, 2048
    table = _GMM_TILING[itemsize]
    for k, n in ((hidden, operand), (operand, hidden)):
        tiling = _gmm_tiling(rows, itemsize, k, n)
        calls = ((rows, k, n), (rows, n, k))   # forward, the backward's swap
        answers = [tiling if isinstance(tiling, tuple) else tiling(*call)
                   for call in calls]
        for (m, kk, nn_), (tm, tk, tn) in zip(calls, answers):
            assert m % tm == 0 and tm == table[0]
            for size, tile, limit in ((kk, tk, table[1]), (nn_, tn, table[2])):
                assert size % tile == 0, (size, tile)
                assert tile <= limit and (tile == size or tile % 128 == 0)
    if itemsize == 2:
        want = {512: 512, 768: 768, 1024: 1024, 1792: 896, 2048: 1024,
                2304: 768}[operand]
        tiling = _gmm_tiling(rows, 2, hidden, operand)
        got = tiling if isinstance(tiling, tuple) else tiling(
            rows, hidden, operand)
        assert got == (512, 1024, want)


def test_gmm_tiling_keeps_the_table_for_what_no_lane_multiple_divides():
    """An operand wider than the tile that no multiple of 128 divides keeps
    the table's tile (the kernel masks its last one); rows that no row tile
    divides have no tiling, and ``ragged_dot`` serves."""
    from paddle_tpu.incubate.moe import _gmm_tiling, _operand_tile

    assert _operand_tile(1024, 1500) == 1024
    assert _operand_tile(1024, 1792) == 896 and _operand_tile(512, 1792) == 256
    assert _gmm_tiling(65536, 2, 2048, 1500)(65536, 2048, 1500) == (
        512, 1024, 1024)
    assert _gmm_tiling(65531, 2, 2048, 1792) is None
    assert _gmm_tiling(65536, 1, 2048, 1792) is None


def test_grouped_matmul_at_a_width_of_seven_quarter_tiles_is_exact():
    """[256 -> 1792] and [1792 -> 256] through the megablox kernel (the
    Pallas interpreter) at the divisor tiles against ``ragged_dot``, with
    an empty group and an uneven one, output and both gradients."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.moe import _grouped_matmul

    rng = np.random.default_rng(44)
    sizes = jnp.asarray([200, 0, 312, 512], jnp.int32)
    for k, n in ((256, 1792), (1792, 256)):
        x = jnp.asarray(rng.standard_normal((1024, k)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((4, k, n)) * 0.05, jnp.float32)

        def loss(x, w, kernel):
            return jnp.sum(_grouped_matmul(x, w, sizes, kernel) ** 2)

        got = jax.value_and_grad(loss, (0, 1))(x, w, "interpret")
        want = jax.value_and_grad(loss, (0, 1))(x, w, None)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for g, wnt in zip(got[1], want[1]):
            assert float(jnp.abs(g - wnt).max()) <= 1e-5 * float(
                jnp.abs(wnt).max())
