"""reference: python/paddle/utils/install_check.py — run_check() trains a
tiny model to prove the install works (the reference fits a linear layer
on 1 then 2 GPUs; here: eager step, jitted step, and a dp-sharded SPMD
step over every visible device)."""
import numpy as np

__all__ = ["run_check"]


def run_check():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed import spmd, topology

    dev = jax.devices()[0]
    print(f"Running verify PaddlePaddle(TPU-native) program on "
          f"{len(jax.devices())} x {dev.platform} ({dev.device_kind}) ...")
    paddle.seed(0)
    rng = np.random.RandomState(0)
    x = rng.rand(16, 8).astype(np.float32)
    w = rng.rand(8, 1).astype(np.float32)
    y = (x @ w).astype(np.float32)

    # 1. eager train step
    net = nn.Linear(8, 1)
    opt = optimizer.SGD(0.1, parameters=net.parameters())
    first = last = None
    for _ in range(10):
        loss = nn.functional.mse_loss(net(paddle.to_tensor(x)),
                                      paddle.to_tensor(y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        last = float(loss.numpy())
        first = last if first is None else first
    assert last < first, "eager training loss did not decrease"

    # 2. compiled (to_static analog) + dp-sharded SPMD step on all devices
    ndev = len(jax.devices())
    mesh = topology.build_mesh(dp=ndev)
    topology.set_global_mesh(mesh)
    net2 = nn.Linear(8, 1)
    opt2 = optimizer.SGD(0.1, parameters=net2.parameters())
    step, init = spmd.build_train_step(
        net2, lambda o, t: ((o - t) ** 2).mean(), opt2, mesh=mesh)
    params, state = init()
    batch = x[: max(ndev * 2, 4)]
    target = y[: max(ndev * 2, 4)]
    loss0 = None
    for _ in range(5):
        loss, params, state = step(params, state, batch, target)
        loss0 = float(loss) if loss0 is None else loss0
    assert float(loss) < loss0, "compiled SPMD loss did not decrease"

    if ndev > 1:
        print(f"PaddlePaddle(TPU-native) works well on {ndev} "
              f"{dev.platform} devices (dp={ndev} mesh).")
    print("PaddlePaddle(TPU-native) is installed successfully! Let's start "
          "deep learning with PaddlePaddle(TPU-native) now.")
