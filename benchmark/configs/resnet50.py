"""resnet50: He et al. 2015 at the published sizes (``resnet50.json``;
nothing is reduced), built with ``vision.models.resnet50``. Images arrive
as uint8 and are cast and normalised on the device, inside the wrapper."""
import jax
import jax.numpy as jnp
import numpy as np

# framework f32 (precision "highest") against the float32 reference, as a
# share of the largest reference logit: the same 53 convolutions in
# float32, differing in summation order only. Observed on the v5e (PR 22):
# 2.3e-7. A bf16 computation (3.9e-3 per rounding; the amp forward below is
# off by 4.6e-3) misses this by more than two orders.
F32_RTOL = 2e-5
# amp O1: bf16 convolution inputs with f32 accumulation through 53
# convolutions; with eval-mode batch norm at its initial statistics the
# activations are not renormalised, so rounding errors are carried to the
# logits unchanged. Observed on the v5e (PR 22, 21 seeds): 3.7e-3 to
# 4.7e-3. The tolerance is three times the largest of them: this is the
# only path the window runs, so a convolution path of lower precision gets
# little room to pass as correct (5e-2, ten times the observed, gave it a lot).
AMP_RTOL = 1.5e-2

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)

#: toy sizes for the CPU rehearsal in benchmark/tests (never a cell)
TOY = {"num_classes": 10, "image_size": 32}


def ce_loss(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def build_train(seed, sizes, shapes):
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.vision.models import resnet50

    mean = np.asarray(IMAGENET_MEAN, np.float32).reshape(1, 3, 1, 1)
    std = np.asarray(IMAGENET_STD, np.float32).reshape(1, 3, 1, 1)

    class FromUint8(nn.Layer):
        """uint8 NCHW images -> float32 in [0, 1] -> ImageNet mean/std ->
        the network. On the device: the host sends 1 byte a pixel."""

        def __init__(self, net):
            super().__init__()
            self.net = net

        def forward(self, images):
            x = paddle.cast(images, "float32") / 255.0
            x = (x - paddle.to_tensor(mean)) / paddle.to_tensor(std)
            return self.net(x)

    paddle.seed(seed)
    net = resnet50(num_classes=sizes["num_classes"])
    opt = optimizer.Momentum(0.1, momentum=0.9, parameters=net.parameters(),
                             weight_decay=1e-4)
    return {"layer": FromUint8(net), "loss_fn": ce_loss, "optimizer": opt,
            "amp_level": "O1"}


def check_train(built, reference, sizes, shapes, probe_x):
    """Eval-mode logits of the framework model on the first 8 probe images
    against ``references/resnet50.py``."""
    from benchmark.harness.framework import compare_with_reference

    def ref_fn(params, buffers, images):
        strip = len("net.")
        return reference.forward({k[strip:]: v for k, v in params.items()},
                                 {k[strip:]: v for k, v in buffers.items()},
                                 images, sizes)

    return compare_with_reference(built["layer"], ref_fn, probe_x[:8],
                                  built["amp_level"], F32_RTOL, AMP_RTOL)


def _conv_macs(cin, cout, k, hw_out):
    return cin * cout * k * k * hw_out * hw_out


def forward_macs(sizes, image_size):
    """Multiply-adds of one image's forward pass, from shapes: every
    convolution and the classifier (batch norm, ReLU and pooling are
    elementwise and not counted)."""
    hw = image_size // 2                       # 7x7 stem, stride 2
    macs = _conv_macs(sizes["image_channels"], sizes["stem_width"], 7, hw)
    hw //= 2                                   # 3x3 max pool, stride 2
    cin = sizes["stem_width"]
    for stage, (blocks, mid, out) in enumerate(zip(
            sizes["block_counts"], sizes["bottleneck_widths"],
            sizes["stage_widths"])):
        for blk in range(blocks):
            stride = 2 if (blk == 0 and stage > 0) else 1
            hw_out = hw // stride
            macs += _conv_macs(cin, mid, 1, hw)          # 1x1 at input size
            macs += _conv_macs(mid, mid, 3, hw_out)      # 3x3, v1.5 stride
            macs += _conv_macs(mid, out, 1, hw_out)      # 1x1
            if blk == 0:
                macs += _conv_macs(cin, out, 1, hw_out)  # projection
            cin, hw = out, hw_out
    return macs + cin * sizes["num_classes"]


def flops_per_sample(sizes, shapes):
    """Floating-point operations one image needs in a train step: 2 per
    multiply-add of the forward pass, times 3 for forward + backward.
    Nothing is recomputed."""
    return 3.0 * 2.0 * forward_macs(sizes, shapes["image"][-1])
