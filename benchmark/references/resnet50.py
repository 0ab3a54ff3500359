"""Plain float32 forward of ResNet-50 (He et al. 2015, bottleneck blocks
[3, 4, 6, 3]; v1.5 stride placement as torchvision has it: the stride-2
convolution of a downsampling block is the 3x3), eval mode, in
straightforward ``jax.numpy``/``lax`` with no framework. Weights and
batch-norm statistics come as dicts under the framework's names;
convolution weights are OIHW, images NCHW, the classifier's weight is
[in, out].
"""
import jax
import jax.numpy as jnp

BN_EPS = 1e-5
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


def normalise(images_u8):
    """uint8 NCHW -> float32, scaled to [0, 1], ImageNet mean/std."""
    x = images_u8.astype(jnp.float32) / 255.0
    mean = jnp.asarray(IMAGENET_MEAN, jnp.float32).reshape(1, 3, 1, 1)
    std = jnp.asarray(IMAGENET_STD, jnp.float32).reshape(1, 3, 1, 1)
    return (x - mean) / std


def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bn(x, w, b, name):
    def c(a):
        return a.reshape(1, -1, 1, 1)

    return ((x - c(b[name + "._mean"])) / jnp.sqrt(c(b[name + "._variance"])
                                                   + BN_EPS)
            * c(w[name + ".weight"]) + c(w[name + ".bias"]))


def forward(w, b, images_u8, sizes):
    """Logits [n, num_classes] of uint8 images [n, 3, 224, 224]; ``w`` the
    parameters, ``b`` the batch-norm running statistics."""
    with jax.default_matmul_precision("highest"):
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        b = {k: jnp.asarray(v, jnp.float32) for k, v in b.items()}
        x = normalise(images_u8)
        x = jax.nn.relu(_bn(_conv(x, w["conv1.weight"], 2, 3), w, b, "bn1"))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
            [(0, 0), (0, 0), (1, 1), (1, 1)])
        for stage, blocks in enumerate(sizes["block_counts"], start=1):
            for blk in range(blocks):
                p = f"layer{stage}.{blk}."
                stride = 2 if (blk == 0 and stage > 1) else 1
                out = jax.nn.relu(_bn(_conv(x, w[p + "conv1.weight"], 1, 0),
                                      w, b, p + "bn1"))
                out = jax.nn.relu(_bn(_conv(out, w[p + "conv2.weight"],
                                            stride, 1), w, b, p + "bn2"))
                out = _bn(_conv(out, w[p + "conv3.weight"], 1, 0),
                          w, b, p + "bn3")
                if blk == 0:
                    x = _bn(_conv(x, w[p + "downsample.0.weight"], stride, 0),
                            w, b, p + "downsample.1")
                x = jax.nn.relu(out + x)
        x = jnp.mean(x, axis=(2, 3))
        return x @ w["fc.weight"] + w["fc.bias"]
