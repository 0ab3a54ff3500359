#!/usr/bin/env python3
"""The granite-4.0-h-micro configuration's reference check alone, at
published widths on the chip, over a few seeds, with the readings that set
its tolerances (PERF.md section 6, PR 47): ``configs/granite-4.0-h-micro.py
check_train`` (float32 at logit level over the whole model, amp O1 block by
block, the loss both ways; one row of 8,192 tokens, the model in pieces),
and the same float32 reference computed at the TPU's DEFAULT matmul
precision, which has to come out as not correct by the float32 logits'
bound. Exits 2 without a TPU, 1 if a seed is not correct or the
lower-precision reference passes.

    chiprun -- python3 benchmark/tools/granite_check.py [first-seed] [seeds]

With ``--gate-after-norm``, ``--no-conv-bias`` or ``--scale-eighth``
instead: the PROGRAM broken that way — a mamba block's gate applied after
its norm (the order the repo's other gated norms have), the convolution's
bias left out, or the attention block's scores at 64 ** -0.5 = 1/8 for the
published 1/64 — checked against the reference as it is. The block-by-block
half has to fail on the blocks of that kind and the float32 half with it.
Exits 1 if that program comes out correct.

    chiprun -- python3 benchmark/tools/granite_check.py --no-conv-bias [seed]
"""
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402
from benchmark.harness.datasets import SeededDataset, field_shapes  # noqa: E402

CONFIG, TRAFFIC = "granite-4.0-h-micro", "lm-s8192-b1-ssm"
#: how the program is broken -> the kind of block that has to fail
BROKEN = {"--gate-after-norm": "mamba", "--no-conv-bias": "mamba",
          "--scale-eighth": "attention"}


def probe_rows(traffic, sizes, seed, rows=1):
    """The first ``rows`` rows of the seed's first batch, on the chip."""
    import jax
    import numpy as np

    data = SeededDataset(traffic, sizes, seed, rows)
    return jax.device_put(np.stack([data[i][0] for i in range(rows)]),
                          jax.devices()[0])


@contextlib.contextmanager
def broken_program(how, built):
    """``built``'s program broken as ``how`` says, for the block's length."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.text import models

    lm = built["layer"].lm
    saved = {n: getattr(models, n)
             for n in ("_mamba_gated_norm", "_mamba_streams")}
    scales = [(layer.self_attn, layer.self_attn.attention_multiplier)
              for layer in lm.layers if layer.is_attention]

    def gate_after_norm(y, z, w, *, eps):
        f32 = jnp.float32
        yf = y.astype(f32)
        normed = yf * jax.lax.rsqrt(jnp.mean(yf * yf, -1, keepdims=True)
                                    + eps) * w.astype(f32)
        return (normed * jax.nn.silu(z.astype(f32))).astype(y.dtype)

    def no_conv_bias(xbc, w, bias, **kw):
        return saved["_mamba_streams"](xbc, w, jnp.zeros_like(bias), **kw)

    try:
        if how == "--gate-after-norm":
            models._mamba_gated_norm = gate_after_norm
        elif how == "--no-conv-bias":
            models._mamba_streams = no_conv_bias
        else:
            for attn, _ in scales:
                attn.attention_multiplier = attn.head_dim ** -0.5
        yield
    finally:
        for name, fn in saved.items():
            setattr(models, name, fn)
        for attn, scale in scales:
            attn.attention_multiplier = scale


def load():
    bench = cells.load_benchmark()
    sizes = cells.config_sizes(bench, CONFIG)
    traffic = cells.load_json("traffic", TRAFFIC)
    return (sizes, cells.load_module("configs", CONFIG),
            cells.load_module("references", CONFIG), traffic,
            field_shapes(traffic))


def broken(how, seed):
    sizes, config, reference, traffic, shapes = load()
    built = config.build_train(seed, sizes, shapes)
    x = probe_rows(traffic, sizes, seed)
    with broken_program(how, built):
        check = config.check_train(built, reference, sizes, shapes, x)
    kinds = config.layer_types(sizes)
    keep = ("ok", "amp_rel_err", "amp_rtol", "amp_block_worst",
            "amp_block_medians", "f32_rel_err", "f32_rtol",
            "f32_rel_err_median")
    print(json.dumps({"seed": seed, "program": how[2:], "layer_types": kinds,
                      **{k: check[k] for k in keep}}), flush=True)
    # every block of the broken kind over the bound, no other
    over = [worst > check["amp_rtol"] for worst in check["amp_block_worst"]]
    fails = (not check["ok"] and check["f32_rel_err"] > check["f32_rtol"]
             and over == [kind == BROKEN[how] for kind in kinds])
    return 0 if fails else 1


def default_precision(config, reference, sizes, built, x):
    """The reference itself one precision down (the platform's default)
    against the reference at "highest", by the check's own rule."""
    import numpy as np

    weights = config.Weights(built["layer"], next(iter(x.devices())))
    exact, lower = config.in_pieces(
        [config.reference_pieces(reference, sizes),
         config.reference_pieces(reference, sizes, None)],
        weights, config.layer_types(sizes), x)
    errs = config.token_errors(exact[0], lower[0])
    below = {"logits_rel_err": float(errs.max()),
             "logits_rel_err_median": float(np.median(errs)),
             "loss_rel_err": abs(float(lower[1]) - float(exact[1]))
             / abs(float(exact[1]))}
    below["fails_f32_logits"] = below["logits_rel_err"] > config.F32_RTOL
    below["fails_f32_loss"] = below["loss_rel_err"] > config.LOSS_F32_RTOL
    return below


def main(first, seeds):
    sizes, config, reference, traffic, shapes = load()
    ok = True
    for seed in range(first, first + seeds):
        built = config.build_train(seed, sizes, shapes)
        x = probe_rows(traffic, sizes, seed)
        check = config.check_train(built, reference, sizes, shapes, x)
        below = default_precision(config, reference, sizes, built, x)
        print(json.dumps({"seed": seed, "check": check,
                          "reference_default_precision": below}), flush=True)
        ok = ok and check["ok"] and below["fails_f32_logits"]
        del built
    return 0 if ok else 1


if __name__ == "__main__":
    import jax

    if jax.devices()[0].platform != "tpu":
        print("granite_check.py reads the chip's arithmetic: no TPU",
              file=sys.stderr)
        sys.exit(2)
    from paddle_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = sys.argv[1:]
    if args[:1] and args[0] in BROKEN:
        sys.exit(broken(args[0], int(args[1]) if len(args) > 1
                        else 2147483301))
    sys.exit(main(int(args[0]) if args else 2147483301,
                  int(args[1]) if len(args) > 1 else 2))
