#!/usr/bin/env python3
"""The LFM2-8B-A1B configuration's reference check alone, at published
widths on the chip, over a few seeds, with the readings that set its
tolerances (PERF.md section 6, PR 44): ``configs/lfm2-8b-a1b.py
check_train`` (float32 at logit level over the whole model, amp O1 block by
block, the loss, the overflow count and the pairs that landed on the held
experts a layer; ``CHECK_ROWS`` = 2 rows of 8,192 tokens, so that a row
boundary lies inside), and the same float32 reference computed at the TPU's
DEFAULT matmul precision, which has to come out as not correct by the
float32 logits' bound. It is ``kimi_check.py``'s procedure on this
configuration, its traffic and its rows. Exits 2 without a TPU, 1 if a seed
is not correct or the lower-precision reference passes.

    chiprun -- python3 benchmark/tools/lfm2_check.py [first-seed] [seeds]

With ``--taps-shifted``, ``--gates-swapped`` or ``--rows-joined`` instead:
the PROGRAM's short-convolution stage broken that way — the taps moved by
one token, B and C swapped, or every row fed the row before it as history
(the rows convolved as one) — checked against the reference as it is. The
block-by-block half has to fail on the conv blocks (``--rows-joined``: at
the second row's first two tokens alone) and the float32 half with it.
Exits 1 if that program comes out correct.

    chiprun -- python3 benchmark/tools/lfm2_check.py --rows-joined [seed]
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402
from benchmark.harness.datasets import SeededDataset, field_shapes  # noqa: E402

CONFIG, TRAFFIC = "lfm2-8b-a1b", "lm-s8192-b4-conv"
BROKEN = ("--taps-shifted", "--gates-swapped", "--rows-joined")


def probe_rows(traffic, sizes, seed, rows):
    """The first ``rows`` rows of the seed's first batch, on the chip."""
    import jax
    import numpy as np

    data = SeededDataset(traffic, sizes, seed, rows)
    return jax.device_put(np.stack([data[i][0] for i in range(rows)]),
                          jax.devices()[0])


def broken_stage(how):
    """``ops.linear_attention.gated_short_conv`` broken as ``how`` says."""
    import jax.numpy as jnp
    from paddle_tpu.ops import linear_attention

    stage = linear_attention.gated_short_conv

    def taps_shifted(bcu, w):
        return stage(bcu, jnp.roll(w, 1, axis=0))

    def gates_swapped(bcu, w):
        c = w.shape[1]
        return stage(jnp.concatenate(
            [bcu[..., c:2 * c], bcu[..., :c], bcu[..., 2 * c:]], axis=-1), w)

    def rows_joined(bcu, w):
        b, t, c3 = bcu.shape
        return stage(bcu.reshape(1, b * t, c3), w).reshape(b, t, c3 // 3)

    return {"--taps-shifted": taps_shifted, "--gates-swapped": gates_swapped,
            "--rows-joined": rows_joined}[how]


def load():
    bench = cells.load_benchmark()
    sizes = cells.config_sizes(bench, CONFIG)
    traffic = cells.load_json("traffic", TRAFFIC)
    return (sizes, cells.load_module("configs", CONFIG),
            cells.load_module("references", CONFIG), traffic,
            field_shapes(traffic))


def broken(how, seed):
    from paddle_tpu.ops import linear_attention

    sizes, config, reference, traffic, shapes = load()
    built = config.build_train(seed, sizes, shapes)
    x = probe_rows(traffic, sizes, seed, config.CHECK_ROWS)
    stage = linear_attention.gated_short_conv
    linear_attention.gated_short_conv = broken_stage(how)
    try:
        check = config.check_train(built, reference, sizes, shapes, x)
    finally:
        linear_attention.gated_short_conv = stage
    keep = ("ok", "amp_rel_err", "amp_rtol", "amp_block_medians",
            "f32_rel_err", "f32_rtol", "f32_rel_err_median",
            "f32_rel_err_all_tokens", "f32_compared_share")
    print(json.dumps({"seed": seed, "program_stage": how[2:],
                      "layer_types": config.layer_types(sizes),
                      **{k: check[k] for k in keep}}), flush=True)
    fails = not check["ok"] and check["amp_rel_err"] > check["amp_rtol"]
    return 0 if fails else 1


def main(first, seeds):
    import jax
    import numpy as np

    sizes, config, reference, traffic, shapes = load()
    device = jax.devices()[0]
    ok = True
    for seed in range(first, first + seeds):
        built = config.build_train(seed, sizes, shapes)
        x = probe_rows(traffic, sizes, seed, config.CHECK_ROWS)
        check = config.check_train(built, reference, sizes, shapes, x)

        # the reference itself one precision down: the platform's default
        params, buffers = jax.device_put(
            built["layer"].functional_state(), device)
        exact = jax.device_get(jax.jit(config.reference_outputs(
            reference, sizes))(params, buffers, x))
        lower = jax.device_get(jax.jit(config.reference_outputs(
            reference, sizes, None))(params, buffers, x))
        margin = np.asarray(exact[4])
        errs = config.token_errors(exact[0], lower[0])
        # by the check's own rule: the decided tokens of the clean prefix
        decided = margin >= config.F32_MARGIN
        compared = decided & config.clean_prefix(errs, decided,
                                                 config.F32_RTOL)
        below = {
            "logits_rel_err": float(errs[compared].max()
                                    if compared.any() else np.inf),
            "logits_rel_err_decided": float(errs[decided].max()),
            "compared_share": float(compared.mean()),
            "logits_rel_err_median": float(np.median(errs)),
            "loss_rel_err": abs(float(lower[1]) - float(exact[1]))
            / abs(float(exact[1])),
            "margin_shift_max": float(np.abs(
                np.asarray(lower[4]) - margin).max())}
        below["fails_f32_logits"] = bool(
            below["logits_rel_err"] > config.F32_RTOL
            or below["logits_rel_err_median"] > config.F32_RTOL)
        below["fails_f32_loss"] = below["loss_rel_err"] > config.LOSS_F32_RTOL
        print(json.dumps({"seed": seed, "rows": config.CHECK_ROWS,
                          "check": check,
                          "reference_default_precision": below}), flush=True)
        ok = ok and check["ok"] and below["fails_f32_logits"]
        del built, params, buffers, exact, lower
    return 0 if ok else 1


if __name__ == "__main__":
    import jax

    if jax.devices()[0].platform != "tpu":
        print("lfm2_check.py reads the chip's arithmetic: no TPU",
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
