#!/usr/bin/env python3
"""LFM2's gated short convolution alone on the chip —
``ops.linear_attention.gated_short_conv``: ``C * conv3(B * u)`` on the
[B | C | u] stream ``in_proj`` leaves — on both of its paths (the Mosaic
kernels ``gated_conv_fwd`` / ``_bwd`` and the XLA stage every other program
runs) at the LFM2 cell's shape: 4 rows x 8,192 tokens x 3 x 2,048 channels
in bf16, 3 taps.

A line a path and variant: ms forward and forward + backward, and GB/s on
the stage's least bytes (``benchmark/layer_metrics/
shortconv_stage_roofline.py``'s count: B, C and u read and the result
written forward; those and the cotangent read, three cotangents written
backward).

    chiprun -- python3 tools/shortconv_bench.py [tokens,...] [--thirds]

``tokens``: the kernels' token blocks to try (default: ``gated_conv_tokens``'
choice, half and twice that). ``--thirds``: also the backward that writes
dB, dC and du as three arrays, joined by XLA afterwards, against the one
[B, T, 3 C] array the module's writes. (PR 46 also tried a third cut in
channel steps of 512 and 1,024 and the body in chains of 16 to 128 tokens:
all within 4% of one another, PERF.md section 6, and the module kept
neither knob.) A microbenchmark's numbers are findings for PERF.md, never a
metric of the benchmark. Exits 2 without a TPU.
"""
import functools
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BATCH, SEQ, CHANNELS, TAPS = 4, 8192, 2048, 3


def _the_benchmarks_clock():
    """``benchmark/tools/kda_candidates.py``'s ``timed`` and ``line``: the
    clock the other kernel benches' numbers were taken with."""
    spec = importlib.util.spec_from_file_location(
        "kda_candidates", os.path.join(ROOT, "benchmark", "tools",
                                       "kda_candidates.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.timed, module.line


timed, line = _the_benchmarks_clock()


class _Thirds:
    """Three [1, tokens, C] blocks behind the one [1, tokens, 3 C] block
    the backward's body writes its thirds into."""

    def __init__(self, refs):
        self.refs, self.dtype = refs, refs[0].dtype

    def __setitem__(self, at, value):
        row, tokens, lanes = at
        third, start = divmod(lanes.start, CHANNELS)
        self.refs[third][row, tokens, start:start + 128] = value


def three_array_backward(kernels, bcu, w, dy, *, tokens):
    """``kernels._gated_backward`` with dB, dC and du leaving as three
    arrays, and XLA's concatenation after it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blocks = SEQ // tokens

    def body(bcu_ref, before_ref, w_ref, dy_ref, db, dc, du, dw_ref, carry):
        kernels._gated_bwd_kernel(bcu_ref, before_ref, w_ref, dy_ref,
                                  _Thirds((db, dc, du)), dw_ref, carry)

    def whole(wide):
        return pl.BlockSpec((1, tokens, wide),
                            lambda b, n: (b, blocks - 1 - n, 0))

    before = pl.BlockSpec(
        (1, 16, 3 * CHANNELS), lambda b, n: (
            b, jnp.maximum((blocks - 1 - n) * (tokens // 16) - 1, 0), 0))
    taps_row = pl.BlockSpec((1, TAPS, CHANNELS), lambda b, n: (b, 0, 0))
    like = jax.ShapeDtypeStruct
    *thirds, dw = pl.pallas_call(
        body, grid=(BATCH, blocks),
        in_specs=[whole(3 * CHANNELS), before, taps_row, whole(CHANNELS)],
        out_specs=[whole(CHANNELS)] * 3 + [taps_row],
        out_shape=[like(dy.shape, bcu.dtype)] * 3
        + [like(w.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((kernels.CONV_HALO, CHANNELS),
                                   jnp.float32)],
        name="gated_conv_bwd_thirds", compiler_params=kernels._GATED_PARAMS,
    )(bcu, bcu, w, dy)
    return jnp.concatenate(thirds, axis=-1), dw


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import linear_attention
    from paddle_tpu.ops.pallas import linear_attention as kernels

    if jax.devices()[0].platform != "tpu":
        print("shortconv_bench.py times the chip's kernels: no TPU",
              file=sys.stderr)
        return 2
    args = [a for a in sys.argv[1:] if a != "--thirds"]
    chosen = kernels.gated_conv_tokens(CHANNELS, jnp.bfloat16)
    blocks = ([int(t) for t in args[0].split(",")] if args
              else [chosen, chosen // 2, chosen * 2])
    keys = jax.random.split(jax.random.PRNGKey(46), 3)
    bcu = jax.random.normal(keys[0], (BATCH, SEQ, 3 * CHANNELS), jnp.bfloat16)
    w = jax.random.normal(keys[1], (TAPS, CHANNELS), jnp.float32) * 0.5
    # the cotangent as out_proj's backward leaves it: an array in HBM
    dy = jax.random.normal(keys[2], (BATCH, SEQ, CHANNELS), jnp.bfloat16)
    third = 2 * BATCH * SEQ * CHANNELS             # a third's bytes, bf16
    rows_of_taps = jnp.broadcast_to(w[None], (BATCH,) + w.shape)

    def report(path, fwd, fwd_bwd, **variant):
        f = timed(jax.jit(fwd), bcu, w)
        fb = timed(jax.jit(fwd_bwd), bcu, w, dy)
        line(path=path, **variant, fwd_ms=round(f, 3),
             fwd_bwd_ms=round(fb, 3), bwd_ms=round(fb - f, 3),
             fwd_gb_s=round(4 * third / f / 1e6, 1),
             bwd_gb_s=round(7 * third / (fb - f) / 1e6, 1))

    def with_vjp(fn):
        def both(bcu, w, dy):
            out, vjp = jax.vjp(fn, bcu, w)
            return out, vjp(dy)
        return both

    def xla(bcu, w):
        return linear_attention.gated_short_conv(bcu, w, kernel=None)

    report("xla", xla, with_vjp(xla))
    for tokens in blocks:
        def kernel(bcu, w):
            return kernels.gated_conv(
                bcu, jnp.broadcast_to(w[None], (BATCH,) + w.shape),
                tokens=tokens)

        try:
            report("kernel", kernel, with_vjp(kernel), tokens=tokens)
        except Exception as e:       # a block that does not fit VMEM
            line(path="kernel", tokens=tokens, error=str(e)[:300])
    if "--thirds" in sys.argv:
        for name, backward in (
                ("one_array", functools.partial(
                    kernels._gated_backward, tokens=chosen,
                    interpret=False)),
                ("three_arrays", functools.partial(
                    three_array_backward, kernels, tokens=chosen))):
            ms = timed(jax.jit(backward), bcu, rows_of_taps, dy)
            line(path="kernel", d_bcu=name, tokens=chosen,
                 bwd_ms=round(ms, 3), bwd_gb_s=round(7 * third / ms / 1e6, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
