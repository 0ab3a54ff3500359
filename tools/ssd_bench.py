#!/usr/bin/env python3
"""The selective state-space scan alone on the chip —
``ops.linear_attention.ssd_chunked``, Mamba-2's scalar-decay scan with no
delta correction, in XLA operations — at the granite-4.0-h-micro cell's
shape: 1 row x 8,192 tokens, 64 heads of 64 on a state of 128, B and C one
group's, bf16 operands; by chunk and segment.

A line a (chunk, segment): ms forward and forward + backward (plain
autodiff, every segment under ``jax.checkpoint``), and the share of the
scan's roofline (``benchmark/layer_metrics/ssd_core_roofline.py``'s FLOPs
and least bytes for one forward and one backward).

    chiprun -- python3 tools/ssd_bench.py [chunk x segment,...]

(default: 64x512, 128x1024, 256x1024, 256x2048, 256x4096, 512x2048,
512x4096).
It is what the configuration's ``mamba_chunk`` / ``mamba_segment`` were read
from (PERF.md section 6, PR 47).

``--stage conv`` times the mixer's CONVOLUTION STAGE alone instead —
``text.models._mamba_streams``: taps, bias, SiLU and the x | B | C split of
the 1 x 8,192 x 4,352 bf16 stream — on both of its paths in one call (the
Mosaic kernels ``conv_streams_fwd`` / ``_bwd`` and the XLA stage they are
held to): ms forward and forward + backward, the GB/s those are of the
stage's least bytes (``benchmark/layer_metrics/ssm_conv_stage_roofline.py
stage_bytes``), and how far the kernels' results are from the XLA stage's.

    chiprun -- python3 tools/ssd_bench.py --stage conv

``--path`` times the scan on BOTH of its paths in one call, streams in and
streams out as the layer hands them over (x and y [1, 8192, 4096], B and C
[1, 8192, 128]: the XLA path's relayouts to its head view are part of it) —
the Mosaic kernels ``ssd_chunk_fwd`` / ``_bwd`` (``ops/pallas/ssd.py``) by
chunk, token block and lanes a program, and ``ssd_chunked`` at the
configuration's 256 x 2048: ms forward and forward + backward, the share of
``ssd_core_roofline``'s bound (the algorithm's work at the configuration's
chunk of 256, whatever the kernels' own), and the largest difference between
the two paths' results (y and the six gradients, relative to the XLA path's
largest). It is what the kernels' ``CHUNK`` / ``TOKENS`` / ``LANES`` were
read from (PERF.md section 6, PR 50).

    chiprun -- python3 tools/ssd_bench.py --path [chunk x tokens x lanes,...]

A microbenchmark's numbers are findings for PERF.md, never a metric of the
benchmark. Exits 2 without a TPU.
"""
import functools
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BATCH, SEQ, HEADS, D_HEAD, GROUPS, D_STATE = 1, 8192, 64, 64, 1, 128
DEFAULT = "64x512,128x1024,256x1024,256x2048,256x4096,512x2048,512x4096"


def _load(*path):
    spec = importlib.util.spec_from_file_location(
        path[-1][:-3], os.path.join(ROOT, *path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def conv_stage(clock):
    """A line a path of the convolution stage: ``kernel`` first, ``xla``
    against it."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import placement
    from paddle_tpu.text import models

    stage_bytes = _load("benchmark", "layer_metrics",
                        "ssm_conv_stage_roofline.py").stage_bytes
    inner, state = HEADS * D_HEAD, GROUPS * D_STATE
    channels = inner + 2 * state
    keys = jax.random.split(jax.random.PRNGKey(48), 6)
    bf16 = jnp.bfloat16
    xbc = jax.random.normal(keys[0], (BATCH, SEQ, channels), bf16)
    w = jax.random.normal(keys[1], (4, channels)) * 0.5
    bias = jax.random.normal(keys[2], (channels,))
    dys = tuple(jax.random.normal(k, (BATCH, SEQ, n), bf16)
                for k, n in zip(keys[3:], (inner, state, state)))
    clock.line(device=jax.devices()[0].device_kind, stage="conv",
               batch=BATCH, seq=SEQ, channels=channels, taps=4)

    def far(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(f"{jnp.abs(a - b).max() / jnp.abs(a).max():.3g}")

    first, tokens = None, BATCH * SEQ
    for path, kernel in (("kernel", placement.kernel(sharded=True)),
                         ("xla", None)):
        def stage(xbc, w, bias):
            return models._mamba_streams(xbc, w, bias, inner=inner,
                                         state=state, head=D_HEAD,
                                         kernel=kernel)

        def both(xbc, w, bias, dys):
            out, vjp = jax.vjp(stage, xbc, w, bias)
            return out, vjp(dys)

        both = jax.jit(both)
        f = clock.timed(jax.jit(stage), xbc, w, bias, reps=20)
        fb = clock.timed(both, xbc, w, bias, dys, reps=20)
        got = jax.tree.leaves(both(xbc, w, bias, dys))
        first = first or got
        apart = {tag: far(a, b) for tag, a, b in zip(
            "x b c dxbc dw dbias".split(), first, got)}
        clock.line(
            path=path, fwd_ms=round(f, 3), fwd_bwd_ms=round(fb, 3),
            fwd_gb_per_s=round(stage_bytes(tokens, channels, 1, 0)
                               / f / 1e6, 1),
            fwd_bwd_gb_per_s=round(stage_bytes(tokens, channels, 1, 1)
                                   / fb / 1e6, 1),
            apart_from_kernel=apart)
    return 0


def scan_inputs():
    """The cell's scan operands as heads — x [B, T, H, P] bf16, dt, a, b, c
    [B, T, G, N] bf16, d — a cotangent for y, and the roofline's shape."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(47), 6)
    bf16 = jnp.bfloat16
    x = jax.random.normal(keys[0], (BATCH, SEQ, HEADS, D_HEAD), bf16)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (BATCH, SEQ, HEADS))
                         - 4.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (HEADS,), minval=0.0,
                                    maxval=2.77))
    b, c = (jax.random.normal(k, (BATCH, SEQ, GROUPS, D_STATE), bf16)
            for k in keys[3:5])
    d = jnp.ones((HEADS,))
    dy = jax.random.normal(keys[5], x.shape, bf16)
    return (x, dt, a, b, c, d), dy, (BATCH * SEQ, HEADS, D_HEAD, D_STATE,
                                     GROUPS)


@functools.lru_cache(maxsize=None)
def _yardstick():
    """(``ssd_core_roofline``'s module, the chip's peaks)."""
    import jax

    return (_load("benchmark", "layer_metrics", "ssd_core_roofline.py"),
            _load("benchmark", "harness", "peaks.py").PEAKS[
                jax.devices()[0].device_kind])


def least_ms(shape, chunk=256):
    """``ssd_core_roofline``'s bound for one forward and one backward."""
    roofline, peaks = _yardstick()
    return 1e3 * max(
        roofline.ssd_core_flops(*shape, chunk, 1, 1)
        / peaks["bf16_flops_per_s"],
        roofline.ssd_core_bytes(*shape, 1, 1) / peaks["hbm_bytes_per_s"])


def both_paths(clock, candidates):
    """A line a candidate of the kernels, and the XLA path they are held
    to, all on streams."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import linear_attention
    from paddle_tpu.ops.pallas import ssd as kernels

    (x, dt, a, b, c, d), dy, shape = scan_inputs()
    flat = (x.reshape(BATCH, SEQ, -1), dt, a, b.reshape(BATCH, SEQ, -1),
            c.reshape(BATCH, SEQ, -1), d)
    dy = dy.reshape(flat[0].shape)
    bound = least_ms(shape)
    clock.line(device=jax.devices()[0].device_kind, stage="scan",
               batch=BATCH, seq=SEQ, heads=HEADS, d_head=D_HEAD,
               d_state=D_STATE, groups=GROUPS, least_ms=round(bound, 3))

    def xla(*args):
        return linear_attention._ssd_chunked_output(
            *args, groups=GROUPS, chunk=256, segment=2048)

    def measured(scan):
        def both(*args):
            out, vjp = jax.vjp(scan, *args[:-1])
            return out, vjp(args[-1])

        both = jax.jit(both)
        f = clock.timed(jax.jit(scan), *flat, reps=20)
        fb = clock.timed(both, *flat, dy, reps=20)
        return f, fb, jax.tree.leaves(both(*flat, dy))

    def far(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return float(f"{jnp.abs(got - want).max() / jnp.abs(want).max():.3g}")

    f, fb, want = measured(xla)
    clock.line(path="chunked", chunk=256, segment=2048, fwd_ms=round(f, 3),
               fwd_bwd_ms=round(fb, 3), roofline_pct=round(100 * bound / fb,
                                                           2))
    for cand in candidates.split(","):
        chunk, tokens, lanes = (int(v) for v in cand.split("x"))

        def kernel(*args):
            return kernels.ssd(*args, groups=GROUPS, chunk=chunk,
                               tokens=tokens, lanes=lanes)

        try:
            f, fb, got = measured(kernel)
        except Exception as e:          # a candidate that does not fit
            clock.line(path="kernel", chunk=chunk, tokens=tokens,
                       lanes=lanes, error=str(e)[:300])
            continue
        clock.line(
            path="kernel", chunk=chunk, tokens=tokens, lanes=lanes,
            fwd_ms=round(f, 3), fwd_bwd_ms=round(fb, 3),
            roofline_pct=round(100 * bound / fb, 2),
            apart_from_chunked={tag: far(g, w) for tag, g, w in zip(
                "y dx ddt da db dc dd".split(), got, want)})
    return 0


def main():
    import jax

    from paddle_tpu.ops import linear_attention
    from paddle_tpu.ops.pallas import ssd as kernels

    if jax.devices()[0].platform != "tpu":
        print("ssd_bench.py times the chip: no TPU", file=sys.stderr)
        return 2
    clock = _load("benchmark", "tools", "kda_candidates.py")
    if sys.argv[1:3] == ["--stage", "conv"]:
        return conv_stage(clock)
    if sys.argv[1:2] == ["--path"]:
        return both_paths(clock, sys.argv[2] if len(sys.argv) > 2 else
                          f"{kernels.CHUNK}x{kernels.TOKENS}x{kernels.LANES}")
    args, dy, shape = scan_inputs()

    for pair in (sys.argv[1] if len(sys.argv) > 1 else DEFAULT).split(","):
        chunk, segment = (int(v) for v in pair.split("x"))

        def scan(x, dt, a, b, c, d):
            return linear_attention.ssd_chunked(
                x, dt, a, b, c, d, chunk=chunk, segment=segment)[0]

        def both(x, dt, a, b, c, d, dy):
            out, vjp = jax.vjp(scan, x, dt, a, b, c, d)
            return out, vjp(dy)

        try:
            f = clock.timed(jax.jit(scan), *args)
            fb = clock.timed(jax.jit(both), *args, dy)
        except Exception as e:          # a candidate that does not fit
            clock.line(chunk=chunk, segment=segment, error=str(e)[:300])
            continue
        clock.line(chunk=chunk, segment=segment, fwd_ms=round(f, 3),
                   fwd_bwd_ms=round(fb, 3), bwd_ms=round(fb - f, 3),
                   roofline_pct=round(100 * least_ms(shape, chunk) / fb, 2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
