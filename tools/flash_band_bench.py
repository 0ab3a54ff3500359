#!/usr/bin/env python3
"""The streaming flash kernel alone on the chip at the Trinity-Mini cell's
attention (b1 h32 s16384 d128 bf16, causal): the full-causal calls against
the banded calls of a 2,048-key window, forward and forward + backward, at
the blocks asked for — what the band's grid saves, and which block a band
wants (a larger block runs fewer, fuller grid steps but computes more of
the band's masked corners: at 1,024-wide blocks 3 x 1,024 keys a query for
a window of 2,048, at 512 5 x 512). ``masked`` is the band computed the way
a gate alone would: the full grid under the window's mask (the XLA route
cannot hold 16,384^2 scores at all).

    chiprun -- python3 tools/flash_band_bench.py [1024x1024,512x512,...]

A microbenchmark's numbers are findings for PERF.md, never a metric of the
benchmark. Exits 2 without a TPU.
"""
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BATCH, HEADS, SEQ, D, WINDOW = 1, 32, 16384, 128, 2048


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


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa

    if jax.devices()[0].platform != "tpu":
        print("flash_band_bench.py times the chip's kernels: no TPU",
              file=sys.stderr)
        return 2
    blocks = [tuple(map(int, b.split("x"))) for b in (
        sys.argv[1] if len(sys.argv) > 1 else "1024x1024,512x512").split(",")]
    keys = jax.random.split(jax.random.PRNGKey(40), 3)
    q, k, v = (jax.random.normal(key, (BATCH, HEADS, SEQ, D), jnp.bfloat16)
               for key in keys)
    full_pairs = SEQ * (SEQ + 1) // 2
    band_pairs = fa.band_pairs(SEQ, WINDOW)
    for window, pairs in ((None, full_pairs), (WINDOW, band_pairs)):
        for bq, bk in blocks:
            def fn(q, k, v):
                return fa.mha(q, k, v, causal=True, window=window,
                              block_q=bq, block_k=bk)

            both = jax.jit(jax.grad(
                lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                argnums=(0, 1, 2)))
            try:
                f, fb = timed(jax.jit(fn), q, k, v), timed(both, q, k, v)
            except Exception as e:   # a block that does not fit VMEM
                line(window=window, block=[bq, bk], error=str(e)[:300])
                continue
            flops = 2.0 * BATCH * HEADS * pairs * D
            line(window=window, block=[bq, bk], fwd_ms=round(f, 3),
                 fwd_bwd_ms=round(fb, 3),
                 fwd_model_tflops=round(2 * flops / f / 1e9, 1),
                 fwd_bwd_model_tflops=round(7 * flops / fb / 1e9, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
