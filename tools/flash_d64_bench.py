#!/usr/bin/env python3
"""The streaming flash kernel alone on the chip at the LFM2 cell's attention
core (b4 h32 s8192 d64 bf16, causal: a 64-wide row takes a whole 128-lane
group in VMEM and half of the MXU's 128-deep contraction), forward and
forward + backward at the blocks asked for, beside the same FLOPs and bytes
at heads of 128 (b4 h16 s8192 d128): what ``_stream_block``'s choice gives
at d 64, and what a kernel that packed two heads of 64 a lane group could
reach at most (ROADMAP Speed: its yardstick is ``attn64_flash_roofline``).

    chiprun -- python3 tools/flash_d64_bench.py [1024x1024,512x512,...]

A microbenchmark's numbers are findings for PERF.md, never a metric of the
benchmark. Exits 2 without a TPU.
"""
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BATCH, SEQ = 4, 8192
#: (heads, head_dim): the cell's core, and the same work at full lane groups
SHAPES = ((32, 64), (16, 128))


def _the_benchmarks_clock():
    """``benchmark/tools/kda_candidates.py``'s ``timed`` and ``line``: the
    clock the other kernel benches' numbers were taken with."""
    spec = importlib.util.spec_from_file_location(
        "kda_candidates", os.path.join(ROOT, "benchmark", "tools",
                                       "kda_candidates.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.timed, module.line


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention as fa

    if jax.devices()[0].platform != "tpu":
        print("flash_d64_bench.py times the chip's kernels: no TPU",
              file=sys.stderr)
        return 2
    timed, line = _the_benchmarks_clock()
    blocks = [tuple(map(int, b.split("x"))) for b in (
        sys.argv[1] if len(sys.argv) > 1
        else "1024x1024,1024x512,512x512").split(",")]
    pairs = SEQ * (SEQ + 1) // 2
    for heads, d in SHAPES:
        keys = jax.random.split(jax.random.PRNGKey(44), 3)
        q, k, v = (jax.random.normal(key, (BATCH, heads, SEQ, d),
                                     jnp.bfloat16) for key in keys)
        flops = 2.0 * BATCH * heads * pairs * d
        for bq, bk in blocks:
            def fn(q, k, v):
                return fa.mha(q, k, v, causal=True, block_q=bq, block_k=bk)

            both = jax.jit(jax.grad(
                lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                argnums=(0, 1, 2)))
            try:
                f, fb = timed(jax.jit(fn), q, k, v), timed(both, q, k, v)
            except Exception as e:   # a block that does not fit VMEM
                line(heads=heads, d=d, block=[bq, bk], error=str(e)[-300:])
                continue
            line(heads=heads, d=d, block=[bq, bk],
                 picked=fa._stream_block(d, d, 2) == bq == bk,
                 fwd_ms=round(f, 3), fwd_bwd_ms=round(fb, 3),
                 fwd_model_tflops=round(2 * flops / f / 1e9, 1),
                 fwd_bwd_model_tflops=round(7 * flops / fb / 1e9, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
