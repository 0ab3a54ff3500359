#!/usr/bin/env python3
"""The gated delta rule's scan alone on the chip, at the Kimi-Linear cell's
shape (b1 h32 s16384 d128, bf16 q, k, v and float32 decay): the Mosaic
kernels (``paddle_tpu.ops.pallas.linear_attention``) against the XLA scan
(``ops.linear_attention.kda_chunked``), forward and forward + backward,
at the blocks tried (tokens x heads a program), with the compiled
program's temporary bytes and how far the two are apart.

    chiprun -- python3 tools/kda_kernel_bench.py [256x2,512x1,...]

A microbenchmark's numbers are findings for PERF.md, never a metric of the
benchmark. Exits 2 without a TPU.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _the_benchmarks_tool():
    """``benchmark/tools/kda_candidates.py``: the shape, the inputs and the
    clock PR 32's numbers for the XLA scan were taken with."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "kda_candidates", os.path.join(ROOT, "benchmark", "tools",
                                       "kda_candidates.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_xla = _the_benchmarks_tool()
BATCH, SEQ, HEADS, D = _xla.BATCH, _xla.SEQ, _xla.HEADS, _xla.D
timed, line, inputs = _xla.timed, _xla.line, _xla.inputs


def candidate(name, fn, args, against=None):
    """Times ``fn`` forward and forward + backward; returns its outputs
    (o, gradients) for the next candidate to be compared with."""
    import jax
    import jax.numpy as jnp

    both = jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2, 3, 4)))
    fwd = jax.jit(fn)
    try:
        temp = both.lower(*args).compile().memory_analysis()
        f, fb = timed(fwd, *args), timed(both, *args)
        got = (fwd(*args),) + tuple(both(*args))
    except Exception as e:  # a candidate that does not compile or fit
        line(candidate=name, error=f"{type(e).__name__}: {str(e)[:400]}")
        return None
    apart = {}
    if against is not None:
        for tag, a, b in zip("o dq dk dv dg dbeta".split(), got, against):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            apart[tag] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
    line(candidate=name, fwd_ms=round(f, 3), fwd_bwd_ms=round(fb, 3),
         fwd_bwd_temp_gb=round(temp.temp_size_in_bytes / 1e9, 3),
         apart_from_chunked={t: float(f"{e:.3g}") for t, e in apart.items()})
    return got


def main():
    import jax
    from paddle_tpu.ops import linear_attention as la
    from paddle_tpu.ops.pallas import linear_attention as kernels

    if jax.devices()[0].platform != "tpu":
        print("kda_kernel_bench.py measures on a TPU only", file=sys.stderr)
        return 2
    blocks = ([tuple(map(int, t.split("x"))) for t in sys.argv[1].split(",")]
              if len(sys.argv) > 1 else [(kernels.TOKENS, kernels.TOGETHER)])
    args = inputs(SEQ)
    line(device=jax.devices()[0].device_kind, batch=BATCH, seq=SEQ,
         heads=HEADS, d=D)
    ref = candidate("chunked (XLA scan, segments of 256)",
                    lambda *a: la.kda_chunked(*a)[0], args)
    for tokens, together in blocks:
        candidate(f"kernel, {tokens} tokens of {together} heads a program",
                  lambda *a, t=tokens, h=together: kernels.kda(
                      *a, tokens=t, together=h), args, ref)
    return 0


if __name__ == "__main__":
    sys.exit(main())
