#!/usr/bin/env python3
"""The candidates the Kimi-Linear cell's scan chose from, timed alone on the
chip at the cell's shape (PERF.md section 6, PR 32): the gated delta rule's
chunked scan (``paddle_tpu.ops.linear_attention.kda_chunked``, XLA
operations; no Mosaic body was written) at b1 h32 s16384 d128, bf16 q, k, v
and float32 decay, forward and forward + backward, at

* chunk 32 / 64 / 128 (the loop over chunks halves as the chunk doubles,
  the inverse and the pair terms grow with it),
* segment 64 / 128 / 512 / 1024 / 2048 tokens (what the backward pass
  rebuilds and keeps at a time) beside the 256 that ships, at the chunk that
  ships,

with the roofline share ``layer_metrics/kda_core_roofline.py`` would give
the same passes, and the compiled program's temporary bytes. The token
recurrence at 2,048 tokens stands beside them: what a step would run
without the chunked form.

    chiprun -- python3 benchmark/tools/kda_candidates.py \
        [chunks|segments [256,512,...]]

A microbenchmark's numbers are findings, never a metric of the benchmark.
Exits 2 without a TPU.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

BATCH, SEQ, HEADS, D = 1, 16384, 32, 128
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9     # harness/peaks.py, TPU v5 lite


def timed(fn, *args, reps=5):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def line(**kw):
    print(json.dumps(kw), flush=True)


def inputs(seq):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(32), 5)

    def unit(key):
        x = jax.random.normal(key, (BATCH, seq, HEADS, D), jnp.float32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True))

    q = (unit(ks[0]) * D ** -0.5).astype(jnp.bfloat16)
    k = unit(ks[1]).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (BATCH, seq, HEADS, D), jnp.bfloat16)
    # the decay as the layer starts it: A in (1, 16), dt in (1e-3, 1e-1)
    g = -jax.random.uniform(ks[3], (BATCH, seq, HEADS, D), jnp.float32,
                            1e-3, 1.6)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (BATCH, seq, HEADS)))
    return q, k, v, g, beta


def candidate(name, fn, args, seq, chunk):
    import jax
    import jax.numpy as jnp
    from benchmark.harness import cells

    reader = cells.load_module("layer_metrics", "kda_core_roofline")

    def least_ms(forwards, backwards):
        return 1e3 * max(
            reader.kda_core_flops(seq, HEADS, D, D, chunk, forwards,
                                  backwards) / PEAK_FLOPS,
            reader.kda_core_bytes(seq, HEADS, D, D, forwards, backwards)
            / PEAK_BYTES)

    both = jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2, 3, 4)))
    try:
        fwd = jax.jit(fn)
        temp = both.lower(*args).compile().memory_analysis()
        f, fb = timed(fwd, *args), timed(both, *args)
        line(candidate=name, fwd_ms=round(f, 3), fwd_bwd_ms=round(fb, 3),
             fwd_roofline_pct=round(100 * least_ms(1, 0) / f, 2),
             fwd_bwd_roofline_pct=round(100 * least_ms(1, 1) / fb, 2),
             fwd_bwd_temp_gb=round(temp.temp_size_in_bytes / 1e9, 3))
    except Exception as e:  # a candidate that does not compile or fit
        line(candidate=name, error=f"{type(e).__name__}: {str(e)[:300]}")


def main():
    import jax
    from paddle_tpu.ops import linear_attention as la

    if jax.devices()[0].platform != "tpu":
        print("kda_candidates.py measures on a TPU only", file=sys.stderr)
        return 2
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    args = inputs(SEQ)
    if which in ("chunks", "both"):
        for chunk in (32, 64, 128):
            candidate(f"chunked s{SEQ} chunk {chunk} segment 256",
                      lambda *a, c=chunk: la.kda_chunked(*a, chunk=c)[0],
                      args, SEQ, chunk)
        short = inputs(2048)
        candidate("recurrent s2048 (token by token)",
                  lambda *a: la.kda_recurrent(*a)[0], short, 2048, 1)
        candidate("chunked s2048 chunk 64",
                  lambda *a: la.kda_chunked(*a, chunk=64)[0], short, 2048, 64)
    if which in ("segments", "both"):
        given = sys.argv[2].split(",") if len(sys.argv) > 2 else (
            64, 128, 512, 1024, 2048)
        for segment in map(int, given):
            candidate(f"chunked s{SEQ} chunk 64 segment {segment}",
                      lambda *a, s=segment: la.kda_chunked(
                          *a, chunk=64, segment=s)[0], args, SEQ, 64)
    return 0


if __name__ == "__main__":
    sys.exit(main())
