#!/usr/bin/env python3
"""Softmax attention's stage before the core alone on the chip —
``ops.attention.qk_heads``: a head's RMSNorm of q and k, rotate-half RoPE,
the split into [B, heads, T, d] — on both of its paths (the Mosaic kernels
``qk_heads_fwd`` / ``_bwd`` and the XLA stage every other program runs), at
the shapes of the cells whose stems call it, bf16 streams:

- ``trinity-swa``: 1 x 16,384 x 32 / 4 heads of 128, all features rotated;
- ``trinity-gattn``: the same heads, no positions;
- ``qwen3-next``: 16 / 2 heads of 256, 64 of them rotated, zero-centred, a
  query head every second 256-wide column block of the q stream;
- ``lfm2``: 4 x 8,192 x 32 / 8 heads of 64 — XLA only (half a lane group a
  head: ``qk_path`` refuses the shape), the number ROADMAP Speed 11b starts
  from.

A line a shape and path: ms forward and forward + backward, and GB/s on
the least bytes (each stream read once and each head array written once
forward; streams and cotangents read, gradients written backward).

    chiprun -- python3 tools/qk_heads_bench.py [case,...] [tokens,...]

``tokens``: the kernels' token blocks to try (default ``QK_TOKENS``). A
microbenchmark's numbers are findings for PERF.md, never a metric of the
benchmark. Exits 2 without a TPU.
"""
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: batch, seq, heads, kv_heads, d, stride, rotary_dim (None: no positions),
#: zero_centered, base
CASES = {
    "trinity-swa": (1, 16384, 32, 4, 128, 1, 128, False, 1e4),
    "trinity-gattn": (1, 16384, 32, 4, 128, 1, None, False, 1e4),
    "qwen3-next": (1, 16384, 16, 2, 256, 2, 64, True, 1e7),
    "lfm2": (4, 8192, 32, 8, 64, 1, 64, False, 1e6),
}


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

    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas import qk_heads as kernels

    if jax.devices()[0].platform != "tpu":
        print("qk_heads_bench.py times the chip's kernels: no TPU",
              file=sys.stderr)
        return 2
    names = sys.argv[1].split(",") if len(sys.argv) > 1 else list(CASES)
    blocks = ([int(t) for t in sys.argv[2].split(",")]
              if len(sys.argv) > 2 else [kernels.QK_TOKENS])
    for name in names:
        batch, seq, heads, kv_heads, d, stride, rotary_dim, centred, base = (
            CASES[name])
        keys = jax.random.split(jax.random.PRNGKey(45), 4)
        q = jax.random.normal(keys[0], (batch, seq, heads * stride * d),
                              jnp.bfloat16)
        k = jax.random.normal(keys[1], (batch, seq, kv_heads * d),
                              jnp.bfloat16)
        w_q, w_k = (jax.random.normal(key, (d,), jnp.float32) * 0.1 + 1.0
                    for key in keys[2:])
        # cotangents as a core's backward leaves them: arrays in HBM
        c_q, c_k = (jax.random.normal(key, (batch, n, seq, d), jnp.bfloat16)
                    for key, n in zip(keys[2:], (heads, kv_heads)))
        static = dict(heads=heads, kv_heads=kv_heads, zero_centered=centred,
                      eps=1e-6, rope=rotary_dim is not None, base=base,
                      rotary_dim=rotary_dim, stride=stride)
        # the least bytes a pass: q's and k's features, in and out
        features = 2 * batch * seq * (heads + kv_heads) * d
        takes = kernels.supported(heads, kv_heads, d, q.dtype, rotary_dim)
        paths = [("xla", None, None)] + [
            ("kernel", "mosaic", tokens) for tokens in blocks if takes]
        for path, kernel, tokens in paths:
            def fn(q, k, w_q, w_k):
                return attention.qk_heads(q, k, w_q, w_k, kernel=kernel,
                                          **static)

            @jax.jit
            def both(q, k, w_q, w_k, c_q, c_k):
                out, vjp = jax.vjp(fn, q, k, w_q, w_k)
                return out, vjp((c_q, c_k))

            chosen = kernels.QK_TOKENS
            kernels.QK_TOKENS = tokens or chosen
            try:
                f = timed(jax.jit(fn), q, k, w_q, w_k)
                fb = timed(both, q, k, w_q, w_k, c_q, c_k)
            except Exception as e:   # a block that does not fit VMEM
                line(case=name, path=path, tokens=tokens,
                     error=str(e)[:300])
                continue
            finally:
                kernels.QK_TOKENS = chosen
            line(case=name, path=path, tokens=tokens, fwd_ms=round(f, 3),
                 fwd_bwd_ms=round(fb, 3),
                 fwd_gb_s=round(2 * features / f / 1e6, 1),
                 # backward: streams and cotangents in, gradients out
                 fwd_bwd_gb_s=round(5 * features / fb / 1e6, 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
