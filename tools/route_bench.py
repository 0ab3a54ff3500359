#!/usr/bin/env python3
"""The expert layer's choice alone on the chip — ``incubate.moe._choice``:
top-k ids of the experts' scores, the scores at them, the pairs an expert took —
on both of its paths (the Mosaic kernels ``moe_route_fwd`` / ``_bwd`` and
the XLA stage every other program runs: a sort of every row and ``[N, k,
E]`` one-hot sums), at each expert cell's (tokens, experts, k, scoring),
float32 scores:

- ``nemotron``: 4,096 tokens, top-22 of 512, sigmoid + selection bias;
- ``qwen3-next``: 16,384, top-10 of 512, softmax (the choice is made on the
  weights themselves);
- ``kimi-linear``: 16,384, top-8 of 256, sigmoid + bias;
- ``joyai``: 8,192, top-8 of 256, sigmoid + bias;
- ``trinity-mini``: 16,384, top-8 of 128, sigmoid + bias;
- ``lfm2``: 4 x 8,192, top-4 of 32, sigmoid + bias;
- ``olmoe``: 4 x 4,096, top-8 of 64, softmax.

A line a shape and path: ms forward and forward + backward (the gradient
to the scores from a cotangent on the chosen scores), and that the two
paths chose the same ids and weights. ``route_path``'s rule by E rests on
this table (PERF.md section 6, PR 52).

    chiprun -- python3 tools/route_bench.py [case,...] [tokens,...]

``tokens``: the kernels' token tiles to try (default ``ROUTE_TOKENS``). A
microbenchmark's numbers are findings for PERF.md, never a metric of the
benchmark. Exits 2 without a TPU.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: tokens, experts, k, scoring (``sigmoid``: the choice on scores + bias)
CASES = {
    "nemotron": (4096, 512, 22, "sigmoid"),
    "qwen3-next": (16384, 512, 10, "softmax"),
    "kimi-linear": (16384, 256, 8, "sigmoid"),
    "joyai": (8192, 256, 8, "sigmoid"),
    "trinity-mini": (16384, 128, 8, "sigmoid"),
    "lfm2": (32768, 32, 4, "sigmoid"),
    "olmoe": (16384, 64, 8, "softmax"),
}


def line(**kw):
    """A JSON line a reading, as the other kernel benches print them."""
    print(json.dumps(kw), flush=True)


#: calls in one program a reading: a call is 0.1–2 ms, under what one
#: dispatch costs the host
REPS = 32


def timed(fn, scores, *rest):
    """ms a call of ``fn(scores, *rest)``, ``REPS`` calls in ONE program:
    each call reads the scores the last one touched (one element written in
    place), so the calls run in turn and none is hoisted, and the host
    dispatches once."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def looped(scores, *rest):
        def body(_, carry):
            scores, kept = carry
            out = jax.tree_util.tree_leaves(fn(scores, *rest))
            # a sum of every result: nothing of a call is dead code (the
            # gradient's [N, E] costs both paths the same read)
            probe = sum(jnp.sum(leaf).astype(scores.dtype) for leaf in out)
            return scores.at[0, 0].add(0.0 * probe), kept + probe

        return jax.lax.fori_loop(0, REPS, body, (scores, 0.0 * scores[0, 0]))

    jax.block_until_ready(looped(scores, *rest))
    t0 = time.perf_counter()
    jax.block_until_ready(looped(scores, *rest))
    return 1e3 * (time.perf_counter() - t0) / REPS


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate import moe
    from paddle_tpu.ops.pallas import moe_route as kernels

    if jax.devices()[0].platform != "tpu":
        print("route_bench.py times the chip's kernels: no TPU",
              file=sys.stderr)
        return 2
    names = sys.argv[1].split(",") if len(sys.argv) > 1 else list(CASES)
    tiles = ([int(t) for t in sys.argv[2].split(",")]
             if len(sys.argv) > 2 else [kernels.ROUTE_TOKENS])
    for name in names:
        tokens, experts, k, scoring = CASES[name]
        keys = jax.random.split(jax.random.PRNGKey(52), 3)
        logits = jax.random.normal(keys[0], (tokens, experts), jnp.float32)
        bias = 0.1 * jax.random.normal(keys[1], (experts,), jnp.float32)
        # a cotangent as the renormalisation's backward leaves it
        c = jax.random.normal(keys[2], (tokens, k), jnp.float32)
        scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        want = None
        for path, kernel, tile in [("xla", None, None)] + [
                ("kernel", "mosaic", tile) for tile in tiles]:
            def fn(scores):
                select = (jax.lax.stop_gradient(scores + bias)
                          if scoring == "sigmoid" else scores)
                return moe._choice(select, scores, k, kernel)

            @jax.jit
            def both(scores, c):
                def weights_first(s):
                    topv, *rest = fn(s)
                    return topv, rest

                topv, vjp, rest = jax.vjp(weights_first, scores,
                                          has_aux=True)
                return (topv, *rest), vjp(c)

            chosen = kernels.ROUTE_TOKENS
            kernels.ROUTE_TOKENS = tile or chosen
            forward = jax.jit(fn)
            try:
                f = timed(forward, scores)
                fb = timed(both, scores, c)
                got = forward(scores) + both(scores, c)[1]
            except Exception as e:   # a tile that does not fit VMEM
                line(case=name, path=path, tile=tile, error=str(e)[:300])
                continue
            finally:
                kernels.ROUTE_TOKENS = chosen
            want = want or got
            line(case=name, tokens=tokens, experts=experts, k=k,
                 scoring=scoring, path=path, tile=tile, fwd_ms=round(f, 3),
                 fwd_bwd_ms=round(fb, 3),
                 same_as_xla=all(bool(jnp.array_equal(a, b))
                                 for a, b in zip(got, want)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
