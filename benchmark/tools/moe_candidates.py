#!/usr/bin/env python3
"""The grouped-matmul candidates of the dropless expert layer, and the
pieces around them, timed alone on the chip at the OLMoE cell's shapes
(PERF.md section 6, PR 25): 131,072 assigned rows in 64 groups,
[2048 -> 1024] and [1024 -> 2048], bf16, forward and forward + backward.

    chiprun -- python3 benchmark/tools/moe_candidates.py

Candidates: ``jax.lax.ragged_dot`` (XLA) and the Pallas
``megablox.gmm`` at a few tilings; group sizes uniform, drawn (a
multinomial over the experts, as a router at initialisation gives) and
degenerate (one expert empty, one expert with every row). Also timed: the
sort, the row gather of the dispatch and the streaming flash kernel at the
cell's attention shape. A microbenchmark's numbers are findings, never a
metric of the benchmark. Exits 2 without a TPU.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

ROWS, GROUPS, HIDDEN, WIDTH, TOKENS, TOP_K = 131072, 64, 2048, 1024, 16384, 8


def timed(fn, *args, reps=10):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("moe_candidates.py measures on a TPU only", file=sys.stderr)
        return 2
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    rng = np.random.default_rng(25)
    sizes = {
        "uniform": np.full(GROUPS, ROWS // GROUPS),
        "drawn": rng.multinomial(ROWS, rng.dirichlet(np.full(GROUPS, 4.0))),
        "one-empty": np.concatenate([[0, 2 * ROWS // GROUPS], np.full(
            GROUPS - 2, ROWS // GROUPS)]),
        "all-on-one": np.concatenate([[ROWS], np.zeros(GROUPS - 1, int)]),
    }
    key = jax.random.PRNGKey(0)
    x_up = jax.random.normal(key, (ROWS, HIDDEN), jnp.bfloat16)
    w_up = jax.random.normal(key, (GROUPS, HIDDEN, WIDTH), jnp.bfloat16)
    x_dn = jax.random.normal(key, (ROWS, WIDTH), jnp.bfloat16)
    w_dn = jax.random.normal(key, (GROUPS, WIDTH, HIDDEN), jnp.bfloat16)

    def ragged(x, w, gs):
        return jax.lax.ragged_dot(x, w, gs,
                                  preferred_element_type=jnp.bfloat16)

    def mega(tiling):
        return lambda x, w, gs: gmm(x, w, gs, jnp.bfloat16, tiling)

    candidates = {"ragged_dot": ragged}
    for tiling in ((128, 128, 128), (512, 512, 512), (512, 1024, 1024),
                   (1024, 1024, 1024), (256, 2048, 1024)):
        candidates["gmm" + "x".join(map(str, tiling))] = mega(tiling)

    def line(**kw):
        print(json.dumps(kw), flush=True)

    flops = 2.0 * ROWS * HIDDEN * WIDTH
    for cname, fn in candidates.items():
        fwd = jax.jit(fn)
        # a loss that needs the forward's result: with a plain sum XLA drops
        # the forward gemm and "forward + backward" times the backward alone
        # (PR 25's readings in PERF.md were taken so, and say so)
        both = jax.jit(jax.grad(
            lambda x, w, gs: jnp.sum(fn(x, w, gs).astype(jnp.float32) ** 2),
            argnums=(0, 1)))
        for sname, gs in sizes.items():
            if sname != "drawn" and cname not in ("ragged_dot",
                                                  "gmm512x1024x1024"):
                continue
            gs = jnp.asarray(gs, jnp.int32)
            for label, x, w in (("up", x_up, w_up), ("down", x_dn, w_dn)):
                try:
                    f = timed(fwd, x, w, gs)
                    fb = timed(both, x, w, gs)
                    line(candidate=cname, groups=sname, matmul=label,
                         fwd_ms=round(f, 3), fwd_bwd_ms=round(fb, 3),
                         fwd_tflops=round(flops / f / 1e9, 1),
                         fwd_bwd_tflops=round(3 * flops / fb / 1e9, 1))
                except Exception as e:  # a candidate that does not compile
                    line(candidate=cname, groups=sname, matmul=label,
                         error=f"{type(e).__name__}: {str(e)[:300]}")

    # the dispatch's pieces: sort 131,072 expert ids, gather the rows
    ids = jax.random.randint(key, (ROWS,), 0, GROUPS, jnp.int32)
    x_tok = jax.random.normal(key, (TOKENS, HIDDEN), jnp.bfloat16)
    order = jnp.argsort(ids)
    line(piece="argsort[131072] int32",
         ms=round(timed(jax.jit(jnp.argsort), ids), 3))
    line(piece="gather tokens [16384 -> 131072, 2048] bf16", ms=round(timed(
        jax.jit(lambda x, o: x[o // TOP_K]), x_tok, order), 3))
    line(piece="permute rows [131072, 2048] bf16", ms=round(timed(
        jax.jit(lambda x, o: x[o]), x_up, order), 3))
    line(piece="scatter-add rows [131072 -> 16384, 2048] f32", ms=round(timed(
        jax.jit(lambda x, o: jnp.zeros((TOKENS, HIDDEN), jnp.float32)
                .at[o // TOP_K].add(x.astype(jnp.float32))), x_up, order), 3))

    # the streaming flash kernel at the cell's attention shape
    from paddle_tpu.ops.pallas import flash_attention as fa

    q = jax.random.normal(key, (4, 16, 4096, 128), jnp.bfloat16)
    causal_flops = 0.5 * 4.0 * 4 * 16 * 4096 * 4096 * 128
    for bq, bk in ((256, 256), (512, 512), (512, 1024), (1024, 1024),
                   (256, 512), (1024, 512)):
        def attn(q, k, v):
            return fa.mha(q, k, v, causal=True, block_q=bq, block_k=bk)
        try:
            f = timed(jax.jit(attn), q, q, q)
            fb = timed(jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                attn(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))),
                q, q, q)
            line(piece=f"flash stream b4 h16 s4096 d128 causal bq{bq} bk{bk}",
                 fwd_ms=round(f, 3), fwd_bwd_ms=round(fb, 3),
                 fwd_tflops=round(causal_flops / f / 1e9, 1),
                 fwd_bwd_tflops=round(3.5 * causal_flops / fb / 1e9, 1))
        except Exception as e:
            line(piece=f"flash stream bq{bq} bk{bk}",
                 error=f"{type(e).__name__}: {str(e)[:300]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
