#!/usr/bin/env python3
"""The candidates the JoyAI-LLM-Flash cell chose from, timed alone on the
chip at the cell's shapes (PERF.md section 6, PR 29):

* how latent attention's 192-wide keys meet the 128 lanes, through the
  streaming flash kernel at b1 h32 s8192 bf16 causal, forward and forward +
  backward: ``as-is`` (q, k [.., 192] and v [.., 128] as they are: Mosaic
  pads the 192 to two lane groups in VMEM, the MXU contracts 192), ``pad``
  (q and k zero-padded to 256 in HBM by the caller, the same kernel),
  ``v-wide`` (v padded to 192 too: the equal-width kernel; NOT support,
  timed to show what it would cost); each at a few (block_q, block_k);
* the held experts' grouped matmuls at 8,192 rows (4,096 that land here
  and the row buffer's padding in the last group) in 16 groups of width
  768: ``jax.lax.ragged_dot`` and ``megablox.gmm`` at the OLMoE tiling
  (512, 1024, 1024) as it stands (tiles wider than the operand, masked),
  clamped to the operand (what ``incubate.moe._gmm_tiling`` now gives) and
  narrower;
* the held path's two ways back to token order: the gather of all
  tokens x 8 pairs from the padded rows (what the layer does) and a
  scatter-add of the buffer's rows.

    chiprun -- python3 benchmark/tools/mla_candidates.py [attention|gemm]

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

BATCH, HEADS, SEQ, D_QK, D_V = 1, 32, 8192, 192, 128
ROWS, GROUPS, HIDDEN, WIDTH, TOKENS, TOP_K = 8192, 16, 2048, 768, 8192, 8


def timed(fn, *args, reps=10):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps


def line(**kw):
    print(json.dumps(kw), flush=True)


def attention_candidates():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    key = jax.random.PRNGKey(29)
    q = jax.random.normal(key, (BATCH, HEADS, SEQ, D_QK), jnp.bfloat16)
    v = jax.random.normal(key, (BATCH, HEADS, SEQ, D_V), jnp.bfloat16)
    pairs = SEQ * (SEQ + 1) // 2
    fwd_flops = 2.0 * BATCH * HEADS * pairs * (D_QK + D_V)
    bwd_flops = 2.0 * BATCH * HEADS * pairs * (3 * D_QK + 2 * D_V)
    scale = D_QK ** -0.5

    def widen(x, to):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, to - x.shape[-1]),))

    layouts = {
        "as-is": lambda q, k, v, **kw: fa.mha(q, k, v, **kw),
        "pad": lambda q, k, v, **kw: fa.mha(
            widen(q, 256), widen(k, 256), v, **kw),
        "v-wide": lambda q, k, v, **kw: fa.mha(
            q, k, widen(v, D_QK), **kw)[..., :D_V],
    }
    for layout, fn in layouts.items():
        for bq, bk in ((512, 512), (1024, 512), (1024, 1024), (256, 256)):
            def attn(q, k, v):
                return fn(q, k, v, causal=True, scale=scale, block_q=bq,
                          block_k=bk)
            try:
                f = timed(jax.jit(attn), q, q, v)
                fb = timed(jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                    attn(q, k, v).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2))), q, q, v)
                line(piece=f"flash stream b1 h32 s8192 d192/128 {layout} "
                     f"bq{bq} bk{bk}", fwd_ms=round(f, 3),
                     fwd_bwd_ms=round(fb, 3),
                     fwd_tflops=round(fwd_flops / f / 1e9, 1),
                     fwd_bwd_tflops=round(
                         (fwd_flops + bwd_flops) / fb / 1e9, 1))
            except Exception as e:  # a candidate that does not compile
                line(piece=f"flash stream {layout} bq{bq} bk{bk}",
                     error=f"{type(e).__name__}: {str(e)[:300]}")


def gemm_candidates():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    rng = np.random.default_rng(29)
    drawn = rng.multinomial(4096, np.full(GROUPS, 1.0 / GROUPS))
    drawn[-1] += ROWS - drawn.sum()      # the last group takes the padding
    gs = jnp.asarray(drawn, jnp.int32)
    key = jax.random.PRNGKey(0)
    x_up = jax.random.normal(key, (ROWS, HIDDEN), jnp.bfloat16)
    w_up = jax.random.normal(key, (GROUPS, HIDDEN, WIDTH), jnp.bfloat16)
    x_dn = jax.random.normal(key, (ROWS, WIDTH), jnp.bfloat16)
    w_dn = jax.random.normal(key, (GROUPS, WIDTH, HIDDEN), jnp.bfloat16)

    def clamp(tm, tk, tn):
        return lambda m, k, n: (tm, min(tk, k), min(tn, n))

    candidates = {
        "ragged_dot": lambda x, w, gs: jax.lax.ragged_dot(
            x, w, gs, preferred_element_type=jnp.bfloat16)}
    for name, tiling in (
            ("gmm 512x1024x1024 as it stands", (512, 1024, 1024)),
            ("gmm 512x1024x1024 clamped", clamp(512, 1024, 1024)),
            ("gmm 512x2048x2048 clamped", clamp(512, 2048, 2048)),
            ("gmm 256x1024x1024 clamped", clamp(256, 1024, 1024)),
            ("gmm 512x768x768", clamp(512, 768, 768)),
            ("gmm 512x512x384", clamp(512, 512, 384))):
        candidates[name] = (lambda t: lambda x, w, gs: gmm(
            x, w, gs, jnp.bfloat16, t))(tiling)
    flops = 2.0 * 4096 * HIDDEN * WIDTH      # the rows that land here
    for cname, fn in candidates.items():
        both = jax.jit(jax.grad(
            lambda x, w, gs: jnp.sum(fn(x, w, gs).astype(jnp.float32) ** 2),
            argnums=(0, 1)))
        for label, x, w in (("up", x_up, w_up), ("down", x_dn, w_dn)):
            try:
                f = timed(jax.jit(fn), x, w, gs)
                fb = timed(both, x, w, gs)
                line(candidate=cname, matmul=label, fwd_ms=round(f, 3),
                     fwd_bwd_ms=round(fb, 3),
                     fwd_tflops=round(flops / f / 1e9, 1),
                     fwd_bwd_tflops=round(3 * flops / fb / 1e9, 1))
            except Exception as e:
                line(candidate=cname, matmul=label,
                     error=f"{type(e).__name__}: {str(e)[:300]}")

    # back to token order: the layer's gather against a scatter-add
    ys = jax.random.normal(key, (ROWS, HIDDEN), jnp.bfloat16)
    pairs = TOKENS * TOP_K
    taken = jnp.asarray(rng.choice(pairs, ROWS, replace=False), jnp.int32)
    inv = jnp.full((pairs,), ROWS, jnp.int32).at[taken].set(
        jnp.arange(ROWS, dtype=jnp.int32))
    weights = jax.random.uniform(key, (TOKENS, TOP_K), jnp.float32)

    def by_gather(ys, inv, w):
        padded = jnp.concatenate([ys, jnp.zeros_like(ys[:1])])
        return jnp.einsum("nkh,nk->nh", padded[inv].reshape(
            TOKENS, TOP_K, HIDDEN).astype(jnp.float32), w)

    def by_scatter(ys, taken, w):
        scaled = ys.astype(jnp.float32) * w.reshape(-1)[taken][:, None]
        return jnp.zeros((TOKENS, HIDDEN), jnp.float32).at[
            taken // TOP_K].add(scaled)

    line(piece=f"combine: gather [65536 of {ROWS + 1} rows, 2048] bf16 + sum",
         ms=round(timed(jax.jit(by_gather), ys, inv, weights), 3))
    line(piece=f"combine: scatter-add [{ROWS} -> 8192, 2048] f32",
         ms=round(timed(jax.jit(by_scatter), ys, taken, weights), 3))
    ids = jax.random.randint(key, (pairs,), 0, 256, jnp.int32)
    line(piece="argsort[65536] int32",
         ms=round(timed(jax.jit(jnp.argsort), ids), 3))


def main():
    import jax

    if jax.devices()[0].platform != "tpu":
        print("mla_candidates.py measures on a TPU only", file=sys.stderr)
        return 2
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    if which in ("attention", "both"):
        attention_candidates()
    if which in ("gemm", "both"):
        gemm_candidates()
    return 0


if __name__ == "__main__":
    sys.exit(main())
