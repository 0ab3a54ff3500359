#!/usr/bin/env python3
"""The grouped matmul's tile for an operand that the table's tile does not
divide, alone on the chip: ``incubate.moe._gmm_tiling``'s candidates at the
held cells' expert gemms whose hidden size or expert width is wider than
the 1,024 tile and no multiple of it — LFM2's width 1,792 = 7 x 256 (rows
65,536 in 8 groups: ``held_rows_factor`` 2.0 over 4,096 rows an expert) and
Kimi-Linear's hidden size 2,304 = 9 x 256 (rows 16,384 in 8 groups) — one
gemm [k -> n] at a time, forward and forward + backward (the backward's
calls see k and n swapped, so a candidate is a FUNCTION of each call's
operand, as the layer's answer is).

    chiprun -- python3 tools/gmm_tile_bench.py [lfm2,kimi]

Candidates, by what an operand wider than the tile takes: ``masked`` (the
tile itself, the kernel masking its last, part-empty tile: what the layer
ran until PR 44), the lane-multiple divisors of the operand up to the tile
(896 and 256 of 1,792; 768 and 256 of 2,304). Group sizes are drawn as a
router at initialisation gives them, the last group taking the buffer's
unfilled rows as ``_held_places`` gives it.

A microbenchmark's numbers are findings for PERF.md, never a metric of the
benchmark. Exits 2 without a TPU.
"""
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: rows of the held buffer, groups, pairs that fill it on average, hidden
#: size, expert width, and the candidate tiles of the operand at fault
SHAPES = {
    "lfm2": dict(rows=65536, groups=8, filled=32768, hidden=2048,
                 width=1792, tiles=(896, 256)),
    "kimi": dict(rows=16384, groups=8, filled=8192, hidden=2304,
                 width=1024, tiles=(768, 256)),
}
ROW_TILE, TILE = 512, 1024


def _the_benchmarks_clock():
    """``benchmark/tools/kda_candidates.py``'s ``timed`` and ``line``: the
    clock the other kernel benches' numbers were taken with."""
    spec = importlib.util.spec_from_file_location(
        "kda_candidates", os.path.join(ROOT, "benchmark", "tools",
                                       "kda_candidates.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.timed, module.line


def tiling(tile):
    """A candidate as the kernel's table form: an operand up to the table's
    tile is its own tile, a wider one that the tile divides takes it, and
    one it does not divide takes ``tile`` (None: the table's, masked)."""
    def pick(size):
        if size <= TILE or size % TILE == 0 or tile is None:
            return min(TILE, size)
        return tile

    return lambda m, k, n: (ROW_TILE, pick(k), pick(n))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("gmm_tile_bench.py measures on a TPU only", file=sys.stderr)
        return 2
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    timed, line = _the_benchmarks_clock()
    wanted = sys.argv[1].split(",") if len(sys.argv) > 1 else list(SHAPES)
    key = jax.random.PRNGKey(44)
    for cell in wanted:
        shape = SHAPES[cell]
        rows, groups = shape["rows"], shape["groups"]
        rng = np.random.default_rng(44)
        sizes = rng.multinomial(shape["filled"],
                                rng.dirichlet(np.full(groups, 16.0)))
        sizes[-1] += rows - shape["filled"]
        sizes = jnp.asarray(sizes, jnp.int32)
        for label, k, n in (("up", shape["hidden"], shape["width"]),
                            ("down", shape["width"], shape["hidden"])):
            x = jax.random.normal(key, (rows, k), jnp.bfloat16)
            w = jax.random.normal(key, (groups, k, n), jnp.bfloat16) * 0.02
            flops = 2.0 * rows * k * n
            for tile in (None,) + shape["tiles"]:
                def fn(x, w, gs, tile=tile):
                    return gmm(x, w, gs, jnp.bfloat16, tiling(tile))

                # a loss that needs the forward's result, or XLA drops the
                # forward gemm (benchmark/tools/moe_candidates.py)
                both = jax.jit(jax.grad(lambda x, w, gs: jnp.sum(
                    fn(x, w, gs).astype(jnp.float32) ** 2), argnums=(0, 1)))
                try:
                    f = timed(jax.jit(fn), x, w, sizes)
                    fb = timed(both, x, w, sizes)
                    line(cell=cell, matmul=f"{label} [{k} -> {n}]",
                         tile="masked" if tile is None else tile,
                         fwd_ms=round(f, 3), fwd_bwd_ms=round(fb, 3),
                         fwd_tflops=round(flops / f / 1e9, 1),
                         fwd_bwd_tflops=round(3 * flops / fb / 1e9, 1))
                except Exception as e:  # a candidate that does not compile
                    line(cell=cell, matmul=label, tile=tile,
                         error=f"{type(e).__name__}: {str(e)[-300:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
