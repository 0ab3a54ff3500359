#!/usr/bin/env python3
"""The held expert path around its gemms, alone on the chip, at the four
held cells' shapes (N tokens, k choices, ``rows`` computed rows, hidden H,
``count`` of ``experts`` held; bf16 rows, float32 weights and sums): what
``incubate/moe.py`` runs now against what it ran until PR 40 and against
the candidates PR 41 chose from.

- ``sum``: rows -> tokens, the weighted sum of ``_combine(held=True)``
  ([rows, H] bf16 -> [N, H] float32), forward and forward + backward (the
  gradients to the rows and to the weights): ``nk_gather`` (until PR 40: a
  gather of all N*k pairs, the zero row for those not held, and an
  ``nkh,nk->nh`` einsum), ``scatter_add`` (candidate i: the weighted rows
  added into their tokens), ``segment_sum`` (candidate i on rows put in
  token order first, the indices sorted), ``shifts`` (candidate ii, what
  ``moe._rows_to_tokens`` is: rows to token order, k static shifts, N rows
  gathered). ``gb_s`` is of the bytes the sum needs: the rows read once,
  the tokens written once.
- ``places``: the dispatch's index work, expert ids [N, k] -> (taken, inv,
  group_sizes, overflow): ``sort_scatter`` (until PR 40: an argsort of the
  N*k group keys and a scatter of N*k positions) against ``prefix_sums``
  (``moe._held_places``).
- ``layer``: everything of a layer call that is not the router or a gemm
  (the experts stand in as the identity): places, the gather into expert
  order, the weighted sum, and their gradients — ``until_pr40`` against
  ``now``. What ``moe_dispatch_ms_per_step`` saves a layer call and pass.

    chiprun -- python3 tools/moe_held_bench.py [sum,places,layer] [cell,...]
        [--ops]

``--ops`` adds each candidate's costliest device operations, from a trace
of three calls.

A microbenchmark's numbers are findings for PERF.md, never a metric of the
benchmark. Exits 2 without a TPU.
"""
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: (N, k, rows, H) as the four held cells run them, and the experts behind
SHAPES = {
    "trinity-mini": dict(n=16384, k=8, rows=32768, h=2048, experts=128,
                         count=16),
    "qwen3-next-80b-a3b": dict(n=16384, k=10, rows=15360, h=2048,
                               experts=512, count=32),
    "kimi-linear-48b-a3b": dict(n=16384, k=8, rows=16384, h=2304,
                                experts=256, count=8),
    "joyai-llm-flash": dict(n=8192, k=8, rows=8192, h=2048, experts=256,
                            count=16),
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


# ------------------------------------------------ what ran until PR 40

def places_by_sort(topi, *, first, count, rows):
    import jax
    import jax.numpy as jnp

    n, k = topi.shape
    local = topi.reshape(-1) - first
    held = (local >= 0) & (local < count)
    group = jnp.where(held, local, count)       # absent experts sort last
    order = jnp.argsort(group).astype(jnp.int32)
    position = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32), unique_indices=True)
    sizes = jnp.sum(jax.nn.one_hot(group, count, dtype=jnp.int32), axis=0)
    overflow = jnp.maximum(jnp.sum(sizes) - rows, 0)
    ends = jnp.minimum(jnp.cumsum(sizes), rows).at[-1].set(rows)
    inv = jnp.where(held & (position < rows), position, rows)
    return order[:rows], inv, jnp.diff(ends, prepend=0), overflow


def _nk_gather():
    """The weighted sum and the dispatch gather as they stood: both
    token-side sums over all N*k pairs, custom gradients on the rows."""
    import functools

    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def weighted_sum(ys, topv, taken, inv):
        n, k = topv.shape
        by_token = jnp.concatenate([ys, jnp.zeros_like(ys[:1])])[
            inv].reshape(n, k, -1)
        return jnp.einsum("nkh,nk->nh", by_token.astype(jnp.float32),
                          topv.astype(jnp.float32))

    def fwd(ys, topv, taken, inv):
        return weighted_sum(ys, topv, taken, inv), (ys, topv, taken, inv)

    def bwd(res, g):
        ys, topv, taken, inv = res
        n, k = topv.shape
        here = inv[taken] < taken.shape[0]
        g_rows = g[taken // k].astype(jnp.float32)
        weights = topv.reshape(-1)[taken].astype(jnp.float32)
        d_ys = jnp.where(here[:, None], weights[:, None] * g_rows, 0.0)
        d_weights = jnp.where(here, jnp.sum(
            ys.astype(jnp.float32) * g_rows, axis=-1), 0.0)
        d_topv = jnp.zeros((n * k,), jnp.float32).at[taken].set(
            d_weights, unique_indices=True)
        return (d_ys.astype(ys.dtype),
                d_topv.reshape(n, k).astype(topv.dtype), None, None)

    weighted_sum.defvjp(fwd, bwd)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def to_held_order(x, taken, inv, k):
        return x[taken // k]

    def order_fwd(x, taken, inv, k):
        return x[taken // k], inv

    def order_bwd(k, inv, g):
        padded = jnp.concatenate([g, jnp.zeros_like(g[:1])])
        by_token = padded[inv].reshape(inv.shape[0] // k, k, g.shape[-1])
        return (jnp.sum(by_token.astype(jnp.float32), axis=1).astype(
            g.dtype), None, None)

    to_held_order.defvjp(order_fwd, order_bwd)
    return weighted_sum, to_held_order


# ------------------------------------------- candidate (i): scatter-add

def _filled(taken, inv):
    import jax.numpy as jnp

    rows = taken.shape[0]
    return jnp.arange(rows) < jnp.sum(inv < rows)


def sum_by_scatter_add(ys, topv, taken, inv):
    import jax.numpy as jnp

    n, k = topv.shape
    scaled = ys.astype(jnp.float32) * topv.reshape(-1)[taken][:, None]
    token = jnp.where(_filled(taken, inv), taken // k, n)    # n: dropped
    return jnp.zeros((n, ys.shape[-1]), jnp.float32).at[token].add(
        scaled, mode="drop")


def sum_by_segment_sum(ys, topv, taken, inv):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate import moe

    n, k = topv.shape
    row_at, token_at, _, _ = moe._token_major(taken, inv, k)
    weights = topv.reshape(-1)[taken][row_at]
    scaled = ys[row_at].astype(jnp.float32) * weights[:, None]
    return jax.ops.segment_sum(scaled, token_at, num_segments=n + 1,
                               indices_are_sorted=True)[:n]


def _inputs(shape, seed=41):
    import jax
    import jax.numpy as jnp

    n, k, rows, h = shape["n"], shape["k"], shape["rows"], shape["h"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    topi = jax.lax.top_k(jax.random.normal(
        keys[0], (n, shape["experts"]), jnp.float32), k)[1].astype(jnp.int32)
    topv = jax.nn.softmax(jax.random.normal(keys[1], (n, k), jnp.float32))
    x = jax.random.normal(keys[2], (n, h), jnp.bfloat16)
    ys = jax.random.normal(keys[3], (rows, h), jnp.bfloat16)
    g = jax.random.normal(keys[4], (n, h), jnp.float32)
    return topi, topv, x, ys, g


def _apart(got, want):
    import jax
    import jax.numpy as jnp

    def one(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.abs(a - b).max() / jnp.maximum(
            jnp.abs(b).max(), 1e-30))

    return [float(f"{one(a, b):.3g}") for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))]


def _top_ops(fn, args, calls=3, n=10):
    """The device operations with most time in ``calls`` traced calls of a
    jitted ``fn``: [[label, ms a call], ...]."""
    import jax

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from harness import tracing, xplane

    trace_dir = os.path.join(ROOT, ".benchmark_out", "moe_held_bench")
    tracing.start(trace_dir)
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    trace = tracing.stop_and_load(trace_dir)
    return [[label, round(1e3 * seconds / calls, 3)]
            for label, seconds in xplane.top_ops(trace, n)]


def _report(piece, cell, name, fwd, both, args, need_bytes, against,
            ops=False):
    """Times ``fwd`` and ``both`` (forward + backward) on ``args``; a
    candidate that does not compile or fit says so and the rest go on.
    ``ops``: also the costliest device operations of a call, traced."""
    import jax

    last = jax.jit(both if both is not None else fwd)
    try:
        f = timed(jax.jit(fwd), *args)
        fb = timed(last, *args) if both is not None else None
        got = last(*args)
    except Exception as e:
        line(piece=piece, cell=cell, candidate=name,
             error=f"{type(e).__name__}: {str(e)[:300]}")
        return None
    out = dict(piece=piece, cell=cell, candidate=name, fwd_ms=round(f, 3))
    if fb is not None:
        out["fwd_bwd_ms"] = round(fb, 3)
    if need_bytes:
        out["fwd_gb_s"] = round(need_bytes / f / 1e6, 1)
    if against is not None:
        out["apart_from_first"] = _apart(got, against)
    line(**out)
    if ops:
        line(piece=piece, cell=cell, candidate=name,
             ops_ms_a_call=_top_ops(last, args))
    return got


def bench_sum(cell, shape, ops=False):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate import moe

    topi, topv, _, ys, g = _inputs(shape)
    taken, inv, _, _ = jax.jit(moe._held_places, static_argnames=(
        "first", "count", "rows"))(topi, first=0, count=shape["count"],
                                   rows=shape["rows"])
    need = shape["rows"] * shape["h"] * 2 + shape["n"] * shape["h"] * 4
    first = None
    for name, fn in (("nk_gather", _nk_gather()[0]),
                     ("scatter_add", sum_by_scatter_add),
                     ("segment_sum", sum_by_segment_sum),
                     ("shifts", moe._held_weighted_sum)):
        def both(ys, topv, taken, inv, g):
            return jax.value_and_grad(lambda ys, topv: jnp.sum(
                fn(ys, topv, taken, inv) * g), argnums=(0, 1))(ys, topv)

        def fwd(ys, topv, taken, inv, g):
            return fn(ys, topv, taken, inv)

        got = _report("sum", cell, name, fwd, both,
                      (ys, topv, taken, inv, g), need, first, ops)
        first = got if first is None else first


def bench_places(cell, shape):
    import functools

    from paddle_tpu.incubate import moe

    topi = _inputs(shape)[0]
    kw = dict(first=0, count=shape["count"], rows=shape["rows"])
    first = None
    for name, fn in (("sort_scatter", places_by_sort),
                     ("prefix_sums", moe._held_places)):
        got = _report("places", cell, name, functools.partial(fn, **kw),
                      None, (topi,), 0, None)
        if got is not None:
            # the filled rows and every pair's row have to be the same;
            # rows that no held pair fills may hold any pair
            import numpy as np

            filled = int(np.sum(np.asarray(got[1]) < shape["rows"]))
            same = first is None or (
                np.array_equal(got[0][:filled], first[0][:filled])
                and all(np.array_equal(a, b) for a, b in zip(
                    got[1:], first[1:])))
            line(piece="places", cell=cell, candidate=name, filled=filled,
                 overflow=int(got[3]), same_as_first=bool(same))
            first = got if first is None else first


def bench_layer(cell, shape, ops=False):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate import moe

    topi, topv, x, _, g = _inputs(shape)
    kw = dict(first=0, count=shape["count"], rows=shape["rows"])
    old_sum, old_order = _nk_gather()
    first = None
    for name, places, to_held, weighted in (
            ("until_pr40", places_by_sort, old_order, old_sum),
            ("now", moe._held_places, moe._rows_to_held_order,
             moe._held_weighted_sum)):
        def fwd(x, topv, topi, g):
            taken, inv, _, _ = places(topi, **kw)
            xs = to_held(x, taken, inv, shape["k"])
            return weighted(xs, topv, taken, inv)     # identity experts

        def both(x, topv, topi, g):
            return jax.value_and_grad(lambda x, topv: jnp.sum(
                fwd(x, topv, topi, g) * g), argnums=(0, 1))(x, topv)

        got = _report("layer", cell, name, fwd, both, (x, topv, topi, g), 0,
                      first, ops)
        first = got if first is None else first


def main():
    import jax

    if jax.devices()[0].platform != "tpu":
        print("moe_held_bench.py times the chip: no TPU", file=sys.stderr)
        return 2
    args = [a for a in sys.argv[1:] if a != "--ops"]
    pieces = (args[0] if args else "sum,places,layer").split(",")
    cells = args[1].split(",") if len(args) > 1 else list(SHAPES)
    ops = "--ops" in sys.argv
    for cell in cells:
        line(cell=cell, **SHAPES[cell])
        for piece in pieces:
            if piece == "places":
                bench_places(cell, SHAPES[cell])
            else:
                {"sum": bench_sum, "layer": bench_layer}[piece](
                    cell, SHAPES[cell], ops)
    return 0


if __name__ == "__main__":
    sys.exit(main())
