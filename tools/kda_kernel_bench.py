#!/usr/bin/env python3
"""The gated delta rule's scan alone on the chip, at the Kimi-Linear cell's
shape (b1 h32 s16384 d128, bf16 q, k, v and float32 decay): the Mosaic
kernels (``paddle_tpu.ops.pallas.linear_attention``) against the XLA scan
(``ops.linear_attention.kda_chunked``), forward and forward + backward,
at the blocks tried (tokens x heads a program), with the compiled
program's temporary bytes and how far the two are apart.

    chiprun -- python3 tools/kda_kernel_bench.py [256x2,512x1,...]

``--layer [kda|gdn]`` times ONE linear-attention layer between its
projections instead — the stages of ``text/models.py`` and the scan as a
train step runs them (``KimiDeltaAttention`` at the Kimi-Linear cell's
shape, ``GatedDeltaNet`` at the Qwen3-Next cell's 16 key / 32 value heads),
from the projections' bf16 outputs to ``o_proj``'s input: the whole, then
each stage alone, forward and forward + backward, with the compiled
program's bytes accessed and temporaries; the convolution stage on both
of its paths (``ops.linear_attention.conv_path``: the Mosaic kernels the
layer runs here, and the XLA stage they replaced), so that before and
after share a chip call. The before-number for work on this layer (a fused
projection) that is not the whole cell:

    chiprun -- python3 tools/kda_kernel_bench.py --layer [kda|gdn]

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


#: key heads of the ``gdn`` layer (Qwen3-Next: 16 serve the 32 value heads)
GDN_KEY_HEADS = 16


def _conv_on_both_paths(stage, args):
    """The convolution stage's two lines: ``stage`` held to each path
    while it is traced, whatever ``conv_path`` would choose here."""
    from paddle_tpu.ops import linear_attention as la

    def on(path):
        def held(*args):
            chosen, la.conv_path = la.conv_path, lambda *_: path
            try:
                return stage(*args)
            finally:
                la.conv_path = chosen

        return held

    return [(f"conv, {path}", on(path), args) for path in ("kernel", "xla")]


def _layer_stages(kind):
    """``[(stage, fn, args)]`` of one layer: its stages in order, each on
    inputs as the stage before leaves them (drawn, not computed: a stage's
    time does not depend on the values), and ``layer`` = all of them
    chained from the projections' outputs."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import linear_attention as la
    from paddle_tpu.ops.pallas import linear_attention as kernels
    from paddle_tpu.text import models

    bf16, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(39), 16))

    def normal(*shape, dtype=bf16, scale=1.0):
        return (jax.random.normal(next(keys), shape, f32) * scale).astype(
            dtype)

    wide = HEADS * D
    stream = (BATCH, SEQ, wide)
    beta = jax.nn.sigmoid(normal(BATCH, SEQ, HEADS, dtype=f32))
    gate, w_norm = normal(*stream), 1.0 + normal(D, dtype=f32, scale=0.1)
    a_log = jnp.log(jax.random.uniform(next(keys), (HEADS,), f32, 1., 16.))
    if kind == "kda":
        taps = [normal(4, wide, scale=0.5) for _ in range(3)]
        low, w_up = normal(BATCH, SEQ, D), normal(D, wide, scale=D ** -0.5)
        dt_bias = normal(wide, dtype=f32)

        def conv(q, k, v, *taps):
            return models._kda_streams(q, k, v, *taps, heads=HEADS, eps=1e-6)

        def decay(low, w_up, a_log, dt_bias):
            return models._kda_decay(low, w_up, a_log, dt_bias, heads=HEADS)

        def out(o, gate, w):
            return models._kda_gated_norm(o, gate, w, heads=HEADS, eps=1e-5)

        def layer(q, k, v, taps, low, w_up, a_log, dt_bias, beta, gate, w):
            q, k, v = conv(q, k, v, *taps)
            g = decay(low, w_up, a_log, dt_bias)
            return out(kernels.kda(q, k, v, g, beta), gate, w)

        raw = [normal(*stream) for _ in range(3)]
        q, k, v = conv(*raw, *taps)
        g = decay(low, w_up, a_log, dt_bias)
        stages = _conv_on_both_paths(lambda *a: sum(conv(*a)),
                                     (*raw, *taps))
        stages += [("decay", decay, (low, w_up, a_log, dt_bias))]
        whole = (*raw, taps, low, w_up, a_log, dt_bias, beta, gate, w_norm)
    else:
        per_key = HEADS // GDN_KEY_HEADS
        key = GDN_KEY_HEADS * D
        mixed, taps = normal(BATCH, SEQ, 2 * key + wide), normal(
            4, 2 * key + wide, scale=0.5)
        a, dt_bias = normal(BATCH, SEQ, HEADS), normal(HEADS, dtype=f32)

        def conv(mixed, taps):
            return models._gdn_streams(mixed, taps, key_heads=GDN_KEY_HEADS,
                                       d_k=D, eps=1e-6)

        def repeat(q, k):
            return tuple(models._repeat_head_lanes(
                x, heads=GDN_KEY_HEADS, repeats=per_key) for x in (q, k))

        def out(o, gate, w):
            return models._gdn_gated_norm(o, gate, w, heads=HEADS, eps=1e-6)

        def layer(mixed, taps, a, a_log, dt_bias, beta, gate, w):
            q, k, v = conv(mixed, taps)
            g = models._gdn_decay(a, a_log, dt_bias)
            return out(kernels.kda(*repeat(q, k), v, g, beta), gate, w)

        q, k, v = conv(mixed, taps)
        g = models._gdn_decay(a, a_log, dt_bias)
        stages = _conv_on_both_paths(
            lambda *a: jnp.concatenate(conv(*a), -1), (mixed, taps))
        stages += [("repeat", lambda *a: sum(repeat(*a)), (q, k))]
        q, k = repeat(q, k)
        whole = (mixed, taps, a, a_log, dt_bias, beta, gate, w_norm)

    def norm_on_streams(x):
        return x * la.head_rsqrt(x, HEADS, eps=1e-6, mean=True)

    def norm_on_the_head_view(x):
        xh = x.reshape(BATCH, SEQ, HEADS, D)
        return (xh * jax.lax.rsqrt(jnp.mean(xh * xh, axis=-1, keepdims=True)
                                   + 1e-6)).reshape(x.shape)

    # the per-head statistic alone, float32 in and out: the two 0/1
    # products of ``head_rsqrt`` against the [.., H, d] view they replace
    x = normal(*stream, dtype=f32)
    stages += [("core", kernels.kda, (q, k, v, g, beta)),
               ("out", out, (normal(*stream), gate, w_norm)),
               ("norm alone, on streams", norm_on_streams, (x,)),
               ("norm alone, on the head view", norm_on_the_head_view, (x,))]
    return [("layer", layer, whole)] + stages


def layer_mode(kind):
    """One line a stage: forward and forward + backward (every floating
    input differentiated, as a train step does)."""
    import jax
    import jax.numpy as jnp

    line(device=jax.devices()[0].device_kind, layer=kind, batch=BATCH,
         seq=SEQ, heads=HEADS, d=D,
         key_heads=GDN_KEY_HEADS if kind == "gdn" else HEADS)
    for stage, fn, args in _layer_stages(kind):
        both = jax.jit(jax.grad(
            lambda *a, fn=fn: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
            argnums=tuple(range(len(args)))))
        # the executable itself is timed: calling ``both`` compiles again
        compiled = both.lower(*args).compile()
        cost = compiled.cost_analysis() or {}
        line(stage=stage, fwd_ms=round(timed(jax.jit(fn), *args), 3),
             fwd_bwd_ms=round(timed(compiled, *args), 3),
             fwd_bwd_accessed_gb=round(cost.get("bytes accessed", 0) / 1e9,
                                       3),
             fwd_bwd_temp_gb=round(
                 compiled.memory_analysis().temp_size_in_bytes / 1e9, 3))
    return 0


def main():
    import jax
    from paddle_tpu.ops import linear_attention as la
    from paddle_tpu.ops.pallas import linear_attention as kernels

    if jax.devices()[0].platform != "tpu":
        print("kda_kernel_bench.py measures on a TPU only", file=sys.stderr)
        return 2
    if sys.argv[1:2] == ["--layer"]:
        return layer_mode(sys.argv[2] if len(sys.argv) > 2 else "kda")
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
