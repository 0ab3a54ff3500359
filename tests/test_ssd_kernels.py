"""The selective state-space scan's Mosaic kernels (``ops/pallas/ssd.py``:
``ssd_chunk_fwd`` / ``ssd_chunk_bwd`` under one ``jax.custom_vjp``) in the
Pallas interpreter on the CPU, on the streams a layer hands them — x [B, T,
H P], dt [B, T, H], B and C [B, T, G N] — against the recurrence over tokens
(``ops.linear_attention.ssd_recurrent``, float32) and against the chunked XLA
scan: y and every gradient (x, dt, A, B, C, D), at a length that is no
multiple of the token block, one group and two, values of 64 and of 128, a
head group a program and several; bf16 operands with float32 inside; no
positive exponent; a row that starts from nothing; and which shapes the
kernels take."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import linear_attention as la
from paddle_tpu.ops.pallas import ssd as kernels

D_STATE = 128


@pytest.fixture(autouse=True)
def _float32_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def inputs(seed, rows, seq, heads, d_head, groups, dtype=jnp.float32,
           strong=False):
    """x, dt (after its softplus), A < 0, B, C of ``groups`` groups and D,
    as heads. ``strong``: A down to -e^5, so a chunk's exp(-G) overflows."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (normal(rows, seq, heads, d_head).astype(dtype),
            jax.nn.softplus(normal(rows, seq, heads) - 1.0),
            -jnp.exp(jnp.asarray(rng.uniform(0.0, 5.0 if strong else 2.7,
                                             heads), jnp.float32)),
            normal(rows, seq, groups, D_STATE).astype(dtype),
            normal(rows, seq, groups, D_STATE).astype(dtype), normal(heads))


def kernel_scan(x, dt, a, b, c, d, **sizes):
    """The kernels as the op calls them: streams in, a stream out, viewed
    as heads again for the comparison."""
    rows, seq = x.shape[:2]
    y = kernels.ssd(x.reshape(rows, seq, -1), dt, a, b.reshape(rows, seq, -1),
                    c.reshape(rows, seq, -1), d, groups=b.shape[2],
                    interpret=True, **sizes)
    return y.reshape(x.shape)


def weighted(fn):
    """value and the six gradients of a loss that tells positions and
    features apart."""
    def of(*a):
        o = fn(*a).astype(jnp.float32)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size, dtype=jnp.float32)
                                   .reshape(o.shape)))
    return jax.jit(jax.value_and_grad(of, argnums=tuple(range(6))))


def close(got, want, rtol):
    got, want = jnp.asarray(got), jnp.asarray(want)
    scale = float(jnp.abs(want).max())
    assert bool(jnp.isfinite(got).all())
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert err <= rtol * scale, (err, scale)


# Float32 both ways the kernels are the recurrence's arithmetic in another
# order (chunks, the mask, three-term sums of the decay): the chunked scan's
# own tolerances (tests/test_granite_hybrid_model.py).
CASES = {
    # seq, heads, d_head, groups, chunk, tokens, lanes, against
    "ragged-one-program-of-heads": (150, 16, 64, 1, 32, 64, 1024,
                                    "recurrent"),
    "ragged-two-programs-of-heads": (150, 16, 64, 1, 32, 64, 512, "chunked"),
    "two-groups": (100, 16, 64, 2, 32, 64, 1024, "recurrent"),
    "values-of-128": (70, 16, 128, 1, 32, 64, 1024, "recurrent"),
    "values-of-128-two-groups": (70, 16, 128, 2, 32, 32, 1024, "chunked"),
    "whole-blocks-all-heads": (128, 8, 64, 1, 64, 128, 1024, "recurrent"),
    "values-of-32": (40, 8, 32, 1, 16, 32, 1024, "chunked"),
    "strong-decay": (96, 8, 64, 1, 32, 64, 1024, "recurrent"),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_are_the_scan_forward_and_backward(case):
    seq, heads, d_head, groups, chunk, tokens, lanes, against = CASES[case]
    args = inputs(len(case), 2, seq, heads, d_head, groups,
                  strong=case == "strong-decay")
    other = {"recurrent": lambda *a: la.ssd_recurrent(*a)[0],
             "chunked": lambda *a: la.ssd_chunked(*a, chunk=16,
                                                  segment=64)[0]}[against]

    def scan(*a):
        return kernel_scan(*a, chunk=chunk, tokens=tokens, lanes=lanes)

    want_y = other(*args)
    got_y = jax.jit(scan)(*args)
    assert got_y.shape == want_y.shape and got_y.dtype == want_y.dtype
    close(got_y, want_y, 2e-5)
    _, want = weighted(other)(*args)
    _, got = weighted(scan)(*args)
    for name, g, w in zip("x dt a b c d".split(), got, want):
        assert float(jnp.abs(w).max()) > 0, name
        assert g.shape == w.shape and g.dtype == w.dtype, name
        close(g, w, 5e-5)


def test_the_kernels_take_bf16_operands_and_keep_float32_inside():
    """bf16 x, B and C, float32 dt, A and D, as amp O1 hands them over: y,
    dx, dB and dC take bf16, d dt, dA and dD float32, and all of them are a
    bf16 rounding off the float32 recurrence on the same (rounded) inputs;
    the kept entering states are float32 and the large products take bf16
    operands into a float32 sum."""
    args = inputs(8, 1, 150, 16, 64, 1, dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    want_y = la.ssd_recurrent(*exact)[0]
    _, want = weighted(lambda *a: la.ssd_recurrent(*a)[0])(*exact)

    def scan(*a):
        return kernel_scan(*a, chunk=32, tokens=64)

    got_y = scan(*args)
    _, got = weighted(scan)(*args)
    assert got_y.dtype == jnp.bfloat16
    close(got_y, want_y, 3e-2)
    for name, g, w in zip("x dt a b c d".split(), got, want):
        assert g.dtype == (jnp.bfloat16 if name in "xbc" else jnp.float32)
        close(g, w, 3e-2)
    x, dt, a, b, c, d = args
    rows = jnp.pad(jnp.swapaxes(dt, 1, 2), ((0, 0), (0, 0), (0, 42)))

    def padded(t):
        return jnp.pad(t.reshape(1, 150, -1), ((0, 0), (0, 42), (0, 0)))

    _, s0 = kernels._forward(
        padded(x), rows, rows * a[None, :, None], padded(b), padded(c),
        jnp.ones((1, 1, 16 * 64)), chunk=32, tokens=64, together=16,
        groups=1, keep=True, interpret=True)
    assert s0.dtype == jnp.float32 and s0.shape == (1, 1, 6, D_STATE, 1024)
    assert float(jnp.abs(s0[0, 0, 0]).max()) == 0.0     # a row's start
    assert float(jnp.abs(s0[0, 0, 1]).max()) > 0.0
    jaxpr = str(jax.make_jaxpr(scan)(*args))
    assert "bf16" in jaxpr and "preferred_element_type=float32" in jaxpr


def test_no_exponent_in_the_kernels_is_positive():
    """Every ``exp`` a chunk's terms and the heads' masks take — what both
    bodies build — on a decay that overflows a factorisation into exp(G_r)
    exp(-G_i): its argument is never above zero, and off the triangle the
    mask is exactly 0."""
    x, dt, a, b, c, _ = inputs(9, 1, 64, 8, 64, 1, strong=True)
    dt = dt * 8.0
    rows = jnp.swapaxes(dt, 1, 2)[0]
    seen = []
    real = jnp.exp

    def exp(v):
        seen.append(float(jnp.max(v)))
        return real(v)

    try:
        jnp.exp = exp
        terms = kernels._chunk_terms(x[0].reshape(64, -1), rows,
                                     rows * a[:, None], b[0, :, 0],
                                     c[0, :, 0], 8)
        masks = [kernels._mask(terms, head, 64) for head in range(8)]
    finally:
        jnp.exp = real
    assert len(seen) == 3 + 8 and max(seen) <= 0.0
    assert float(terms["cum"].min()) < -1000        # exp(-G) would be inf
    upper = np.triu(np.ones((64, 64), bool), 1)
    for mask in masks:
        assert float(jnp.abs(jnp.where(upper, mask, 0.0)).max()) == 0.0
        np.testing.assert_array_equal(np.diag(np.asarray(mask)), 1.0)


def test_a_row_starts_from_a_zero_state_whatever_the_row_before_held():
    """Two rows a call, several token blocks each: the second row's y and
    gradients are those of the row alone — the state in VMEM scratch (and
    the backward's ``dS``) is zeroed at a row's first block."""
    args = inputs(10, 2, 100, 8, 64, 1)

    def scan(*a):
        return kernel_scan(*a, chunk=32, tokens=32)

    def second(t):
        return t[1:] if t.ndim > 1 else t

    alone = tuple(second(t) for t in args)
    np.testing.assert_array_equal(np.asarray(scan(*args)[1:]),
                                  np.asarray(scan(*alone)))

    def loss(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a)[-1] ** 2), argnums=(0, 1))

    for g, w in zip(loss(scan)(*args), loss(scan)(*alone)):
        assert float(jnp.abs(g[0]).max()) == 0.0   # nothing reaches row 0
        np.testing.assert_allclose(np.asarray(g[1:]), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("heads, groups, d_head, lanes, together", [
    (64, 1, 64, None, 16),      # granite-4.0-h-micro: 1,024 lanes a program
    (64, 1, 64, 512, 8),
    (64, 8, 64, None, 8),       # a group's 8 heads: one block of B and C
    (64, 16, 64, None, 0),      # 4 heads a group: no 8-row tile of dt
    (128, 1, 128, None, 8),
    (4, 1, 256, None, 4),       # all the heads
    (24, 1, 64, None, 8),
    (6, 1, 64, None, 6),
    (12, 1, 64, 256, 0),        # 4 do not fill a tile, 12 are past 256 lanes
])
def test_the_head_cut_keeps_a_group_and_a_tile_whole(heads, groups, d_head,
                                                     lanes, together):
    assert kernels.heads_together(heads, groups, d_head, lanes) == together


@pytest.mark.parametrize("heads, groups, d_head, d_state, dtype, takes", [
    (64, 1, 64, 128, jnp.bfloat16, True),
    (64, 8, 128, 256, jnp.bfloat16, True),
    (64, 1, 64, 128, jnp.float32, False),     # the XLA path's
    (64, 1, 64, 64, jnp.bfloat16, False),     # half a lane group of state
    (64, 1, 96, 128, jnp.bfloat16, False),    # values across lane groups
    (64, 3, 64, 128, jnp.bfloat16, False),
    (8, 1, 16, 32, jnp.bfloat16, False),      # the tests' toy mixer
])
def test_which_scans_the_kernels_take(heads, groups, d_head, d_state, dtype,
                                      takes):
    assert kernels.supported(heads, groups, d_head, d_state, dtype) == takes


def test_a_cut_that_serves_no_head_is_refused():
    x, dt, a, b, c, d = inputs(11, 1, 32, 12, 64, 1)
    with pytest.raises(ValueError, match="no head cut"):
        kernels.ssd(x.reshape(1, 32, -1), dt, a, b.reshape(1, 32, -1),
                    c.reshape(1, 32, -1), d, lanes=256, interpret=True)
