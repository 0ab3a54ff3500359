"""``fleet.utils.recompute`` keeps what a kernel inside its block offered
by name (``ops/residuals.py``): the streaming flash kernel's output and
log-sum-exp stay from a block's first forward, so its backward holds ONE
``flash_stream_fwd`` call where a plain ``jax.checkpoint`` holds two, with
the same numbers to the last bit; a block with no such kernel differentiates
to the program it did before there was a policy. On the CPU: the kernels in
the Pallas interpreter, toy widths (latent attention: hidden 64, 4 heads of
24-wide keys and 16-wide values, 64 tokens)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import dispatch
from paddle_tpu.core import random as random_core
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import topology
from paddle_tpu.distributed.fleet.utils import recompute
from paddle_tpu.ops import residuals
from paddle_tpu.ops.attention import scaled_dot_product_attention
from paddle_tpu.text.models import MLAttention

ROWS, SEQ, HIDDEN, HEADS = 2, 64, 64, 4
MODES = ("none", "plain", "kept")
FWD, BWD = "flash_stream_fwd", "flash_stream_bwd_dkv_dq"


@pytest.fixture(autouse=True)
def _kernels_in_the_interpreter():
    saved = topology._GLOBAL_MESH
    topology.set_global_mesh(None)
    paddle.set_flags({"pallas_interpret": True,
                      "pallas_attention_min_seq": 0})
    yield
    paddle.set_flags({"pallas_interpret": False,
                      "pallas_attention_min_seq": 1024})
    topology.set_global_mesh(saved)


class DropoutAttention(nn.Layer):
    """Causal self-attention through the dispatching sdpa with dropout on
    the probabilities: the kernel draws its mask from a seed."""

    def __init__(self, dropout_p):
        super().__init__()
        self.qkv = nn.Linear(HIDDEN, 3 * HIDDEN)
        self.out = nn.Linear(HIDDEN, HIDDEN)
        self.dropout_p = dropout_p

    def forward(self, x):
        b, s, _ = x.shape
        q, k, v = (t.reshape([b, s, HEADS, HIDDEN // HEADS]).transpose(
            [0, 2, 1, 3]) for t in self.qkv(x).chunk(3, axis=-1))
        o = scaled_dot_product_attention(
            q, k, v, dropout_p=self.dropout_p, is_causal=True,
            training=self.training)
        return self.out(o.transpose([0, 2, 1, 3]).reshape([b, s, HIDDEN]))


def latent_attention():
    return MLAttention(HIDDEN, HEADS, 48, 32, 16, 8, 16)


BLOCKS = {"latent": latent_attention,
          "dropout-0.1": lambda: DropoutAttention(0.1),
          "dropout-0": lambda: DropoutAttention(0.0)}


def build(kind):
    paddle.seed(36)
    block = BLOCKS[kind]()
    block.train()
    x = jnp.asarray(np.random.default_rng(36).standard_normal(
        (ROWS, SEQ, HIDDEN)), jnp.float32)
    return block, block.functional_state()[0], x


def loss_fn(block, mode, mesh=None):
    """sum(block(x)^2) of (params, x): the block called plainly, under
    ``jax.checkpoint`` alone, or under ``recompute``."""
    def call(a):
        return block(Tensor(a))._value

    def fn(params, x):
        saved = block.functional_state()
        try:
            with dispatch.trace_mode(), random_core.rng_guard(
                    jax.random.PRNGKey(5)), topology.tracing_for(mesh):
                block.load_functional_state(params, saved[1])
                if mode == "kept":
                    out = recompute(block, Tensor(x))._value
                elif mode == "plain":
                    out = jax.checkpoint(call)(x)
                else:
                    out = call(x)
        finally:
            block.load_functional_state(*saved)
        return jnp.sum(jnp.square(out)), out
    return fn


def grad_of(block, mode, mesh=None):
    return jax.value_and_grad(loss_fn(block, mode, mesh), argnums=(0, 1),
                              has_aux=True)


def kernel_calls(jaxpr):
    """Names of the Pallas calls of a jaxpr, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += kernel_calls(sub)
    return found


def offered_and_kept(offered, kept):
    return {(n, e): count for n in residuals.NAMES
            for e, count in (("offered", offered), ("kept", kept))}


def assert_same_bits(got, want):
    """Two results of ``grad_of``: output, parameters' and input's
    gradients."""
    (_, out), (grads, dx) = want
    (_, out_m), (grads_m, dx_m) = got
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_m))
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dx_m))
    for name in grads:
        np.testing.assert_array_equal(
            np.asarray(grads[name]), np.asarray(grads_m[name]), name)


@pytest.mark.parametrize("kind", list(BLOCKS))
def test_a_recomputed_block_runs_the_forward_kernel_once(
        kind, residual_counts):
    """The gradient's jaxpr: no recompute 1 forward call, a plain
    ``jax.checkpoint`` 2 (the second only to make o and lse again),
    ``recompute`` 1; one backward call each."""
    block, params, x = build(kind)
    for mode, forwards in zip(MODES, (1, 2, 1)):
        before = residual_counts()
        calls = kernel_calls(
            jax.make_jaxpr(grad_of(block, mode))(params, x).jaxpr)
        assert calls.count(FWD) == forwards, (mode, calls)
        assert calls.count(BWD) == 1 and len(calls) == forwards + 1, calls
        # one kernel call offers its two arrays in every mode; only a
        # recompute's policy keeps them
        assert residual_counts(before) == offered_and_kept(
            1, 1 if mode == "kept" else 0), mode


@pytest.mark.parametrize("kind", list(BLOCKS))
def test_kept_residuals_change_no_bit(kind):
    """Outputs and gradients (parameters' and the input's) are the same to
    the last bit with no recompute, under a plain ``jax.checkpoint`` and
    with the residuals kept — with dropout too: the mask comes from the
    same seed in the one forward call as in the two."""
    block, params, x = build(kind)
    got = {mode: jax.jit(grad_of(block, mode))(params, x) for mode in MODES}
    (_, out), (_, dx) = got["none"]
    assert float(jnp.abs(dx).max()) > 0
    for mode in MODES[1:]:
        assert_same_bits(got[mode], got["none"])
    if kind == "dropout-0.1":
        # and the mask is there: without it the numbers differ
        calm, params0, _ = build("dropout-0")
        assert not np.array_equal(
            np.asarray(out), np.asarray(jax.jit(grad_of(calm, "none"))(
                params0, x)[0][1]))


def test_a_block_without_a_kernel_is_checkpointed_as_before(
        monkeypatch, residual_counts):
    """``recompute`` of a segment that holds no offering kernel (the
    ``segment`` of test_distributed.py::test_recompute_util;
    test_kimi_linear_model.py holds the KDA blocks to the same) lowers to
    the program of ``jax.checkpoint`` with no policy, and its policy keeps
    nothing. (What an offer compiles to where nothing keeps it:
    test_mosaic_compile.py.)"""
    paddle.seed(1)
    lin = nn.Linear(4, 4)

    def segment(h):
        return lin(nn.functional.relu(h))

    def lowered():
        def fn(x):
            with dispatch.trace_mode():
                return jnp.sum(recompute(segment, Tensor(x))._value)
        return jax.jit(jax.grad(fn)).lower(jnp.ones((4, 4))).as_text()

    before = residual_counts()
    now = lowered()
    assert not any(residual_counts(before).values())
    monkeypatch.setattr(residuals, "keep_offered", None)
    assert lowered() == now


@pytest.mark.parametrize("mode", ["plain", "kept"])
def test_on_a_mesh_a_recomputed_block_traces_and_matches(
        mode, residual_counts):
    """On an announced dp2 mesh the kernel runs inside ``on_mesh``'s
    ``shard_map``. jax's partial evaluation hands a checkpoint's policy on
    into the map's body, so the residuals are kept there too (they leave
    the map as its outputs); kept or not, the block gives the unrecomputed
    block's numbers."""
    mesh = topology.build_mesh(dp=2, devices=jax.devices()[:2])
    block, params, x = build("latent")
    kept = 1 if mode == "kept" else 0
    before = residual_counts()
    jaxpr = jax.make_jaxpr(grad_of(block, mode, mesh))(params, x)
    assert "shard_map" in str(jaxpr)
    calls = kernel_calls(jaxpr.jaxpr)
    assert calls.count(FWD) == 2 - kept and calls.count(BWD) == 1
    assert residual_counts(before) == offered_and_kept(1, kept)
    assert_same_bits(jax.jit(grad_of(block, mode, mesh))(params, x),
                     jax.jit(grad_of(block, "none", mesh))(params, x))


def test_only_listed_names_are_offered():
    with pytest.raises(ValueError, match="not one of"):
        residuals.offer(jnp.zeros(()), "flash_stream.scores")
