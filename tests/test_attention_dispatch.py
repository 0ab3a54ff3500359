"""The attention gate (ops/attention.py ``attention_route``): one function of
what it can observe, three routes.

- ``short``: unmasked, non-causal self-attention whose keys fit one tile
  (seq a multiple of 128 up to 512, heads tiling the 128 lanes), offered
  packed by ``packed_self_attention`` — the whole-sequence kernel reads
  the fused projection in place. BERT's seq-128 cells run it.
- ``stream``: long keys (seq_k >= pallas_attention_min_seq, or a
  min_seq^2 logits product with at least 512 keys) — the streaming flash
  kernel, one head per program; at seq 128 that shape measured 3x slower
  than XLA on the v5e, which is why short sequences never take it.
- ``xla``: everything masked, causal-and-short, cross-attention, odd head
  sizes, long-q/short-k, and every run off the TPU without
  ``pallas_interpret``.

Pinned here with the counter that records each decision, and what happens
when a selected kernel fails: it raises (no route falls back to another).
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import dispatch, flags
from paddle_tpu.ops import attention, placement


@pytest.fixture(autouse=True)
def _seed():
    paddle.seed(0)
    # the dispatch layer caches the jitted op per (name, shape); evict so
    # each test's monkeypatched kernel is actually (re)traced
    dispatch.evict_ops("flash_attention")
    dispatch.evict_ops("short_attention")
    dispatch.evict_ops("sdpa")


@pytest.fixture
def track_kernel(monkeypatch):
    """Count flash-kernel entries without changing its output."""
    from paddle_tpu.ops.pallas import flash_attention

    calls = []
    real = flash_attention.mha

    def spy(*args, **kwargs):
        calls.append(kwargs.get("causal"))
        return real(*args, **kwargs)

    monkeypatch.setattr(flash_attention, "mha", spy)
    return calls


@pytest.fixture
def track_short(monkeypatch):
    """Count whole-sequence-kernel entries without changing its output."""
    from paddle_tpu.ops.pallas import flash_attention

    calls = []
    real = flash_attention.mha_packed

    def spy(*args, **kwargs):
        calls.append(kwargs.get("dropout_p"))
        return real(*args, **kwargs)

    monkeypatch.setattr(flash_attention, "mha_packed", spy)
    return calls


@pytest.fixture(autouse=True)
def _interpret_kernels():
    # the kernel is selected on a TPU, or when the interpreter is asked
    # for explicitly — tests run on CPU
    paddle.set_flags({"pallas_interpret": True})
    yield
    paddle.set_flags({"pallas_interpret": False})


def _qkv(sq, sk, d=16, dv=None):
    rng = np.random.RandomState(0)
    return (jnp.asarray(rng.randn(1, 2, sq, d), jnp.float32),
            jnp.asarray(rng.randn(1, 2, sk, d), jnp.float32),
            jnp.asarray(rng.randn(1, 2, sk, dv or d), jnp.float32))


def _routes():
    return {r: attention._ROUTE_TOTAL.value(route=r)
            for r in ("short", "stream", "xla")}


def _packed(seq, heads=2, head_dim=64, batch=2):
    rng = np.random.RandomState(1)
    return paddle.to_tensor(
        rng.randn(batch, seq, 3 * heads * head_dim).astype(np.float32))


# (case, entry point, its arguments, the route). "packed" calls
# packed_self_attention(qkv[seq, heads, head_dim], ...); "qkv" calls
# scaled_dot_product_attention on [1, 2, seq_q | seq_k, 16].
_ROUTE_CASES = [
    ("short-unmasked", "packed", dict(seq=128), "short"),
    ("short-256-head128", "packed", dict(seq=256, heads=1, head_dim=128),
     "short"),
    ("short-masked", "packed", dict(seq=128, mask=True), "xla"),
    ("short-causal", "packed", dict(seq=128, is_causal=True), "xla"),
    ("short-odd-head-dim", "packed", dict(seq=128, heads=2, head_dim=48),
     "xla"),
    ("short-seq-not-128s", "packed", dict(seq=64), "xla"),
    ("packed-long", "packed", dict(seq=1024), "stream"),
    ("split-heads-short", "qkv", dict(sq=128, sk=128), "xla"),
    ("cross-attention", "qkv", dict(sq=128, sk=256), "xla"),
    ("long-k", "qkv", dict(sq=64, sk=2048), "stream"),
    # latent attention: keys wider than values, the same gate and kernel
    ("long-k-wide-keys", "qkv", dict(sq=1024, sk=1024, d=24, dv=16,
                                     is_causal=True), "stream"),
    ("short-k-wide-keys", "qkv", dict(sq=128, sk=128, d=24, dv=16), "xla"),
    ("long-q-short-k", "qkv", dict(sq=2048, sk=128), "xla"),
    # ADVICE: 8192 x 128 has a min_seq^2 logits product but one k block
    ("very-long-q-short-k", "qkv", dict(sq=8192, sk=128), "xla"),
]


@pytest.mark.parametrize("entry,args,route", [c[1:] for c in _ROUTE_CASES],
                         ids=[c[0] for c in _ROUTE_CASES])
def test_routes(entry, args, route, track_kernel, track_short):
    """The route each observable shape takes, the kernel that then runs,
    and the counter that records it (one count a call here: eager)."""
    before = _routes()
    args = dict(args)
    if entry == "packed":
        seq = args.pop("seq")
        heads, head_dim = args.pop("heads", 2), args.pop("head_dim", 64)
        mask = (paddle.to_tensor(np.zeros((seq, seq), np.float32))
                if args.pop("mask", False) else None)
        out = attention.packed_self_attention(
            _packed(seq, heads, head_dim), heads, attn_mask=mask,
            training=False, **args)
        assert tuple(out.shape) == (2, seq, heads * head_dim)
    else:
        q, k, v = _qkv(args["sq"], args["sk"], args.get("d", 16),
                       args.get("dv"))
        out = attention.scaled_dot_product_attention(
            q, k, v, training=False, is_causal=args.get("is_causal", False))
        assert tuple(out.shape) == (1, 2, args["sq"], v.shape[-1])
    assert len(track_short) == (route == "short")
    assert len(track_kernel) == (route == "stream")
    after = _routes()
    assert {r: after[r] - before[r] for r in after} == {
        r: float(r == route) for r in after}


_BERT_CALL = dict(batch=8, seq_q=128, seq_k=128, num_heads=12, head_dim=64,
                  dtype=jnp.bfloat16, packed=True, masked=False,
                  is_causal=False)


def test_symbolic_batch_is_not_short():
    """jit.save exports batch-polymorphic programs: the short kernel's grid
    is the batch in row blocks, so a batch that is a symbol stays on XLA."""
    from jax import export

    (b,) = export.symbolic_shape("b")
    assert attention.attention_route(**{**_BERT_CALL, "batch": b}) == "xla"
    assert attention.attention_route(**_BERT_CALL) == "short"


@pytest.mark.parametrize("seq,embed,dtype,route", [
    (512, 1024, jnp.float32, "short"),
    (512, 2048, jnp.bfloat16, "short"),
    # a program holds ALL heads: its backward blocks outgrow VMEM
    (512, 2048, jnp.float32, "xla"),
    (128, 8192, jnp.float32, "xla"),
], ids=["f32-1024", "bf16-2048", "f32-2048", "f32-8192-s128"])
def test_short_needs_its_backward_to_fit_vmem(seq, embed, dtype, route):
    call = dict(_BERT_CALL, seq_q=seq, seq_k=seq, num_heads=embed // 64,
                dtype=dtype)
    assert attention.attention_route(**call) == route


@pytest.mark.parametrize("where,route", [
    ("one-device", "short"), ("plain-jit-many-devices", "xla"),
    ("announced-mesh", "short"), ("interpreter-many-devices", "short")])
def test_short_needs_to_know_the_programs_devices(where, route, monkeypatch):
    """GSPMD cannot partition a Mosaic call: with several devices and no
    announced mesh a jitted program may be partitioned over them, so the
    gate says xla (and counts xla) rather than pick a kernel that could
    not be placed. The Pallas interpreter's calls are plain HLO."""
    from paddle_tpu.distributed import topology

    assert jax.device_count() > 1                       # conftest's mesh
    interpret = where == "interpreter-many-devices"
    paddle.set_flags({"pallas_interpret": interpret})
    monkeypatch.setattr(placement, "is_tpu_available", lambda: True)
    if where == "one-device":
        monkeypatch.setattr(jax, "device_count", lambda: 1)
    mesh = topology.build_mesh(dp=jax.device_count())
    with (topology.tracing_for(mesh) if where == "announced-mesh"
          else contextlib.nullcontext()):
        assert attention.attention_route(**_BERT_CALL) == route
        # the stream kernel has no XLA way out at its lengths: as ever
        long = dict(_BERT_CALL, seq_q=2048, seq_k=2048)
        assert attention.attention_route(**long) == "stream"


@pytest.mark.parametrize("masked,dropout", [(False, 0.0), (True, 0.0),
                                            (False, 0.3)],
                         ids=["plain", "masked", "dropout"])
def test_packed_entry_on_the_xla_route_is_split_heads_sdpa(masked, dropout):
    """When the gate does not say short, the packed entry point IS split
    heads + scaled_dot_product_attention + merge heads, bit for bit
    (dropout included: the same key is drawn)."""
    paddle.set_flags({"pallas_interpret": False})      # CPU: route xla
    seq, heads, d = 128, 2, 64
    qkv = _packed(seq, heads, d)
    mask = (paddle.to_tensor(np.random.RandomState(3).randn(seq, seq)
                             .astype(np.float32)) if masked else None)
    paddle.seed(5)
    got = attention.packed_self_attention(qkv, heads, attn_mask=mask,
                                          dropout_p=dropout)
    paddle.seed(5)
    q, k, v = (x.reshape([2, seq, heads, d]).transpose([0, 2, 1, 3])
               for x in paddle.split(qkv, 3, axis=-1))
    want = attention.scaled_dot_product_attention(
        q, k, v, attn_mask=mask, dropout_p=dropout)
    want = want.transpose([0, 2, 1, 3]).reshape([2, seq, heads * d])
    assert np.array_equal(got.numpy(), want.numpy())


def test_short_route_agrees_with_xla_route_through_the_layer():
    """MultiHeadAttention in eval mode gives the same answer on both
    routes (the benchmark's reference check runs this at 8 rows), and its
    gradients reach the fused projection's parameters."""
    paddle.seed(2)
    layer = paddle.nn.MultiHeadAttention(128, 2, dropout=0.1)
    layer.eval()
    x = paddle.to_tensor(
        np.random.RandomState(4).randn(2, 128, 128).astype(np.float32))
    before = _routes()
    short = layer(x)
    assert _routes()["short"] == before["short"] + 1
    paddle.set_flags({"pallas_interpret": False})
    ref = layer(x)
    np.testing.assert_allclose(short.numpy(), ref.numpy(), rtol=2e-4,
                               atol=2e-4)

    paddle.set_flags({"pallas_interpret": True})
    layer.train()
    layer(x).sum().backward()
    for proj in (layer.q_proj, layer.k_proj, layer.v_proj, layer.out_proj):
        g = proj.weight.grad.numpy()
        assert np.isfinite(g).all() and np.abs(g).max() > 0


def test_huge_product_routes_to_kernel(track_kernel):
    # both sides below min_seq individually, but the logits buffer is
    # min_seq^2-scale: kernel avoids the S^2 materialisation
    q, k, v = _qkv(4096, 512)
    attention.scaled_dot_product_attention(q, k, v, training=False)
    assert len(track_kernel) == 1


def test_flag_zero_always_kernel(track_kernel):
    paddle.set_flags({"pallas_attention_min_seq": 0})
    try:
        q, k, v = _qkv(64, 64)
        attention.scaled_dot_product_attention(q, k, v, training=False)
        assert len(track_kernel) == 1
    finally:
        paddle.set_flags({"pallas_attention_min_seq": 1024})


def test_paths_numerically_agree(track_kernel):
    q, k, v = _qkv(64, 2048)
    out_kernel = attention.scaled_dot_product_attention(q, k, v,
                                                        training=False)
    assert len(track_kernel) == 1
    ref = attention._sdpa_ref(q, k, v, None, None,
                              scale=1.0 / np.sqrt(16), dropout_p=0.0,
                              is_causal=False)
    kv = out_kernel._value if hasattr(out_kernel, "_value") else out_kernel
    np.testing.assert_allclose(np.asarray(kv), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_kernel_failure_raises(monkeypatch, recwarn):
    """A selected kernel that fails is an error — never a warning plus
    the XLA path, which would report a broken kernel as a slow one."""
    from paddle_tpu.ops.pallas import flash_attention

    def boom(*a, **kw):
        raise RuntimeError("kernel exploded")

    monkeypatch.setattr(flash_attention, "mha", boom)
    q, k, v = _qkv(64, 2048)
    for _ in range(2):  # the failure is not remembered either
        with pytest.raises(RuntimeError, match="kernel exploded"):
            attention.scaled_dot_product_attention(q, k, v, training=False)
    assert not [w for w in recwarn if "falling back" in str(w.message)]


def test_kernel_not_selected_off_tpu_without_the_flag():
    paddle.set_flags({"pallas_interpret": False})
    assert placement.kernel(sharded=True) is None  # CPU backend, no flag
    paddle.set_flags({"pallas_interpret": True})
    assert placement.kernel(sharded=True) == "interpret"
    paddle.set_flags({"use_pallas_kernels": False})
    try:
        assert placement.kernel(sharded=True) is None
    finally:
        paddle.set_flags({"use_pallas_kernels": True})
