"""The few calls into the program that more than one configuration needs.
This is the only harness module that imports ``paddle_tpu``."""
import contextlib

import jax
import numpy as np


def functional_forward(layer, params, buffers, x, amp_level=None):
    """``layer.forward`` as a pure function of (params, buffers, x), the
    way ``spmd.build_train_step`` traces it: for jitting a model's forward
    in one program (an eager forward compiles op by op)."""
    from paddle_tpu.core import dispatch
    from paddle_tpu.core.tensor import Tensor

    saved_p, saved_b = layer.functional_state()
    try:
        with contextlib.ExitStack() as stack:
            stack.enter_context(dispatch.trace_mode())
            if amp_level:
                from paddle_tpu.amp.auto_cast import auto_cast

                stack.enter_context(auto_cast(enable=True, level=amp_level,
                                              dtype="bfloat16"))
            layer.load_functional_state(params, buffers)
            out = layer.forward(Tensor(x, stop_gradient=True))
            return out._value if isinstance(out, Tensor) else out
    finally:
        layer.load_functional_state(saved_p, saved_b)


def compare_with_reference(layer, reference_fn, x, amp_level, f32_rtol,
                           amp_rtol):
    """The framework model's eval-mode forward against the plain reference
    on the same weights and inputs, at logit level. Three programs, one
    jitted call each:

      reference            float32, matmul precision "highest"
      framework, float32   matmul precision "highest" -> within f32_rtol
      framework, amp       the cell's amp level       -> within amp_rtol

    Errors are the largest absolute difference over the largest reference
    magnitude. Returns a dict with ``ok`` and both errors."""
    was_training = layer.training
    layer.eval()
    try:
        params, buffers = layer.functional_state()
        ref = jax.jit(reference_fn)(params, buffers, x)
        with jax.default_matmul_precision("highest"):
            got32 = jax.jit(lambda p, b, a: functional_forward(
                layer, p, b, a))(params, buffers, x)
        got_amp = jax.jit(lambda p, b, a: functional_forward(
            layer, p, b, a, amp_level))(params, buffers, x)
    finally:
        if was_training:
            layer.train()
    ref = np.asarray(ref, np.float32)
    scale = float(np.abs(ref).max())

    def err(got):
        got = np.asarray(got).astype(np.float32)
        if got.shape != ref.shape or not np.isfinite(got).all():
            return float("inf")
        return float(np.abs(got - ref).max()) / scale

    e32, eamp = err(got32), err(got_amp)
    return {"ok": bool(np.isfinite(ref).all() and scale > 0
                       and e32 <= f32_rtol and eamp <= amp_rtol),
            "f32_rel_err": e32, "f32_rtol": f32_rtol,
            "amp_rel_err": eamp, "amp_rtol": amp_rtol,
            "ref_max_abs": scale}
