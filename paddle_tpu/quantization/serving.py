"""Quantized serving modes (ROADMAP item 4a): the bridge from the
quantization package's fake-quant/PTQ capability to first-class serving
artifacts.

Three modes, one ladder (README "Quantized serving" has the matrix):

    mode    weights                     activations   accumulate
    ------  --------------------------  ------------  ----------
    w8      int8 + per-channel f32      f32           f32
            scales (dequantize-into-
            gemm at compute)
    w8a8    as w8                       quantize-     f32
                                        dequantize at
                                        the calibrated
                                        abs-max scale
    bf16w   bf16 (cast once at export)  f32           f32

In every mode the *stored/streamed* weights are the reduced-precision
arrays — they ride as runtime arguments through ``jit.save``'s export
and the serving engines exactly like f32 weights do, which is where the
2–4x weight memory/bandwidth win on the decode hot path lives (decode
streams every weight every token). Compute dequantizes into the float
domain (the MXU path; the pallas guide's ``values.astype(f32) * scale``
pattern), so XLA sees genuine ``s8``/``bf16`` parameters plus
``convert`` ops — which tests/test_quant_serving.py
(``test_ledger_events_carry_mode``) asserts reached the HLO.

Documented accuracy bounds vs the float program, on well-scaled
(unit-ish variance) weights — what tests/test_quant_serving.py pins on
the toy models and the contract tests gate:

    w8      per-channel int8 weight rounding: relative logit error
            <= ~2 * depth / 127 (observed ~1e-2 on the toys)
    w8a8    adds one activation rounding per quantized layer: observed
            <= ~5e-2 relative on the toys
    bf16w   bf16 has 8 mantissa bits: relative logit error <= ~1e-2

Greedy decode over these logit gaps is NOT bitwise vs the float model
(different program, different rounding) — the quantized contract is
the same one f32 decode has: a sequence decoded in-batch emits exactly
its OWN solo tokens, per mode (tests/test_quant_serving.py).
"""
import numpy as np

QUANT_MODES = ("w8", "w8a8", "bf16w")

#: documented per-mode relative-error bounds for the accuracy contract
#: (toy models, unit-variance weights; see module docstring)
ACCURACY_BOUNDS = {"w8": 5e-2, "w8a8": 1e-1, "bf16w": 5e-2}


def check_mode(quant):
    """Validate a quant-mode string and return its canonical form:
    ``None`` for f32 (the explicit ``"f32"`` spelling every deployment
    surface accepts normalizes here, so one templated mode string works
    across jit.save / serve_model / DecodeEngine / the env knob)."""
    if quant in (None, "f32"):
        return None
    if quant not in QUANT_MODES:
        raise ValueError(
            f"unknown quant mode {quant!r}; expected one of "
            f"{QUANT_MODES} (or 'f32'/None)")
    return quant


def detect_mode(layer):
    """The quant mode already baked into a layer tree, or None.
    ``quantize_weights``/``quantize_for_serving`` convert IN PLACE, so
    a model object can arrive at ``jit.save`` already carrying Int8*
    layers — the save must record THAT mode, not silently stamp the
    artifact f32 (every downstream label — sidecar, fingerprint,
    ArtifactKey, metrics — would then misdescribe an int8 program)."""
    from .post_training import Int8Conv2D, Int8Linear

    mode = None
    for _, sub in layer.named_sublayers(include_self=True):
        if isinstance(sub, (Int8Linear, Int8Conv2D)):
            if sub.act_scale is not None:
                return "w8a8"
            mode = "w8"
    return mode


def quantize_for_serving(layer, quant, calib=None):
    """Apply a serving quant mode to an nn.Layer IN PLACE (the
    ``jit.save(..., quant=...)`` backend; same in-place semantics as
    ``quantize_weights``). Returns ``(layer, meta)`` where ``meta`` is
    the JSON-able scale record the ``.pdmeta.json`` sidecar stores.

    - ``w8``: every Linear/Conv2D becomes Int8Linear/Int8Conv2D
      (int8 weights + per-channel scales as runtime-arg buffers).
    - ``w8a8``: additionally calibrates activation scales by running
      ``calib`` (a sample-batch generator, PostTrainingQuantization's
      ``sample_generator``) and bakes them into the quantized layers.
    - ``bf16w``: no layer surgery here — the weight cast happens at
      export (jit.save casts f32 params to bf16 and the traced fn
      upcasts, so the convert sits in the program and the stored
      weights are half-width).
    """
    quant = check_mode(quant)
    baked = detect_mode(layer)
    if baked is not None:
        # the tree was already converted in place (an earlier
        # quantize_weights / PTQ / jit.save(quant=) call on the same
        # object): record the TRUE mode. quant=None adopts it —
        # PostTrainingQuantization.save_quantized_model has always
        # saved an already-frozen model — an explicit matching mode is
        # a no-op, and a DIFFERENT mode is an error (int8 weights
        # cannot be re-quantized or mislabeled).
        if quant not in (None, baked):
            raise ValueError(
                f"layer already carries {baked!r}-quantized sublayers; "
                f"it cannot be re-saved as {quant!r} — re-instantiate "
                "the float model to change modes")
        return layer, {"mode": baked, "detected": True}
    if quant is None:
        return layer, None
    meta = {"mode": quant}
    if quant == "bf16w":
        return layer, meta
    from .post_training import PostTrainingQuantization

    if quant == "w8a8":
        if calib is None:
            raise ValueError(
                "quant='w8a8' needs calibration data: pass "
                "quant_calib=<sample generator> (a callable yielding "
                "input batches)")
        ptq = PostTrainingQuantization(layer, sample_generator=calib)
        ptq.quantize(act_quant=True)
        meta["act_scales"] = {k: float(v)
                              for k, v in ptq.activation_scales.items()}
    else:
        ptq = PostTrainingQuantization(layer)
        ptq.quantize()
    meta["weight_scale_layers"] = sorted(ptq.weight_scales)
    return layer, meta


def _w8_plan(params):
    """Per-param quantization plan for a flat DecodeModel param list:
    ``("w8", q_int8, scale)`` for float32 matrices (per-channel on the
    LAST axis — the out axis of every [in, out]-layout matmul weight,
    including embedding [vocab, hidden] and unembedding [hidden,
    vocab]), ``("raw", arr)`` for everything else (biases, norms,
    integer tables stay exact)."""
    from .post_training import _quantize_array

    plan = []
    for p in params:
        a = np.asarray(p)
        if a.dtype == np.float32 and a.ndim >= 2:
            q, s = _quantize_array(a, channel_axis=a.ndim - 1)
            plan.append(("w8", q, s))
        else:
            plan.append(("raw", a))
    return plan


def quantize_decode_model(model, quant):
    """A NEW DecodeModel serving ``model``'s computation under a quant
    mode: reduced-precision params ride as the runtime args (the decode
    bandwidth win) and wrapped prefill/step fns dequantize into f32
    before calling the original functions (f32 accumulate).

    ``w8``: each f32 matrix param becomes an (int8, f32 per-out-channel
    scale) pair in the flat param list. ``bf16w``: f32 params cast to
    bf16. ``w8a8`` is an export-time mode (it needs layer-structure
    calibration hooks) and is rejected here — the decode ladder serves
    ``w8``/``bf16w`` (ISSUE 13 acceptance).

    The returned model carries ``quant`` so engine ArtifactKeys,
    metrics, and ledger events are mode-labelled; its fingerprint is
    computed from its OWN (quantized) step program, so quantized
    artifacts can never collide with f32 ones in the store.
    """
    import jax.numpy as jnp

    from ..inference.decode import DecodeModel

    quant = check_mode(quant)
    if quant is None:
        return model
    if getattr(model, "quant", None) is not None:
        raise ValueError(
            f"model is already quantized (mode {model.quant!r})")
    if quant == "w8a8":
        raise ValueError(
            "decode serving supports quant='w8' | 'bf16w'; w8a8 "
            "activation calibration is a jit.save-time mode")

    if quant == "bf16w":
        new_params = [jnp.asarray(p).astype(jnp.bfloat16)
                      if np.asarray(p).dtype == np.float32
                      else jnp.asarray(p) for p in model.params]

        def unpack(param_list):
            return [p.astype(jnp.float32)
                    if p.dtype == jnp.bfloat16 else p
                    for p in param_list]
    else:  # w8
        plan = _w8_plan(model.params)
        new_params = []
        layout = []  # ("w8",) consumes two flat entries, ("raw",) one
        for entry in plan:
            if entry[0] == "w8":
                new_params.extend([jnp.asarray(entry[1]),
                                   jnp.asarray(entry[2])])
            else:
                new_params.append(jnp.asarray(entry[1]))
            layout.append(entry[0])

        def unpack(param_list):
            out, i = [], 0
            for kind in layout:
                if kind == "w8":
                    q, s = param_list[i], param_list[i + 1]
                    out.append(q.astype(jnp.float32) * s)
                    i += 2
                else:
                    out.append(param_list[i])
                    i += 1
            return out

    def wrap(fn):
        def quantized_fn(param_list, *args):
            return fn(unpack(param_list), *args)

        return quantized_fn

    qm = DecodeModel(new_params, wrap(model.prefill_fn),
                     wrap(model.step_fn),
                     kv_spec=[(tr, dt) for tr, dt in model.kv_spec],
                     vocab_size=model.vocab_size,
                     feature_spec=[(tr, dt)
                                   for tr, dt in model.feature_spec],
                     eos_token_id=model.eos_token_id,
                     quant=quant)
    return qm


def weight_bytes(params):
    """Total bytes of a flat param list — the per-decode-step
    bytes-moved proxy (every decode step streams every weight once)."""
    return int(sum(np.asarray(p).nbytes for p in params))
