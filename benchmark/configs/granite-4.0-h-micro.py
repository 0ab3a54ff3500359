"""granite-4.0-h-micro: ibm-granite/granite-4.0-h-micro (HF
``granitemoehybrid``, 3B dense) at its published widths, cut to ONE CHIP'S
SHARE of a pipeline of four stages of ten layers, the tied vocabulary over
eight chips (``granite-4.0-h-micro.json`` holds every size, the cut's
arithmetic and what it distorts): published layers 0-9 — Mamba-2's selective
state-space mixer in nine of them (64 heads of 64 on a state of 128, B and C
one group's, 4 taps with a bias), grouped-query attention without positions
at a scale of 1/64 in layer 5 — a dense SwiGLU of 8,192 in every one, an
eighth of the tied vocabulary, one row of 8,192 tokens a step. Built with
``text.models.GraniteHybridModel``: ``ops.linear_attention.ssd_scan`` (the
chunked scan in XLA operations), ``conv_streams``' biased XLA stage, the
streaming flash kernel at 32 query heads of 64 (K and V repeated from 8)
through ``scaled_dot_product_attention(scale=1/64)``, per-block
recomputation with the kernel's residuals kept, and a tied head loss that
never holds the logits. The train recipe lives here; what differs from the
source is listed under ``departures`` in the JSON."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import cells

# The check is the other LM configurations' (float32 over the whole model at
# logit level, amp O1 block by block, the loss both ways), two things apart.
# There is no router here, so every token is decided and every token is
# compared: no margins, no clean prefix. And when the check runs the driver
# has made its first step: 9.27 GB of train state (weights and two moments)
# and the step's 0.27 GB of code are resident, so a second whole copy of the
# parameters (3.1 GB) beside three chains' hidden states a block (1.3 GB of
# taps), float32 logits and a block's float32 intermediates is within a few
# hundred MB of the chip: the model goes through IN PIECES — the stem, ten
# blocks, the tail — one block's parameters (0.3 GB) on the chip at a time,
# the float32 reference, the float32 program and the amp program in step,
# each handing its own hidden states on. The numbers are the whole model's:
# what reaches the logits has passed every block.
_kimi = cells.load_module("configs", "kimi-linear-48b-a3b")
_traced, token_errors, lm_loss = (_kimi._traced, _kimi.token_errors,
                                  _kimi.lm_loss)

# FLOAT32, whole model, at logit level: the framework's float32 forward
# (precision "highest"; the CHUNKED scan, the streaming kernel with float32
# operands) against the float32 reference (the recurrence token by token,
# every key under an explicit mask), the logits token by token as a share of
# the largest reference logit: the worst token and the median.
# Readings (my chip runs, PR 47, TPU v5 lite, 8,192 tokens a seed; 8 seeds):
# 1.71e-6 to 1.89e-6 worst token, 3.2e-7 to 3.3e-7 median; a float32 block
# on its own input 6.8e-7 (the attention block) to 5.0e-6 of its
# contribution. The float32 reference itself at the TPU's default precision
# (bf16 passes, the nearest precision below): 5.06e-3 at the worst token,
# 3.40e-3 at the median — it fails the bound by 250 x. (The reference's
# recurrence takes its decay through an exp of its own: with the TPU's
# float32 exp, good to 5e-6 and compounded over a slow head's thousands of
# tokens, the program read 3.09e-5 against it.)
F32_RTOL = 2e-5
# AMP O1, block by block: the reference block in float32 is fed the
# program's own (amp) block input and compared with the program's output at
# every token; the error is a share of the block's own largest contribution
# (output minus input).
# Readings: worst token of a block 6.75e-3 to 7.02e-3 (the first block the
# highest), a block's median 3.1e-3 to 4.1e-3. A mamba block that gates
# AFTER its norm reads 0.42 to 0.54 at the worst token (medians 0.26 to
# 0.32), one that drops the convolution's bias 0.46 to 0.68, the attention
# block at 1/8 for 1/64 0.171 — each with the blocks of the other kind where
# they were (tools/granite_check.py --gate-after-norm / --no-conv-bias /
# --scale-eighth).
AMP_RTOL = 4e-2
# The loss against the reference's, relative, both ways: it holds the loss's
# own arithmetic (the shift by one, the mean, the chunks, the tied weight's
# transpose, the divisor before the head); rounding averages out in a mean,
# so this bound does not tell float32 from bf16 passes: the logits' does.
# Readings: float32 0.0, amp 2.2e-6 to 3.5e-6; the default-precision
# reference reads 6.1e-7.
LOSS_F32_RTOL = 2e-5
LOSS_AMP_RTOL = 3e-3

LIMITS = ("F32_RTOL", "AMP_RTOL", "LOSS_F32_RTOL", "LOSS_AMP_RTOL")

#: JSON key -> ``GraniteHybridModel`` argument, where the two agree
MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads",
              "intermediate_size", "mamba_n_heads", "mamba_d_head",
              "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
              "mamba_conv_bias", "mamba_chunk", "mamba_segment",
              "attention_multiplier", "embedding_multiplier",
              "residual_multiplier", "logits_scaling", "rms_norm_eps",
              "initializer_range")

#: toy widths for the CPU tests and rehearsal (never a cell): the same ten
#: layers at 9 : 1, 8 state-space heads of 16 on a state of 32 (P != N), 4
#: query heads of 16 on 2 key/value heads at a scale that is not 16 ** -0.5
TOY = {"vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
       "shared_intermediate_size": 96, "mamba_n_heads": 8,
       "mamba_d_head": 16, "mamba_d_state": 32, "mamba_chunk": 16,
       "mamba_segment": 32, "attention_multiplier": 0.0625 / 2,
       "initializer_range": 0.1, "reference_q_block": 16}

PREFIX = "lm."   # the parameters' names under the train wrapper
#: parameters AdamW does not decay: the decay rate, the step's bias, the
#: skip and every norm's weight
NO_DECAY = ("A_log", "dt_bias", ".D", "norm_weight")


def layer_types(sizes):
    """The types of the layers run: the published list's first
    ``num_hidden_layers`` (layers 0-9: one whole period at 9 : 1)."""
    return list(sizes["layer_types"][:sizes["num_hidden_layers"]])


def model_kwargs(sizes):
    kw = {k: sizes[k] for k in MODEL_KEYS if k in sizes}
    kw["layer_types"] = layer_types(sizes)
    return kw


def build_train(seed, sizes, shapes):
    """The model, loss and optimizer of a train cell. The step's one input
    is the [rows, seq] token ids, and its label the same array."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.text.models import GraniteHybridModel

    class CausalLM(nn.Layer):
        """What the loss needs instead of logits: the final hidden states
        (already over ``logits_scaling``) and the head's weight — the
        embedding's, transposed."""

        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, input_ids):
            return self.lm.features(input_ids), self.lm.lm_head.weight

    paddle.seed(seed)
    # the eager copy of the parameters stays in host memory: the step
    # builder copies them onto the chip (donation), and 3.1 GB of unused
    # copy beside a step of 12.4 GB would leave it no room
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = GraniteHybridModel(use_recompute=True, **model_kwargs(sizes))
    opt = optimizer.AdamW(
        2.2e-4, beta1=0.9, beta2=0.95, epsilon=1e-8,
        parameters=model.parameters(), weight_decay=0.1,
        apply_decay_param_fun=lambda name: not name.endswith(NO_DECAY),
        grad_clip=nn.ClipGradByGlobalNorm(1.0))
    return {"layer": CausalLM(model), "loss_fn": lm_loss, "optimizer": opt,
            "amp_level": "O1", "sizes": sizes}


# ------------------------------------------------------------ the check
def reference_pieces(reference, sizes, precision="highest"):
    """The reference as jitted pieces: ``stem(w, ids) -> h``, ``block(w, h,
    kind) -> h`` on a block's own parameter names, ``tail(w, h, ids) ->
    (logits, loss)``; float32 at ``precision`` (None: the platform's
    default, the must-fail reading)."""
    def within(fn):
        def run(w, *args, **kw):
            with reference._precision(precision):
                return fn(reference._f32(w), *args, **kw)
        return run

    def tail(w, h, ids):
        logits = reference.head(w, h, sizes)
        return logits, reference.cross_entropy(logits, ids)

    return {
        "stem": jax.jit(within(lambda w, ids: reference.embed(w, ids, sizes))),
        "block": jax.jit(within(lambda w, h, kind: reference.block(
            w, h.astype(jnp.float32), sizes, "", kind)),
            static_argnames=("kind",)),
        "tail": jax.jit(within(tail))}


def framework_pieces(built, amp_level):
    """The program as the same pieces, each traced as the step builder
    traces (train mode, amp as given, a block through ``lm._block``: under
    recompute), at precision "highest" without amp. One module a kind of
    block — the first of its kind, given each block's parameters in turn —
    so that nine state-space blocks are one program."""
    from paddle_tpu.core.tensor import Tensor

    lm = built["layer"].lm
    modules = {kind: lm.layers[lm.layer_types.index(kind)]
               for kind in set(lm.layer_types)}

    def within(fn):
        if amp_level:
            return fn

        def run(*args, **kw):
            with jax.default_matmul_precision("highest"):
                return fn(*args, **kw)
        return run

    def stem(w, ids):
        return _traced(lm, w, {}, amp_level, lambda: lm.embed(
            Tensor(ids, stop_gradient=True))._value)[0]

    def block(w, h, kind):
        module = modules[kind]
        return _traced(module, w, {}, amp_level, lambda: lm._block(
            module, Tensor(h))._value)[0]

    def tail(w, h, ids):
        def fn():
            hidden = lm.final(Tensor(h))
            loss = built["loss_fn"]((hidden, lm.lm_head.weight),
                                    Tensor(ids, stop_gradient=True))
            return lm.lm_head(hidden)._value, loss

        return _traced(lm, w, {}, amp_level, fn)[0]

    return {"stem": jax.jit(within(stem)),
            "block": jax.jit(within(block), static_argnames=("kind",)),
            "tail": jax.jit(within(tail))}


def _block_error(reference, sizes):
    """The amp half's one block: the reference block in float32 on the
    program's own input -> each token's error as a share of the block's
    largest contribution."""
    def errors(w, start, got, kind):
        with jax.default_matmul_precision("highest"):
            start = start.astype(jnp.float32)
            ref = reference.block(reference._f32(w), start, sizes, "", kind)
            err = jnp.abs(got.astype(jnp.float32) - ref).max(axis=-1)
            return err / jnp.abs(ref - start).max()

    return jax.jit(errors, static_argnames=("kind",))


class Weights:
    """The eager (host) copy of the parameters, a piece at a time on the
    chip: ``ends()`` the embedding and the final norm, ``block(i)`` block
    i's under its own names."""

    def __init__(self, layer, device):
        self.params, self.device = layer.functional_state()[0], device

    def _put(self, prefix, strip):
        return jax.device_put(
            {n[len(strip):]: v for n, v in self.params.items()
             if n.startswith(prefix)}, self.device)

    def ends(self):
        return {**self._put(PREFIX + "embed_tokens.", PREFIX),
                **self._put(PREFIX + "norm.", PREFIX)}

    def block(self, i):
        prefix = f"{PREFIX}layers.{i}."
        return self._put(prefix, prefix)


def in_pieces(sides, weights, kinds, x, each_block=None):
    """Every side's (logits, loss) on ids x, the sides in step so that a
    block's parameters cross to the chip once: ``each_block(i, kind, w,
    inputs, outputs)`` sees every block's hidden states."""
    ends = weights.ends()
    hs = [side["stem"](ends, x) for side in sides]
    for i, kind in enumerate(kinds):
        w = weights.block(i)
        outs = [side["block"](w, h, kind=kind) for side, h in zip(sides, hs)]
        if each_block is not None:
            each_block(i, kind, w, hs, outs)
        hs = outs
    # a side's logits leave the chip before the next side's arrive
    return [jax.device_get(side["tail"](ends, h, x))
            for side, h in zip(sides, hs)]


def check_train(built, reference, sizes, shapes, probe_x, rows=1):
    """What the timed program computes on the first probe row at the timed
    size (train mode, recomputation on, the chunked scan, the streaming
    kernel) against ``references/granite-4.0-h-micro.py`` (the recurrence
    token by token, attention in query blocks under an explicit mask): in
    float32 at logit level over the whole model, under the cell's amp level
    block by block, and the loss both ways — in pieces (above), beside the
    train state."""
    layer = built["layer"]
    layer.train()
    x = probe_x[:rows]
    weights = Weights(layer, next(iter(x.devices())))
    kinds = layer_types(sizes)
    error_of = _block_error(reference, sizes)
    blocks, blocks32 = [], []

    def each_block(i, kind, w, inputs, outputs):
        # the reference block on the program's own input, both programs
        blocks32.append(float(error_of(w, inputs[1], outputs[1],
                                       kind=kind).max()))
        blocks.append(np.asarray(error_of(w, inputs[2], outputs[2],
                                          kind=kind)))

    ref, got32, got_amp = in_pieces(
        [reference_pieces(reference, sizes), framework_pieces(built, None),
         framework_pieces(built, built["amp_level"])],
        weights, kinds, x, each_block)
    out = compare(ref, got32, got_amp, blocks)
    # where a float32 error comes from, a block: a reading, not a limit
    out["f32_block_worst"] = blocks32
    return out


def compare(ref, got32, got_amp, blocks):
    """The check's dict from the reference's (logits, loss), the
    framework's in float32 and under amp, and the amp half's per-token
    errors a block."""
    out = {"ref_loss": float(ref[1]),
           "tokens": int(np.prod(ref[0].shape[:-1])),
           "ref_max_abs": float(np.abs(np.asarray(ref[0])).max())}
    ok = bool(np.isfinite(np.asarray(ref[0])).all())

    def loss_error(tag, got, rtol):
        err = abs(float(got[1]) - float(ref[1])) / abs(float(ref[1]))
        out[f"loss_{tag}_rel_err"], out[f"loss_{tag}_rtol"] = err, rtol
        return err <= rtol

    errs = token_errors(ref[0], got32[0])
    out.update({"f32_rel_err": float(errs.max()), "f32_rtol": F32_RTOL,
                "f32_rel_err_median": float(np.median(errs))})
    ok = loss_error("f32", got32, LOSS_F32_RTOL) and ok and (
        out["f32_rel_err"] <= F32_RTOL)
    out.update({"amp_rel_err": float(max(b.max() for b in blocks)),
                "amp_rtol": AMP_RTOL,
                "amp_block_worst": [float(b.max()) for b in blocks],
                "amp_block_medians": [float(np.median(b)) for b in blocks],
                "amp_logits_rel_err_median": float(np.median(
                    token_errors(ref[0], got_amp[0])))})
    ok = loss_error("amp", got_amp, LOSS_AMP_RTOL) and ok and (
        out["amp_rel_err"] <= AMP_RTOL)
    out["ok"] = bool(ok)
    return out


# ------------------------------------------------------------ FLOPs
def mixer_counts(sizes):
    """(state-space layers, attention layers) the step runs."""
    types = layer_types(sizes)
    mamba = types.count("mamba")
    return mamba, len(types) - mamba


def mamba_projection_flops_per_token(sizes):
    """Forward FLOPs a token of one state-space mixer's two matrices
    (hidden -> [z | xBC | dt] and inner -> hidden; the 4 taps a channel, the
    step and the gated norm are no matmul and are not counted)."""
    h = sizes["hidden_size"]
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    state = sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return 2 * (h * (2 * inner + 2 * state + sizes["mamba_n_heads"])
                + inner * h)


def ssd_core_flops_per_token(sizes):
    """Forward FLOPs a token of one state-space layer's chunked scan, all
    heads (the reader's function of heads, widths, groups and the chunk)."""
    reader = cells.load_module("layer_metrics", "ssd_core_roofline")
    return reader.ssd_core_flops(
        1, sizes["mamba_n_heads"], sizes["mamba_d_head"],
        sizes["mamba_d_state"], sizes["mamba_n_groups"], sizes["mamba_chunk"])


def attention_projection_flops_per_token(sizes):
    """Forward FLOPs a token of the attention sublayer's four matrices."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return 2 * (2 * h * heads * d + 2 * h * kv * d)


def core_flops(sizes, seq, passes=1.0):
    """Forward FLOPs of the attention core on one sequence: QK^T and PV
    over d-wide heads, every QUERY head, on the causal seq (seq + 1) / 2
    pairs; ``passes`` scales. (The reader's function:
    ``attn64_nope_flash_roofline`` through ``gqa_flash_roofline``.)"""
    reader = cells.load_module("layer_metrics", "attn64_nope_flash_roofline")
    return passes * reader.core_flops(
        1, sizes["num_attention_heads"], seq, sizes["head_dim"], 1, 0)


def flops_per_sample(sizes, shapes):
    """Floating-point operations one sample (one sequence) needs in a train
    step, from shapes: 2 per multiply-add of the forward pass, times 3 for
    forward + backward; NOTHING recomputed (the blocks' second forward and
    the scan's rebuilt segments are the program's choice, not the model's
    work). Counted: every state-space mixer's two matrices and its chunked
    scan's own products, the attention sublayer's four and its core over the
    causal pairs, the dense SwiGLU of EVERY block, and the tied head over
    the held vocabulary slice."""
    seq = shapes["input_ids"][0]
    h = sizes["hidden_size"]
    mamba, attn = mixer_counts(sizes)
    per_token = (
        mamba * (mamba_projection_flops_per_token(sizes)
                 + ssd_core_flops_per_token(sizes))
        + attn * attention_projection_flops_per_token(sizes)
        + (mamba + attn) * 3 * 2 * h * sizes["intermediate_size"]
        + 2 * h * sizes["vocab_size"])
    return 3.0 * (seq * per_token + attn * core_flops(sizes, seq))
