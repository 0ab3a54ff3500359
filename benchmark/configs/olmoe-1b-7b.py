"""olmoe-1b-7b: allenai/OLMoE-1B-7B-0125-Instruct at its published widths,
cut to ONE layer (``olmoe-1b-7b.json`` holds every size, the cut's
arithmetic and what it distorts), built with ``text.models.OlmoeModel``:
attention with QK-norm on the streaming flash kernel, 64 SwiGLU experts
top-8 on ``incubate.moe``'s dropless path, an untied 50,304-row head whose
loss never holds the logits whole. The train recipe lives here; what
differs from the source is listed under ``departures`` in the JSON."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

# The comparison is a token at a time, and leaves out the tokens whose
# router is UNDECIDED at the arithmetic compared: where a token's 8th and
# 9th router logits lie closer than that arithmetic's error in them, it may
# rightly take another expert, and one swapped expert of eight moves that
# token's logits by a quarter of their scale (at initialisation the experts'
# sum is as large as the residual stream). With one layer a swap touches
# that token alone. The reference reports each token's margin
# (``router_margin``); the share of tokens left out is bounded too, and the
# median error over ALL tokens, so a router that is wrong everywhere cannot
# hide. Readings: my chip runs, PR 25 (tools/olmoe_check.py and the cell's
# own 15 runs, 15 seeds), TPU v5 lite, 4,096 tokens a seed.
#
# Framework f32 (precision "highest") against the float32 reference, as a
# share of the largest reference logit: the same equations in float32,
# differing in summation order (the grouped matmul sums a row's 2,048
# products in another order than the reference's dense loop). Tokens with a
# margin under 1e-4 are left out (0.07-0.27% of them; float32 router logits
# differ by ~1e-6). Observed: 1.7e-6 to 2.2e-6, no token swapped; the
# float32 reference itself at the TPU's default precision (bf16 passes) is
# off by 0.23-0.25 at these tokens (swaps) and 8.6e-3 to 1.0e-2 at tokens
# it decides: it fails this by three orders.
F32_RTOL = 2e-5
F32_MARGIN = 1e-4
F32_UNDECIDED_MAX = 0.02
# amp O1 (bf16 matmul operands, f32 accumulation; router, norms, softmax
# and loss in f32). The router is float32, but its input carries the
# attention block's bf16 error: QK-norm makes q and k unit-scale over 128
# features, so scores are O(10) and bf16's 4e-3 moves them by 0.04 — the
# router margins move by up to 0.03 (read on the default-precision
# reference: 0.027, 0.030), and a token swapped at a margin over 0.03 was
# seen. Tokens with a margin under 0.1 (three times that) are left out: at
# initialisation the router is nearly uniform and that is 76-78% of them
# (it falls as a router trains). Observed at the ~950 decided tokens:
# 1.02e-2 to 1.36e-2 (the default-precision reference: 8.6e-3, 8.9e-3),
# median over all tokens 7.2e-3 to 8.0e-3, worst swapped token 0.23-0.28;
# a wrong program is off by O(1) of the scale everywhere.
AMP_RTOL = 3e-2
AMP_MARGIN = 0.1
AMP_UNDECIDED_MAX = 0.9
# The scalar loss (cross-entropy + 0.01 L_lb + 0.001 L_z, about 11.35 at
# initialisation) against the reference's, relative, over ALL tokens: it
# holds the loss's own arithmetic (the shift by one, the mean over 4,095
# positions, the chunks, both auxiliary terms) — a missing term is off by
# 1e-2. Observed f32 0.0 to 2.8e-6 (a float32 mean of 4,095 logsumexps over
# 50,304 columns in two summation orders), amp 3.2e-6 to 1.2e-4. Rounding
# averages out in a mean, so this bound does not tell float32 from bf16
# passes (the default-precision reference reads 8.5e-6 to 4.5e-5): the
# logits' bound above does that.
LOSS_F32_RTOL = 2e-5
LOSS_AMP_RTOL = 2e-3

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "intermediate_size", "num_experts",
              "num_experts_per_tok", "rms_norm_eps", "rope_theta",
              "norm_topk_prob", "router_aux_loss_coef", "router_z_loss_coef",
              "initializer_range")

#: toy widths for the CPU rehearsal in benchmark/tests (never a cell); the
#: wider initialiser keeps the router logits' spread (range x sqrt(hidden))
#: at the published model's 0.9, which the check's margins are in units of
TOY = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
       "num_attention_heads": 4, "intermediate_size": 32, "num_experts": 8,
       "num_experts_per_tok": 2, "max_position_embeddings": 64,
       "initializer_range": 0.1}

PREFIX = "lm."   # the parameters' names under the train wrapper


def model_kwargs(sizes):
    return {k: sizes[k] for k in MODEL_KEYS if k in sizes}


def lm_loss(out, input_ids):
    """Next-token cross-entropy from (final hidden states, head weight):
    position t is scored against token t + 1, a row's last position
    against nothing (mean over the s - 1 predicted positions of each row)."""
    from paddle_tpu.nn import functional as F

    hidden, head = out
    labels = jnp.concatenate(
        [input_ids[:, 1:], jnp.full_like(input_ids[:, :1], -100)], axis=1)
    return F.linear_cross_entropy(hidden, head, labels)._value


def build_train(seed, sizes, shapes):
    """The model, loss and optimizer of a train cell. The step's one input
    is the [rows, seq] token ids, and its label the same array."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.text.models import OlmoeModel

    class CausalLM(nn.Layer):
        """What the loss needs instead of logits: the final hidden states
        and the head's weight."""

        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, input_ids):
            return self.lm.features(input_ids), self.lm.lm_head.weight

    paddle.seed(seed)
    # the eager copy of the parameters stays in host memory: the step
    # builder copies them onto the chip (donation), and 2.5 GB of unused
    # copy beside 10.0 GB of train state would not leave the step room
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = OlmoeModel(**model_kwargs(sizes))
    opt = optimizer.AdamW(4e-4, beta1=0.9, beta2=0.95, epsilon=1e-8,
                          parameters=model.parameters(), weight_decay=0.1,
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    return {"layer": CausalLM(model), "loss_fn": lm_loss, "optimizer": opt,
            "amp_level": "O1"}


def _framework(layer, params, amp_level, x):
    """The framework's logits (the model's ordinary forward) and its
    training loss (the wrapper's forward, the auxiliary losses collected as
    ``build_train_step`` collects them, ``lm_loss``) in one traced function:
    (logits, total loss, cross-entropy)."""
    from paddle_tpu.amp.auto_cast import auto_cast
    from paddle_tpu.core import dispatch
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.aux_loss import collect_aux_losses, total_aux_loss

    saved = layer.functional_state()
    try:
        with contextlib.ExitStack() as stack:
            stack.enter_context(dispatch.trace_mode())
            if amp_level:
                stack.enter_context(auto_cast(enable=True, level=amp_level,
                                              dtype="bfloat16"))
            layer.load_functional_state(params, {})
            ids = Tensor(x, stop_gradient=True)
            logits = layer.lm(ids)._value
            with collect_aux_losses() as auxes:
                out = layer.forward(ids)
            ce = lm_loss(out, x)
            return logits, ce + total_aux_loss(auxes), ce
    finally:
        layer.load_functional_state(*saved)


def reference_outputs(reference, sizes, precision="highest"):
    """(params, ids) -> the reference's (logits, total loss, cross-entropy,
    router margins) on the wrapper's parameter names."""
    def fn(p, ids):
        w = {k[len(PREFIX):]: v for k, v in p.items()}
        total, ce, _, _ = reference.loss_terms(w, ids, sizes,
                                               precision=precision)
        return (reference.forward(w, ids, sizes, precision=precision),
                total, ce,
                reference.router_margin(w, ids, sizes, precision=precision))

    return fn


def check_train(built, reference, sizes, shapes, probe_x, rows=1):
    """The framework model in eval mode on the first probe row (what the
    reference can hold at published widths: its [4096, 50304] float32
    logits are 0.8 GB) against ``references/olmoe-1b-7b.py``: the logits
    and the training loss, in float32 and under the cell's amp level."""
    layer = built["layer"]
    x = probe_x[:rows]
    device = next(iter(x.devices()))
    was_training = layer.training
    layer.eval()
    try:
        params = jax.device_put(layer.functional_state()[0], device)
        ref = jax.jit(reference_outputs(reference, sizes))(params, x)
        with jax.default_matmul_precision("highest"):
            got32 = jax.jit(lambda p, a: _framework(layer, p, None, a))(
                params, x)
        got_amp = jax.jit(lambda p, a: _framework(
            layer, p, built["amp_level"], a))(params, x)
    finally:
        if was_training:
            layer.train()
    return compare(ref, got32, got_amp)


def token_errors(ref, got):
    """Each token's largest logit error over the largest reference logit
    ([n, s]; inf where the shapes differ or a value is not finite)."""
    ref_logits = np.asarray(ref[0], np.float32)
    got = np.asarray(got[0]).astype(np.float32)
    if got.shape != ref_logits.shape or not np.isfinite(got).all():
        return np.full(ref_logits.shape[:-1], np.inf)
    return np.abs(got - ref_logits).max(axis=-1) / np.abs(ref_logits).max()


def compare(ref, got32, got_amp):
    """(logits, total loss, cross-entropy[, margins]) -> the check's dict.
    ``ref`` carries the router margins of its tokens."""
    ref_logits = np.asarray(ref[0], np.float32)
    margin = np.asarray(ref[3], np.float32)
    scale = float(np.abs(ref_logits).max())
    out = {"ref_loss": float(ref[1]), "ref_ce": float(ref[2]),
           "ref_max_abs": scale, "tokens": int(margin.size)}
    ok = bool(np.isfinite(ref_logits).all() and scale > 0)
    for tag, got, rtol, min_margin, most, loss_rtol in (
            ("f32", got32, F32_RTOL, F32_MARGIN, F32_UNDECIDED_MAX,
             LOSS_F32_RTOL),
            ("amp", got_amp, AMP_RTOL, AMP_MARGIN, AMP_UNDECIDED_MAX,
             LOSS_AMP_RTOL)):
        errs = token_errors(ref, got)
        decided = margin >= min_margin
        err = float(errs[decided].max()) if decided.any() else float("inf")
        median = float(np.median(errs))
        undecided = 1.0 - float(decided.mean())
        loss_err = abs(float(got[1]) - float(ref[1])) / abs(float(ref[1]))
        out.update({
            f"{tag}_rel_err": err, f"{tag}_rtol": rtol,
            f"{tag}_rel_err_all_tokens": float(errs.max()),
            f"{tag}_rel_err_median": median,
            f"{tag}_undecided_share": undecided,
            f"{tag}_undecided_max": most,
            f"loss_{tag}_rel_err": loss_err, f"loss_{tag}_rtol": loss_rtol,
            f"ce_{tag}_rel_err": abs(float(got[2]) - float(ref[2]))
            / abs(float(ref[2]))})
        ok = (ok and err <= rtol and median <= rtol and undecided <= most
              and loss_err <= loss_rtol)
    out["ok"] = ok
    return out


def flops_per_sample(sizes, shapes):
    """Floating-point operations one sample (one sequence) needs in a train
    step, from shapes: 2 per multiply-add of every matmul of the forward
    pass, times 3 for forward + backward. Counted: the four attention
    projections, the causal HALF of QK^T and PV (a position attends to
    itself and what precedes it: seq (seq + 1) / 2 pairs), the router, the
    three matrices of the 8 experts a token is routed to (not the 64), and
    the head at every position. Elementwise work, the norms and the
    embedding lookup are not counted; nothing is recomputed."""
    seq = shapes["input_ids"][0]
    h, width = sizes["hidden_size"], sizes["intermediate_size"]
    per_token = sizes["num_hidden_layers"] * (
        2 * 4 * h * h                                       # q, k, v, o
        + 2 * h * sizes["num_experts"]                      # router
        + sizes["num_experts_per_tok"] * 2 * 3 * h * width)  # experts
    causal_pairs = seq * (seq + 1) // 2
    attention = sizes["num_hidden_layers"] * 2 * 2 * h * causal_pairs
    head = 2 * h * sizes["vocab_size"]
    return 3.0 * (seq * (per_token + head) + attention)
