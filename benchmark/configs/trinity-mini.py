"""trinity-mini: arcee-ai/Trinity-Mini (HF ``afmoe``, 26B-A3B) at its
published widths, cut to ONE CHIP'S SHARE of an 8-chip expert-parallel
deployment (``trinity-mini.json`` holds every size, the cut's arithmetic
and what it distorts): published layer 0 (sliding window, dense SwiGLU) and
published layers 4-7 (sliding, sliding, sliding, full attention without
positions: one whole period, each with the expert layer), 16 of the 128
routed experts held in each expert layer, an eighth of the vocabulary, one
row of 16,384 tokens a step. Built with ``text.models.AfmoeModel``: the
streaming flash kernel's BANDED calls for the window of 2,048 keys
(``ops.attention`` with ``window=``: grids over the band alone) beside its
full-causal calls at 16,384 keys, grouped queries at d 128 with QK-norm and
a gate matrix of its own, four norms a block, ``incubate.moe.MoELayer``'s
sigmoid bias-balanced router over all 128 experts with the held range on
the dropless sorted path and a shared expert, per-block recomputation with
the kernel's residuals kept, and a head loss that never holds the logits.
The train recipe lives here; what differs from the source is listed under
``departures`` in the JSON."""
import jax
import numpy as np

from benchmark.harness import cells

# The check's rule is the Kimi-Linear configuration's (and through it the
# JoyAI configuration's), for their reason: a swapped expert does not stay
# where it happened — a token that takes another expert in one layer reaches
# every later token of the row through the attention of every later layer.
# It runs on THIS module's own copy of that module (``load_module`` makes a
# new one a call), given this configuration's limits below: its trace, the
# overflow count, the per-token error, the clean prefix, the tapped forward,
# the block-by-block comparison and ``compare``'s readings are used as they
# are; the verdict is taken here, because the median has a limit of its own
# (below). The router is theirs too (sigmoid scores, a selection bias,
# top-8): a margin is the gap between a token's 8th and 9th biased SCORE.
_kimi = cells.load_module("configs", "kimi-linear-48b-a3b")

# FLOAT32, whole model, at logit level: the framework's float32 forward
# (precision "highest"; the banded and the full kernel with float32
# operands) against the float32 reference (every key under an explicit
# mask), the logits token by token as a share of the largest reference
# logit, the worst over the row's CLEAN PREFIX — the tokens whose router is
# DECIDED in every expert layer (margin at least F32_MARGIN) before the
# row's first undecided token that is over the bound — and the median over
# ALL tokens. Readings (my chip runs, PR 40, TPU v5 lite, 16,384 tokens a
# seed; 10 seeds): 1.39e-6 to 1.68e-6 worst token, 0.38-0.53% of the tokens
# under F32_MARGIN; two seeds swapped one expert (34% and 40% of the row
# compared), the others compared 99.5-99.6%. The float32 reference itself
# at the TPU's default precision (bf16 passes, the nearest precision
# below): 0.155 and 0.143 at the worst token of its clean prefix — it fails
# the bound by four orders.
F32_RTOL = 2e-5
F32_MARGIN = 1e-5
F32_UNDECIDED_MAX = 0.05
# The median token has a limit of its own. A swap DOES move it here: a
# sliding layer's query reads a swapped token with 1 / 2,048 of its weight
# wherever it stands in the row (full attention dilutes it by 1 / position),
# and the post-norms keep a sublayer's contribution at full size, so when a
# token swaps in the row's first half every token after it — the median's
# among them — carries the echo. Readings: 8.5e-7 to 9.6e-7 on the nine seeds
# whose median token lies before any swap, 1.18e-5 on the one that swapped
# at 34% of the row; the default-precision reference 5.9e-3 and 6.0e-3. The
# limit lies between the two with an order of room on both sides.
F32_MEDIAN_RTOL = 2e-4
# AMP O1, block by block: the reference block in float32 is fed the
# program's own (amp) block input and compared with the program's output at
# the tokens decided IN THAT BLOCK by AMP_MARGIN; the error is a share of
# the block's own largest contribution (output minus input: the two
# post-norms' outputs). The dense block has no router: all its tokens
# count. A block's bf16 scores at d 128 over 2,048 or up to 16,384 keys are
# under this bound; a block whose window is off by one kernel block (1,024
# keys) is over it (tools/trinity_check.py --window-off: 0.68 at the worst
# decided token, the four sliding blocks' medians 0.28 to 0.44, the full
# block's 2.9e-3). Readings: worst decided token 7.1e-3 to 8.5e-3, a block's
# median over ALL tokens 2.7e-3 to 5.0e-3 (the dense block the highest),
# 31.7% to 35.2% of each expert block's tokens compared, all of the dense
# one.
AMP_RTOL = 4e-2
AMP_MARGIN = 1e-2
AMP_COMPARED_MIN = 0.05
# The loss against the reference's, relative, over ALL tokens, both ways:
# it holds the loss's own arithmetic (the shift by one, the mean, the
# chunks); rounding averages out in a mean, so this bound does not tell
# float32 from bf16 passes (the default-precision reference reads 4.0e-6
# and 1.1e-5): the logits' does. Readings: float32 0 to 5.4e-7, amp 4.2e-6
# to 3.0e-5.
LOSS_F32_RTOL = 2e-5
LOSS_AMP_RTOL = 3e-3

LIMITS = ("F32_RTOL", "F32_MARGIN", "F32_UNDECIDED_MAX", "AMP_RTOL",
          "AMP_MARGIN", "AMP_COMPARED_MIN", "LOSS_F32_RTOL", "LOSS_AMP_RTOL")
for _name in LIMITS:
    setattr(_kimi, _name, globals()[_name])
overflow_total = _kimi.overflow_total
token_errors, clean_prefix = _kimi.token_errors, _kimi.clean_prefix
reference_outputs, lm_loss = _kimi.reference_outputs, _kimi.lm_loss
check_train, _readings = _kimi.check_train, _kimi.compare


def compare(ref, got32, got_amp, blocks):
    """The Kimi-Linear configuration's readings (its ``compare``: every
    error beside its limit), and this configuration's verdict on them: the
    same criteria, the median token held to ``F32_MEDIAN_RTOL``."""
    out = _readings(ref, got32, got_amp, blocks)
    out["f32_median_rtol"] = F32_MEDIAN_RTOL
    nothing_dropped = (out["ref_dropped"] == out["f32_dropped"]
                       == out["amp_dropped"] == 0)
    float32 = (out["f32_rel_err"] <= F32_RTOL
               and out["f32_rel_err_median"] <= F32_MEDIAN_RTOL
               and out["f32_undecided_share"] <= F32_UNDECIDED_MAX
               and max(out["loss_f32_rel_err"],
                       out["ce_f32_rel_err"]) <= LOSS_F32_RTOL)
    amp = (out["amp_rel_err"] <= AMP_RTOL
           and max(out["amp_block_medians"]) <= AMP_RTOL
           and min(out["amp_compared_share"]) >= AMP_COMPARED_MIN
           and max(out["loss_amp_rel_err"],
                   out["ce_amp_rel_err"]) <= LOSS_AMP_RTOL)
    out["ok"] = bool(np.isfinite(out["ref_max_abs"]) and nothing_dropped
                     and float32 and amp)
    return out


_kimi.compare = compare     # what its check_train calls

#: JSON key -> ``AfmoeModel`` argument, where the two agree
MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "num_shared_experts",
              "num_dense_layers", "sliding_window", "rope_theta",
              "rms_norm_eps", "route_norm", "route_scale",
              "load_balance_coeff", "mup_enabled", "initializer_range",
              "held_rows_factor")

#: toy widths for the CPU tests and rehearsal (never a cell): the same five
#: layers (dense sliding, three sliding, one full), 4 query heads on 2
#: key/value heads, a window of 8 keys, a share of 8 of 32 experts; the
#: wider initialiser keeps the router scores' spread near the published one
TOY = {"vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
       "moe_intermediate_size": 32, "num_experts": 8, "n_routed_experts": 8,
       "router_experts": 32, "held_experts": [8, 8],
       "num_experts_per_tok": 4, "sliding_window": 8,
       "initializer_range": 0.1, "held_rows_factor": 2.0,
       "reference_q_block": 16}

PREFIX = "lm."   # the parameters' names under the train wrapper
#: parameters AdamW does not decay: every norm's weight
NO_DECAY = ("norm_weight",)


def layer_types(sizes):
    """The types of the layers run: published layer i for i in
    ``run_layers`` (0, then one whole period 4-7)."""
    return [sizes["layer_types"][i] for i in sizes["run_layers"]]


def model_kwargs(sizes):
    """The constructor's arguments: the router is as wide as published
    (``router_experts``), ``num_experts`` of them are held here; the layers'
    types are the published ones of the layers run."""
    kw = {k: sizes[k] for k in MODEL_KEYS if k in sizes}
    kw["num_experts"] = sizes["router_experts"]
    kw["held_experts"] = tuple(sizes["held_experts"])
    kw["layer_types"] = layer_types(sizes)
    return kw


def build_train(seed, sizes, shapes):
    """The model, loss and optimizer of a train cell. The step's one input
    is the [rows, seq] token ids, and its label the same array."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.text.models import AfmoeModel

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
    # builder copies them onto the chip (donation), and 2.8 GB of unused
    # copy beside 11.3 GB of train state would not leave a 16k row room
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = AfmoeModel(use_recompute=True, **model_kwargs(sizes))
    opt = optimizer.AdamW(
        2.2e-4, beta1=0.9, beta2=0.95, epsilon=1e-8,
        parameters=model.parameters(), weight_decay=0.1,
        apply_decay_param_fun=lambda name: not name.endswith(NO_DECAY),
        grad_clip=nn.ClipGradByGlobalNorm(1.0))
    return {"layer": CausalLM(model), "loss_fn": lm_loss, "optimizer": opt,
            "amp_level": "O1", "sizes": sizes}


# ------------------------------------------------------------ FLOPs
def mixer_counts(sizes):
    """(sliding-window layers, full-attention layers) the step runs."""
    types = layer_types(sizes)
    sliding = types.count("sliding_attention")
    return sliding, len(types) - sliding


def block_counts(sizes):
    """(dense blocks, expert blocks) the step runs."""
    dense = min(sizes["num_dense_layers"], sizes["num_hidden_layers"])
    return dense, sizes["num_hidden_layers"] - dense


def attention_projection_flops_per_token(sizes):
    """Forward FLOPs a token of one attention sublayer's five matrices (q,
    k, v, the gate's own and o), either layer type."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return 2 * (3 * h * heads * d + 2 * h * kv * d)


def core_flops(sizes, seq, sliding, passes=1.0):
    """Forward FLOPs of one attention core on one sequence: QK^T and PV over
    d-wide heads, every QUERY head, on the (query, key) pairs the layer's
    mask admits — the band's ``sum_i min(i + 1, window)`` for a sliding
    layer, the causal seq (seq + 1) / 2 for a full one; ``passes`` scales.
    (The readers' functions: ``swa_flash_roofline`` and
    ``gqa_flash_roofline`` through ``global_flash_roofline``.)"""
    heads, d = sizes["num_attention_heads"], sizes["head_dim"]
    if sliding:
        reader = cells.load_module("layer_metrics", "swa_flash_roofline")
        return passes * reader.band_flops(
            1, heads, seq, sizes["sliding_window"], d, 1, 0)
    reader = cells.load_module("layer_metrics", "global_flash_roofline")
    return passes * reader.core_flops(1, heads, seq, d, 1, 0)


#: forward FLOPs a token of one expert layer's HELD routed experts (a
#: token's k choices land here with probability held / all): the same keys
held_expert_flops_per_token = _kimi.held_expert_flops_per_token


def flops_per_sample(sizes, shapes):
    """Floating-point operations one sample (one sequence) needs in a train
    step, from shapes: 2 per multiply-add of the forward pass, times 3 for
    forward + backward; NOTHING recomputed (the blocks' second forward is
    the program's choice, not the model's work), and of a sliding layer's
    core ONLY THE BAND (what a kernel computes beside it is no work of the
    model's). Counted: every attention sublayer's five matrices, the
    sliding cores over the band's pairs and the full core over the causal
    pairs, the dense block's SwiGLU, in every expert block the router over
    all 128, the shared expert and ONLY THE HELD experts' rows (tokens x 8
    x 16 / 128), and the head over the held vocabulary slice."""
    seq = shapes["input_ids"][0]
    h = sizes["hidden_size"]
    sliding, full = mixer_counts(sizes)
    dense, expert = block_counts(sizes)
    per_token = (
        (sliding + full) * attention_projection_flops_per_token(sizes)
        + dense * 3 * 2 * h * sizes["intermediate_size"]
        + expert * (2 * h * sizes["router_experts"]
                    + sizes["num_shared_experts"] * 3 * 2 * h
                    * sizes["moe_intermediate_size"]
                    + held_expert_flops_per_token(sizes))
        + 2 * h * sizes["vocab_size"])
    return 3.0 * (seq * per_token
                  + sliding * core_flops(sizes, seq, True)
                  + full * core_flops(sizes, seq, False))
