"""lfm2-8b-a1b: LiquidAI/LFM2-8B-A1B (HF ``lfm2_moe``, 8.3B-A1.5B) at its
published widths, cut to ONE CHIP'S SHARE of a 4-chip expert-parallel
deployment — exactly one four-chip host — (``lfm2-8b-a1b.json`` holds every
size, the cut's arithmetic and what it distorts): published layer 0 (the
doubly gated short convolution, dense SwiGLU) and published layers 2-5
(grouped-query attention, then three short convolutions: one whole period,
each with the expert layer), 8 of the 32 routed experts held in each expert
layer, a quarter of the tied vocabulary, four rows of 8,192 tokens a step.
Built with ``text.models.Lfm2Model``: ``ops.linear_attention
.gated_short_conv`` between the mixer's two projections, the streaming
flash kernel at 32 query heads of 64 (K and V repeated from 8) with QK-norm
and full-width RoPE, ``incubate.moe.MoELayer``'s sigmoid bias-balanced
router over all 32 experts with the held range on the dropless sorted path
through 1,792-wide grouped matmuls, per-block recomputation with the
kernel's residuals kept, and a tied head loss that never holds the logits.
The train recipe lives here; what differs from the source is listed under
``departures`` in the JSON."""
import jax

from benchmark.harness import cells

# The check's rule is the Kimi-Linear configuration's (and through it the
# JoyAI configuration's), for their reason: a swapped expert does not stay
# where it happened — a token that takes another expert in one layer reaches
# the two tokens after it through every later short convolution and every
# later token of its row through the attention layer. It runs on THIS
# module's own copy of that module (``load_module`` makes a new one a call),
# given this configuration's limits below, and on ``CHECK_ROWS`` rows of the
# step's four, so that a row boundary lies inside what is compared: the
# short convolution's history is a row's own, and a program that fed row 1
# the tail of row 0 is caught on the chip (tools/lfm2_check.py
# --rows-joined) and not only in the CPU tests.
_kimi = cells.load_module("configs", "kimi-linear-48b-a3b")
CHECK_ROWS = 2

# FLOAT32, whole model, at logit level: the framework's float32 forward
# (precision "highest"; the streaming kernel with float32 operands) against
# the float32 reference (three shifted multiply-adds, every key under an
# explicit mask), the logits token by token as a share of the largest
# reference logit, the worst over each row's CLEAN PREFIX — the tokens
# whose router is DECIDED in every expert layer (margin at least
# F32_MARGIN) before the row's first undecided token that is over the bound
# — and the median over ALL tokens, held to the same limit (a swapped
# expert reaches two tokens through a convolution and the rest of its row
# through ONE attention layer at 1 / position of its weight: the median
# token does not hear it). Readings (my chip runs, PR 44, TPU v5 lite, 2 x
# 8,192 tokens a seed; 13 seeds): 6.18e-7 to 7.27e-7 worst token, 4.1e-7 to
# 4.4e-7 median, 0.10-0.21% of the tokens under F32_MARGIN; two seeds
# swapped one expert (57.5% and 68.9% of their rows compared), the others
# compared 99.8-99.9%. The float32
# reference itself at the TPU's default precision (bf16 passes, the nearest
# precision below): 0.149 and 0.154 at the worst token of its clean prefix
# (3-5% of the rows: its router scores shift by up to 2.6e-2 and experts
# swap early), 8.5e-3 and 8.0e-3 at the median token — it fails the bound
# on both by two orders or more.
F32_RTOL = 2e-5
F32_MARGIN = 1e-5
F32_UNDECIDED_MAX = 0.05
# AMP O1, block by block: the reference block in float32 is fed the
# program's own (amp) block input and compared with the program's output at
# the tokens decided IN THAT BLOCK by AMP_MARGIN; the error is a share of
# the block's own largest contribution (output minus input). The dense
# block has no router: all its tokens count. A conv block's bf16 in_proj
# and out_proj around float32 gates and taps, and the attention block's
# bf16 scores at d 64 over up to 8,192 keys, are under this bound; a conv
# block whose taps sit a token late is over it at every token
# (tools/lfm2_check.py --taps-shifted: 1.50 at the worst decided token, the
# four conv blocks' medians 0.68 to 0.87, the attention block's 5.2e-4), and
# one whose second row reads the first row's last two tokens at that row's
# first two tokens (--rows-joined: 0.677 at the worst decided token, every
# block's median where it was; the float32 half reads 0.78 there).
# Readings: worst decided token 8.0e-3 to 1.04e-2, a block's median over
# ALL tokens 5.6e-4 (the attention block) to 5.8e-3 (the dense conv block),
# 68.6% to 70.2% of each expert block's tokens compared (a quarter of the
# experts is held and the scores lie close: more margins are wide than on
# the other configurations' 20-35%), all of the dense one.
AMP_RTOL = 4e-2
AMP_MARGIN = 1e-2
AMP_COMPARED_MIN = 0.05
# The loss against the reference's, relative, over ALL tokens, both ways:
# it holds the loss's own arithmetic (the shift by one, the mean, the
# chunks, the tied weight's transpose); rounding averages out in a mean, so
# this bound does not tell float32 from bf16 passes (the default-precision
# reference reads 2.8e-5 and 1.3e-5): the logits' does. Readings: float32
# 0 to 4.7e-7, amp 6.8e-6 to 6.7e-5.
LOSS_F32_RTOL = 2e-5
LOSS_AMP_RTOL = 3e-3

LIMITS = ("F32_RTOL", "F32_MARGIN", "F32_UNDECIDED_MAX", "AMP_RTOL",
          "AMP_MARGIN", "AMP_COMPARED_MIN", "LOSS_F32_RTOL", "LOSS_AMP_RTOL")
for _name in LIMITS:
    setattr(_kimi, _name, globals()[_name])
token_errors, clean_prefix = _kimi.token_errors, _kimi.clean_prefix
reference_outputs, lm_loss = _kimi.reference_outputs, _kimi.lm_loss


def check_train(built, reference, sizes, shapes, probe_x, rows=CHECK_ROWS):
    """The Kimi-Linear configuration's check (what the timed program
    computes in train mode against ``references/lfm2-8b-a1b.py``: float32
    at logit level over the whole model, amp block by block, the loss both
    ways, the held share's overflow) on the first ``rows`` probe rows of
    8,192 tokens: the second row's first tokens have a row before them."""
    return _kimi.check_train(built, reference, sizes, shapes, probe_x,
                             rows=rows)


#: JSON key -> ``Lfm2Model`` argument, where the two agree
MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads",
              "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "num_dense_layers", "conv_L_cache",
              "rope_theta", "norm_eps", "norm_topk_prob", "use_expert_bias",
              "routed_scaling_factor", "bias_update_speed",
              "initializer_range", "held_rows_factor")

#: toy widths for the CPU tests and rehearsal (never a cell): the same five
#: layers (dense conv, attention, three conv), 4 query heads of 16 on 2
#: key/value heads, a share of 8 of 32 experts; the wider initialiser keeps
#: the router scores' spread near the published one
TOY = {"vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
       "moe_intermediate_size": 32, "num_experts": 8, "n_routed_experts": 8,
       "router_experts": 32, "held_experts": [8, 8],
       "num_experts_per_tok": 4, "initializer_range": 0.1,
       "held_rows_factor": 2.0, "reference_q_block": 16}

PREFIX = "lm."   # the parameters' names under the train wrapper
#: parameters AdamW does not decay: every norm's weight
NO_DECAY = ("norm_weight",)


def layer_types(sizes):
    """The types of the layers run: published layer i for i in
    ``run_layers`` (0, then one whole period 2-5)."""
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
    from paddle_tpu.text.models import Lfm2Model

    class CausalLM(nn.Layer):
        """What the loss needs instead of logits: the final hidden states
        and the head's weight — the embedding's, transposed."""

        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, input_ids):
            return self.lm.features(input_ids), self.lm.lm_head.weight

    paddle.seed(seed)
    # the eager copy of the parameters stays in host memory: the step
    # builder copies them onto the chip (donation), and 2.0 GB of unused
    # copy beside 8.1 GB of train state is room a 32,768-token step needs
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = Lfm2Model(use_recompute=True, **model_kwargs(sizes))
    opt = optimizer.AdamW(
        2.2e-4, beta1=0.9, beta2=0.95, epsilon=1e-8,
        parameters=model.parameters(), weight_decay=0.1,
        apply_decay_param_fun=lambda name: not name.endswith(NO_DECAY),
        grad_clip=nn.ClipGradByGlobalNorm(1.0))
    return {"layer": CausalLM(model), "loss_fn": lm_loss, "optimizer": opt,
            "amp_level": "O1", "sizes": sizes}


# ------------------------------------------------------------ FLOPs
def mixer_counts(sizes):
    """(short-convolution layers, attention layers) the step runs."""
    types = layer_types(sizes)
    conv = types.count("conv")
    return conv, len(types) - conv


def block_counts(sizes):
    """(dense blocks, expert blocks) the step runs."""
    dense = min(sizes["num_dense_layers"], sizes["num_hidden_layers"])
    return dense, sizes["num_hidden_layers"] - dense


def shortconv_flops_per_token(sizes):
    """Forward FLOPs a token of one short-convolution mixer's two matrices
    (hidden -> 3 x hidden and hidden -> hidden; the 3 taps and the two
    gates a channel are no matmul and are not counted)."""
    h = sizes["hidden_size"]
    return 2 * (3 * h * h + h * h)


def attention_projection_flops_per_token(sizes):
    """Forward FLOPs a token of the attention sublayer's four matrices."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return 2 * (2 * h * heads * d + 2 * h * kv * d)


def core_flops(sizes, seq, passes=1.0):
    """Forward FLOPs of the attention core on one sequence: QK^T and PV
    over d-wide heads, every QUERY head, on the causal seq (seq + 1) / 2
    pairs; ``passes`` scales. (The reader's function:
    ``attn64_flash_roofline`` through ``gqa_flash_roofline``.)"""
    reader = cells.load_module("layer_metrics", "attn64_flash_roofline")
    return passes * reader.core_flops(
        1, sizes["num_attention_heads"], seq, sizes["head_dim"], 1, 0)


#: forward FLOPs a token of one expert layer's HELD routed experts (a
#: token's k choices land here with probability held / all): the same keys
held_expert_flops_per_token = _kimi.held_expert_flops_per_token


def flops_per_sample(sizes, shapes):
    """Floating-point operations one sample (one sequence) needs in a train
    step, from shapes: 2 per multiply-add of the forward pass, times 3 for
    forward + backward; NOTHING recomputed (the blocks' second forward is
    the program's choice, not the model's work). Counted: every short
    convolution's two matrices, the attention sublayer's four and its core
    over the causal pairs, the dense block's SwiGLU, in every expert block
    the router over all 32 and ONLY THE HELD experts' rows (tokens x 4 x 8
    / 32), and the tied head over the held vocabulary slice."""
    seq = shapes["input_ids"][0]
    h = sizes["hidden_size"]
    conv, attn = mixer_counts(sizes)
    dense, expert = block_counts(sizes)
    per_token = (
        conv * shortconv_flops_per_token(sizes)
        + attn * attention_projection_flops_per_token(sizes)
        + dense * 3 * 2 * h * sizes["intermediate_size"]
        + expert * (2 * h * sizes["router_experts"]
                    + held_expert_flops_per_token(sizes))
        + 2 * h * sizes["vocab_size"])
    return 3.0 * (seq * per_token + attn * core_flops(sizes, seq))
