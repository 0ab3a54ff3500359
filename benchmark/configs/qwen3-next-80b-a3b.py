"""qwen3-next-80b-a3b: Qwen/Qwen3-Next-80B-A3B-Instruct at its published
widths, cut to ONE CHIP'S SHARE of a 16-chip expert-parallel deployment
(``qwen3-next-80b-a3b.json`` holds every size, the cut's arithmetic and what
it distorts): the model's first four layers — three that mix tokens with
Gated DeltaNet and one with gated grouped-query attention, every one with
the expert layer — 32 of the 512 routed experts held in each, an eighth of
the vocabulary, one row of 16,384 tokens a step. Built with
``text.models.Qwen3NextModel``: the gated delta rule's scan with ONE decay a
head (``ops.linear_attention``, its Mosaic kernels on the chip), one short
convolution over q | k | v (``nn.CausalDepthwiseConv1D``), the streaming
flash kernel at d 256, ``incubate.moe.MoELayer``'s softmax router over all
512 experts, top-10 renormalised, with the held range on the dropless sorted
path and a shared expert behind a gate of its own, per-block recomputation,
and a head loss that never holds the logits. The train recipe lives here;
what differs from the source is listed under ``departures`` in the JSON."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import cells

# the check's rule is the JoyAI configuration's and its set-up the
# Kimi-Linear configuration's: the trace, the overflow count, the per-token
# error, the clean prefix, the tapped forward and the block-by-block
# comparison are used as they are
_joyai = cells.load_module("configs", "joyai-llm-flash")
_kimi = cells.load_module("configs", "kimi-linear-48b-a3b")
overflow_total = _joyai.overflow_total
token_errors, clean_prefix = _joyai.token_errors, _joyai.clean_prefix
_framework, _block_errors = _kimi._framework, _kimi._block_errors
reference_outputs, lm_loss = _kimi.reference_outputs, _kimi.lm_loss

# The check has the two halves of the JoyAI and Kimi-Linear configurations',
# for their reason: a swapped expert does not stay where it happened (a
# token that takes another expert in one layer reaches every later token of
# the row through the DeltaNet state of every later layer and through
# attention), and top-10 of 512 swaps more often than top-8 of 256. The
# router's margin is the gap between a token's 10th and 11th router LOGIT
# (the softmax keeps their order; a probability's gap would shrink with the
# other 510).
#
# FLOAT32, whole model, at logit level: the framework's float32 forward
# (precision "highest"; the scan's kernels on the chip) against the float32
# reference (the recurrence token by token), the logits token by token as a
# share of the largest reference logit, the worst over the row's CLEAN
# PREFIX — the tokens whose router is DECIDED in every layer (margin at
# least F32_MARGIN) before the row's first undecided token that is over the
# bound — and the median over ALL tokens, which no swap moves. Readings (my
# chip runs, PR 38: tools/qwen3_next_check.py and the cell's own runs, TPU v5
# lite, 16,384 tokens a seed; 14 seeds): 3.47e-6 to 4.46e-6 worst token,
# 2.1e-6 to 2.4e-6 median, 99.87-99.91% of the row compared, 0.09-0.13% of
# the tokens under F32_MARGIN. The float32 reference itself at the TPU's
# default precision (bf16 passes, the nearest precision below): 3.6e-2 and
# 4.8e-2 at the worst token of its clean prefix, 1.0e-2 at the median token:
# it fails the bound on both by three orders.
F32_RTOL = 2e-5
F32_MARGIN = 1e-5
F32_UNDECIDED_MAX = 0.05
# AMP O1, block by block: the reference block in float32 is fed the
# program's own (amp) block input and compared with the program's output at
# the tokens decided IN THAT BLOCK by AMP_MARGIN; the error is a share of
# the block's own largest contribution (output minus input). A DeltaNet
# block's bf16 products and the state they build up, and the attention
# block's bf16 scores at d 256, are under this bound; a wrong block is off
# by O(1) of its contribution at every token. Readings: worst decided token
# 0.82e-2 to 0.96e-2, a block's median over ALL tokens 1.1e-3 (the attention
# block) to 5.2e-3, 33% to 35% of each block's tokens compared (a layer's
# 10th and 11th logits are 0.037 apart in the mean).
AMP_RTOL = 4e-2
AMP_MARGIN = 4e-2
AMP_COMPARED_MIN = 0.05
# The loss against the reference's, relative, over ALL tokens, both ways:
# it holds the loss's own arithmetic (the shift by one, the mean, the
# chunks, the balance term's weight); rounding averages out in a mean, so
# this bound does not tell float32 from bf16 passes (the default-precision
# reference reads 1.5e-5 and 2.4e-5): the logits' does. Readings: float32
# 0 to 9e-8, amp 1.1e-5 to 2.4e-5.
LOSS_F32_RTOL = 2e-5
LOSS_AMP_RTOL = 3e-3

#: JSON key -> ``Qwen3NextModel`` argument, where the two differ
MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "partial_rotary_factor", "rope_theta",
              "full_attention_interval", "linear_num_key_heads",
              "linear_num_value_heads", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim",
              "moe_intermediate_size", "shared_expert_intermediate_size",
              "num_experts_per_tok", "norm_topk_prob",
              "router_aux_loss_coef", "rms_norm_eps", "gdn_chunk",
              "initializer_range", "held_rows_factor")

#: toy widths for the CPU tests and rehearsal (never a cell): the same four
#: layer types, 2 key heads serving 4 value heads, 4 query heads on 2
#: key/value heads, a share of 8 of 32 experts; the wider initialiser keeps
#: the router logits' spread near the published one
TOY = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
       "linear_num_key_heads": 2, "linear_num_value_heads": 4,
       "linear_key_head_dim": 16, "linear_value_head_dim": 16,
       "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
       "num_experts": 8, "n_routed_experts": 8, "router_experts": 32,
       "held_experts": [8, 8], "num_experts_per_tok": 4, "gdn_chunk": 16,
       "initializer_range": 0.1, "held_rows_factor": 2.0}

PREFIX = "lm."   # the parameters' names under the train wrapper
#: parameters AdamW does not decay: the decay's scale and its bias, and
#: every norm's weight
NO_DECAY = ("A_log", "dt_bias", "norm_weight")


def model_kwargs(sizes):
    """The constructor's arguments: the router is as wide as published
    (``router_experts``), ``num_experts`` of them are held here."""
    kw = {k: sizes[k] for k in MODEL_KEYS if k in sizes}
    kw["num_experts"] = sizes["router_experts"]
    kw["held_experts"] = tuple(sizes["held_experts"])
    return kw


def build_train(seed, sizes, shapes):
    """The model, loss and optimizer of a train cell. The step's one input
    is the [rows, seq] token ids, and its label the same array."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.text.models import Qwen3NextModel

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
    # copy beside 10.0 GB of train state would not leave a 16k row room
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = Qwen3NextModel(use_recompute=True, **model_kwargs(sizes))
    opt = optimizer.AdamW(
        2.2e-4, beta1=0.9, beta2=0.95, epsilon=1e-8,
        parameters=model.parameters(), weight_decay=0.1,
        apply_decay_param_fun=lambda name: not name.endswith(NO_DECAY),
        grad_clip=nn.ClipGradByGlobalNorm(1.0))
    return {"layer": CausalLM(model), "loss_fn": lm_loss, "optimizer": opt,
            "amp_level": "O1", "sizes": sizes}


# ------------------------------------------------------------ the check
def check_train(built, reference, sizes, shapes, probe_x, rows=1):
    """What the timed program computes on the first probe row at the timed
    size (train mode, recomputation on, the scan's kernels; the reference's
    attention runs in query blocks so that its [heads, block, 16384] scores
    fit, its delta rule token by token) against
    ``references/qwen3-next-80b-a3b.py``: in float32 at logit level over the
    whole model, under the cell's amp level block by block, the loss both
    ways, and the held share's overflow count — of this forward and of the
    train steps made so far (the layer's buffers). The programs run one
    after the other and hand their results to the host, so that each one's
    activations are gone before the next one's arrive beside the 10.0 GB of
    train state."""
    layer = built["layer"]
    layer.train()
    x = probe_x[:rows]
    device = next(iter(x.devices()))
    params, buffers = jax.device_put(layer.functional_state(), device)
    steps_overflow = int(overflow_total(buffers))
    # this forward's own count starts from nothing
    buffers = {n: jnp.zeros_like(v) if n.endswith("held_overflow") else v
               for n, v in buffers.items()}
    ref = jax.device_get(jax.jit(reference_outputs(reference, sizes))(
        params, buffers, x))
    with jax.default_matmul_precision("highest"):
        got32 = jax.device_get(jax.jit(
            lambda p, b, a: _framework(built, p, b, None, a)[:4])(
                params, buffers, x))
    *got_amp, states = jax.jit(lambda p, b, a: _framework(
        built, p, b, built["amp_level"], a))(params, buffers, x)
    got_amp = jax.device_get(got_amp)    # the logits leave the chip first
    blocks = _block_errors(reference, sizes, params, buffers, states)
    out = compare(ref, got32, got_amp, blocks)
    out["overflow_train_steps"] = steps_overflow
    out["ok"] = out["ok"] and steps_overflow == 0
    return out


def compare(ref, got32, got_amp, blocks):
    """The check's dict from the reference's outputs (logits, total, cross-
    entropy, dropped, margins, landed), the framework's in float32 and
    under amp ((logits, total, cross-entropy, dropped) each) and the amp
    half's per-block (errors, margins)."""
    margin = np.asarray(ref[4], np.float32)
    out = {"ref_loss": float(ref[1]), "ref_ce": float(ref[2]),
           "tokens": int(margin.size), "ref_dropped": int(ref[3]),
           "held_pairs_landed": [int(v) for v in ref[5]],
           "ref_max_abs": float(np.abs(np.asarray(ref[0])).max())}
    ok = bool(np.isfinite(np.asarray(ref[0])).all() and out["ref_dropped"] == 0)

    def loss_errors(tag, got, rtol):
        errs = {name: abs(float(got[i]) - float(ref[i])) / abs(float(ref[i]))
                for name, i in (("loss", 1), ("ce", 2))}
        out.update({f"{name}_{tag}_rel_err": e for name, e in errs.items()})
        out[f"loss_{tag}_rtol"] = rtol
        out[f"{tag}_dropped"] = int(got[3])
        return max(errs.values()) <= rtol and int(got[3]) == 0

    # float32: logits, worst decided token of the clean prefix
    decided = margin >= F32_MARGIN
    errs = token_errors(ref[0], got32[0])
    compared = decided & clean_prefix(errs, decided, F32_RTOL)
    err = float(errs[compared].max()) if compared.any() else float("inf")
    out.update({"f32_rel_err": err, "f32_rtol": F32_RTOL,
                "f32_rel_err_all_tokens": float(errs.max()),
                "f32_rel_err_median": float(np.median(errs)),
                "f32_compared_share": float(compared.mean()),
                "f32_undecided_share": 1.0 - float(decided.mean()),
                "f32_undecided_max": F32_UNDECIDED_MAX})
    losses_ok = loss_errors("f32", got32, LOSS_F32_RTOL)
    ok = (ok and err <= F32_RTOL and np.median(errs) <= F32_RTOL
          and out["f32_undecided_share"] <= F32_UNDECIDED_MAX and losses_ok)

    # amp: block by block
    worst, shares, medians = 0.0, [], []
    for errs, block_margin in blocks:
        errs, block_margin = np.asarray(errs), np.asarray(block_margin)
        decided = block_margin >= AMP_MARGIN
        shares.append(float(decided.mean()))
        medians.append(float(np.median(errs)))
        worst = max(worst, float(errs[decided].max()) if decided.any()
                    else float("inf"))
    out.update({"amp_rel_err": worst, "amp_rtol": AMP_RTOL,
                "amp_block_medians": medians, "amp_compared_share": shares,
                "amp_compared_min": AMP_COMPARED_MIN})
    losses_ok = loss_errors("amp", got_amp, LOSS_AMP_RTOL)
    ok = (ok and worst <= AMP_RTOL and max(medians) <= AMP_RTOL
          and min(shares) >= AMP_COMPARED_MIN and losses_ok)
    out["ok"] = bool(ok)
    return out


# ------------------------------------------------------------ FLOPs
def mixer_counts(sizes):
    """(Gated DeltaNet layers, gated-attention layers) the step runs."""
    full = sizes["num_hidden_layers"] // sizes["full_attention_interval"]
    return sizes["num_hidden_layers"] - full, full


def gdn_projection_flops_per_token(sizes):
    """Forward FLOPs a token of one Gated DeltaNet sublayer's three
    matrices: the fused q | k | v | z, the fused b | a, and the output (the
    convolution's 4 taps a channel are no matmul and are not counted)."""
    h = sizes["hidden_size"]
    key = sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
    value = sizes["linear_num_value_heads"] * sizes["linear_value_head_dim"]
    return 2 * (h * (2 * key + 2 * value)
                + h * 2 * sizes["linear_num_value_heads"] + value * h)


def gdn_core_flops_per_token(sizes):
    """Forward FLOPs a token of one Gated DeltaNet layer's chunked scan, all
    value heads (the reader's function of heads, widths and the chunk)."""
    reader = cells.load_module("layer_metrics", "gdn_core_roofline")
    return reader.gdn_core_flops(
        1, sizes["linear_num_value_heads"], sizes["linear_key_head_dim"],
        sizes["linear_value_head_dim"], sizes["gdn_chunk"])


def gqa_projection_flops_per_token(sizes):
    """Forward FLOPs a token of one gated-attention sublayer's four
    matrices (the query's twice as wide: query and gate)."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return 2 * (h * 2 * heads * d + 2 * h * kv * d + heads * d * h)


def gqa_core_flops(sizes, seq, passes=1.0):
    """Forward FLOPs of one gated-attention core on one sequence: QK^T and
    PV over d-wide heads, every QUERY head, on the causal seq (seq + 1) / 2
    pairs; ``passes`` scales."""
    reader = cells.load_module("layer_metrics", "gqa_flash_roofline")
    return passes * reader.core_flops(1, sizes["num_attention_heads"], seq,
                                      sizes["head_dim"], 1, 0)


def held_expert_flops_per_token(sizes):
    """Forward FLOPs a token of one expert layer's HELD routed experts:
    a token's k choices land here with probability held / all."""
    return (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
            / sizes["router_experts"] * 3 * 2 * sizes["hidden_size"]
            * sizes["moe_intermediate_size"])


def shared_expert_flops_per_token(sizes):
    """Forward FLOPs a token of one layer's shared expert and its gate."""
    h = sizes["hidden_size"]
    return 3 * 2 * h * sizes["shared_expert_intermediate_size"] + 2 * h


def flops_per_sample(sizes, shapes):
    """Floating-point operations one sample (one sequence) needs in a train
    step, from shapes: 2 per multiply-add of the forward pass, times 3 for
    forward + backward; NOTHING recomputed (the blocks' second forward is
    the program's choice, not the model's work). Counted: every Gated
    DeltaNet layer's projections and its chunked scan's own products, the
    gated-attention layer's projections and causal core, in every layer the
    router over all 512, the shared expert with its gate and ONLY THE HELD
    experts' rows (tokens x 10 x 32 / 512), and the head over the held
    vocabulary slice."""
    seq = shapes["input_ids"][0]
    h = sizes["hidden_size"]
    gdn, gqa = mixer_counts(sizes)
    per_token = (
        gdn * (gdn_projection_flops_per_token(sizes)
               + gdn_core_flops_per_token(sizes))
        + gqa * gqa_projection_flops_per_token(sizes)
        + sizes["num_hidden_layers"] * (
            2 * h * sizes["router_experts"]
            + shared_expert_flops_per_token(sizes)
            + held_expert_flops_per_token(sizes))
        + 2 * h * sizes["vocab_size"])
    return 3.0 * (seq * per_token + gqa * gqa_core_flops(sizes, seq))
