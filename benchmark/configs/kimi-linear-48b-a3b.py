"""kimi-linear-48b-a3b: moonshotai/Kimi-Linear-48B-A3B-Instruct at its
published widths, cut to ONE CHIP'S SHARE of a 32-chip expert-parallel
deployment (``kimi-linear-48b-a3b.json`` holds every size, the cut's
arithmetic and what it distorts): the model's first five layers — the dense
layer and four expert layers, four of them mixing tokens with Kimi Delta
Attention and one with latent attention without positions — 8 of the 256
routed experts held in each expert layer, an eighth of the vocabulary, one
row of 16,384 tokens a step. Built with ``text.models.KimiLinearModel``:
the gated delta rule's chunked scan (``ops.linear_attention``), the short
convolutions of ``nn.CausalDepthwiseConv1D``, ``MLAttention(q_lora_rank=
None, rope=False)`` on the streaming flash kernel, ``incubate.moe
.MoELayer``'s sigmoid bias-balanced router over all 256 experts with the
held range on the dropless sorted path, a shared expert, per-block
recomputation, and a head loss that never holds the logits. The train
recipe lives here; what differs from the source is listed under
``departures`` in the JSON."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import cells

# the check's rule is the JoyAI configuration's: its trace set-up, overflow
# count, per-token error and clean prefix are used as they are
_joyai = cells.load_module("configs", "joyai-llm-flash")
_traced, overflow_total = _joyai._traced, _joyai.overflow_total
token_errors, clean_prefix = _joyai.token_errors, _joyai.clean_prefix

# The check has the two halves of the JoyAI configuration's, for its reason:
# a swapped expert does not stay where it happened. Here a token that takes
# another expert in one layer reaches every later token of the row through
# the KDA state of every later layer as well as through latent attention.
#
# FLOAT32, whole model, at logit level: the framework's float32 forward
# (precision "highest"; the chunked scan) against the float32 reference (the
# recurrence token by token), the logits token by token as a share of the
# largest reference logit, the worst over the row's CLEAN PREFIX — the
# tokens whose router is DECIDED in every expert layer (8th and 9th biased
# scores further apart than F32_MARGIN) before the row's first undecided
# token that is over the bound — and the median over ALL tokens, which no
# swap moves. Readings (my chip runs, PR 32: tools/kimi_check.py and the
# cell's own runs, TPU v5 lite, 16,384 tokens a seed; 21 seeds): 3.45e-6 to
# 5.78e-6 worst token, 2.38e-6 to 3.15e-6 median; three seeds swapped one
# expert (51%, 73%, 91% of the row compared), the others compared 99.3-99.5%;
# 0.5-0.7% of the tokens are under F32_MARGIN. The float32 reference itself
# at the TPU's default precision (bf16 passes, the nearest precision below):
# 4.6e-2 and 4.9e-2 at the worst token of its clean prefix, 9.1e-3 and 8.8e-3
# at the median token: it fails the bound on both by two orders or more. The
# reference's recurrence takes its decay through an exp of its own: the
# TPU's float32 exp is good to 5e-6, which the recurrence compounds over a
# slow channel's memory (against it the program read 6.1e-5 at the median).
F32_RTOL = 2e-5
F32_MARGIN = 1e-5
F32_UNDECIDED_MAX = 0.05
# AMP O1, block by block: the reference block in float32 is fed the
# program's own (amp) block input and compared with the program's output at
# the tokens decided IN THAT BLOCK by AMP_MARGIN; the error is a share of
# the block's own largest contribution (output minus input). The dense
# block has no router: all its tokens count. A KDA block's bf16 products
# (q, k, v, the chunk's [C, C] and [C, d] operands) and the state they
# build up are under this bound; a wrong block is off by O(1) of its
# contribution at every token. Readings: worst decided token 8.4e-3 to
# 1.05e-2, a block's median over ALL tokens 2.1e-3 to 6.4e-3 (the dense KDA
# block the highest, the latent-attention block the lowest), 20.5% to 22.0%
# of each expert block's tokens compared, all of the dense one.
AMP_RTOL = 4e-2
AMP_MARGIN = 1e-2
AMP_COMPARED_MIN = 0.05
# The loss against the reference's, relative, over ALL tokens, both ways:
# it holds the loss's own arithmetic (the shift by one, the mean, the
# chunks, the balance term's weight). Readings: float32 0.0 to 1.8e-7, amp
# 2.1e-6 to 1.9e-5; rounding averages out in a mean, so this bound does not
# tell float32 from bf16 passes (the default-precision reference reads
# 3.0e-6 and 7.5e-6): the logits' bound above does that.
LOSS_F32_RTOL = 2e-5
LOSS_AMP_RTOL = 3e-3

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "intermediate_size",
              "moe_intermediate_size", "num_experts_per_tok",
              "n_shared_experts", "first_k_dense_replace", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "rms_norm_eps", "routed_scaling_factor", "linear_attn_config",
              "kda_gate_rank", "kda_chunk", "bias_update_speed",
              "balance_loss_weight", "initializer_range", "held_rows_factor")

#: toy widths for the CPU tests and rehearsal (never a cell): the same five
#: layer types, a share of 8 of 32 experts; the wider initialiser keeps the
#: router logits' spread near the published one
TOY = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 5,
       "num_attention_heads": 4, "intermediate_size": 96,
       "moe_intermediate_size": 32, "n_routed_experts": 8,
       "router_experts": 32, "num_experts": 32, "held_experts": [8, 8],
       "num_experts_per_tok": 4, "num_experts_per_token": 4,
       "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "kda_gate_rank": 16, "kda_chunk": 16,
       "linear_attn_config": {
           "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
           "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4},
       "initializer_range": 0.1, "held_rows_factor": 2.0}

PREFIX = "lm."   # the parameters' names under the train wrapper
#: parameters AdamW does not decay: the decay's scale and its bias
NO_DECAY = ("A_log", "dt_bias")


def model_kwargs(sizes):
    """The constructor's arguments: the router is as wide as published
    (``router_experts``), ``n_routed_experts`` of them are held here."""
    kw = {k: sizes[k] for k in MODEL_KEYS if k in sizes}
    kw["n_routed_experts"] = sizes["router_experts"]
    kw["norm_topk_prob"] = sizes["moe_renormalize"]
    kw["held_experts"] = tuple(sizes["held_experts"])
    return kw


def lm_loss(out, input_ids):
    """The cross-entropy from (final hidden states, the head's weight):
    position i's label is token i + 1."""
    from paddle_tpu.text.models import mtp_lm_loss

    hidden, head = out
    return mtp_lm_loss(hidden, [], head, input_ids)[0]._value


def build_train(seed, sizes, shapes):
    """The model, loss and optimizer of a train cell. The step's one input
    is the [rows, seq] token ids, and its label the same array."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.text.models import KimiLinearModel

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
    # builder copies them onto the chip (donation), and 2.4 GB of unused
    # copy beside 9.6 GB of train state would not leave a 16k row room
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = KimiLinearModel(use_recompute=True, **model_kwargs(sizes))
    opt = optimizer.AdamW(
        2.2e-4, beta1=0.9, beta2=0.95, epsilon=1e-8,
        parameters=model.parameters(), weight_decay=0.1,
        apply_decay_param_fun=lambda name: not name.endswith(NO_DECAY),
        grad_clip=nn.ClipGradByGlobalNorm(1.0))
    return {"layer": CausalLM(model), "loss_fn": lm_loss, "optimizer": opt,
            "amp_level": "O1", "sizes": sizes}


# ------------------------------------------------------------ the check
def _framework(built, params, buffers, amp_level, x):
    """The framework's forward as the step runs it (train mode, recomputed
    blocks, the wrapper's forward, the cell's loss), tapped a block:
    (logits, total loss, cross-entropy, pairs dropped, [(block input, block
    output) a block])."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.text.models import mtp_lm_loss

    layer = built["layer"]
    lm = layer.lm

    def fn():
        ids = Tensor(x, stop_gradient=True)
        with lm.tapped() as taps:
            hidden, head = layer.forward(ids)
        ce = mtp_lm_loss(hidden, [], head, ids)[0]
        states = [(inputs[0]._value, out._value) for _, inputs, out in taps]
        return lm.lm_head(hidden)._value, ce._value, states

    (logits, ce, states), aux, new_buffers = _traced(
        layer, params, buffers, amp_level, fn)
    dropped = overflow_total(new_buffers) - overflow_total(buffers)
    return logits, ce + aux, ce, dropped, states


def _strip(tree):
    return {k[len(PREFIX):]: v for k, v in tree.items()}


def reference_outputs(reference, sizes, precision="highest"):
    """(params, buffers, ids) -> the reference's (logits, total loss,
    cross-entropy, pairs dropped, router margins, pairs landed a block) on
    the wrapper's parameter names."""
    def fn(params, buffers, ids):
        w = {**_strip(params), **_strip(buffers)}
        logits, total, ce, _, dropped, margin, landed = reference.outputs(
            w, ids, sizes, precision=precision)
        return logits, total, ce, dropped, margin, landed

    return fn


def _block_errors(reference, sizes, params, buffers, states):
    """The amp half: for every tapped block, the reference block in float32
    on the program's own input -> (each token's error as a share of the
    block's largest contribution, each token's router margin in it). One
    small program a block (three kinds of block: three compiles), so that
    no program holds two blocks' float32 intermediates."""
    w = {**_strip(params), **_strip(buffers)}

    def errors(w_block, start, got, dense, mixer):
        with jax.default_matmul_precision("highest"):
            start = start.astype(jnp.float32)
            ref, _, margin, _, _ = reference.block(
                reference._f32(w_block), start, sizes, "", dense, mixer)
            err = jnp.abs(got.astype(jnp.float32) - ref).max(axis=-1)
            return err / jnp.abs(ref - start).max(), margin

    run = jax.jit(errors, static_argnames=("dense", "mixer"))
    out = []
    for i, ((start, got), mixer) in enumerate(
            zip(states, reference.layer_types(sizes))):
        prefix = f"layers.{i}."
        w_block = {n[len(prefix):]: v for n, v in w.items()
                   if n.startswith(prefix)}
        out.append(jax.device_get(run(
            w_block, start, got, dense=i < sizes["first_k_dense_replace"],
            mixer=mixer)))
    return out


def check_train(built, reference, sizes, shapes, probe_x, rows=1):
    """What the timed program computes on the first probe row at the timed
    size (train mode, recomputation on, the chunked scan; the reference's
    attention runs in query blocks so that its [heads, block, 16384] scores
    fit, its delta rule token by token) against
    ``references/kimi-linear-48b-a3b.py``: in float32 at logit level over
    the whole model, under the cell's amp level block by block, the loss
    both ways, and the held share's overflow count — of this forward and of
    the train steps made so far (the layer's buffers). The programs run one
    after the other and hand their results to the host, so that each one's
    activations are gone before the next one's arrive beside the 9.6 GB of
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
    """(KDA layers, latent-attention layers) the step runs."""
    linear = sizes["linear_attn_config"]
    layers = range(1, sizes["num_hidden_layers"] + 1)
    kda = sum(i in linear["kda_layers"] for i in layers)
    return kda, sizes["num_hidden_layers"] - kda


def block_counts(sizes):
    """(dense blocks, expert blocks) the step runs."""
    dense = min(sizes["first_k_dense_replace"], sizes["num_hidden_layers"])
    return dense, sizes["num_hidden_layers"] - dense


def kda_projection_flops_per_token(sizes):
    """Forward FLOPs a token of one KDA sublayer's matrices: q, k, v and o,
    the two low-rank gates and beta (the convolutions' 4 taps a channel are
    no matmul and are not counted)."""
    h, linear = sizes["hidden_size"], sizes["linear_attn_config"]
    inner = linear["num_heads"] * linear["head_dim"]
    rank = sizes.get("kda_gate_rank") or linear["head_dim"]
    return 2 * (4 * h * inner + 2 * (h * rank + rank * inner)
                + h * linear["num_heads"])


def kda_core_flops_per_token(sizes):
    """Forward FLOPs a token of one KDA layer's chunked scan, all heads
    (the reader's function of heads, widths and the chunk)."""
    linear = sizes["linear_attn_config"]
    reader = cells.load_module("layer_metrics", "kda_core_roofline")
    return reader.kda_core_flops(1, linear["num_heads"], linear["head_dim"],
                                 linear["head_dim"], sizes["kda_chunk"])


def mla_projection_flops_per_token(sizes):
    """Forward FLOPs a token of one latent-attention sublayer's four
    matrices (no q rank)."""
    h, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    return 2 * (h * heads * qk
                + h * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
                + sizes["kv_lora_rank"] * heads
                * (sizes["qk_nope_head_dim"] + sizes["v_head_dim"])
                + heads * sizes["v_head_dim"] * h)


def mla_core_flops(sizes, seq, passes=1.0):
    """Forward FLOPs of one latent-attention core on one sequence: QK^T over
    the (nope + rope)-wide keys and PV over the v-wide values, on the causal
    seq (seq + 1) / 2 pairs; ``passes`` scales."""
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    pairs = seq * (seq + 1) // 2
    return passes * 2 * sizes["num_attention_heads"] * pairs * (
        qk + sizes["v_head_dim"])


def held_expert_flops_per_token(sizes):
    """Forward FLOPs a token of one expert layer's HELD routed experts:
    a token's k choices land here with probability held / all."""
    return (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
            / sizes["router_experts"] * 3 * 2 * sizes["hidden_size"]
            * sizes["moe_intermediate_size"])


def flops_per_sample(sizes, shapes):
    """Floating-point operations one sample (one sequence) needs in a train
    step, from shapes: 2 per multiply-add of the forward pass, times 3 for
    forward + backward; NOTHING recomputed (the blocks' second forward and
    the scan's rebuilt segments are the program's choice, not the model's
    work). Counted: every KDA layer's projections and its chunked scan's
    own products, the latent-attention layer's projections and causal core,
    the dense block's SwiGLU, in every expert block the router over all
    256, the shared expert and ONLY THE HELD experts' rows (tokens x 8 x 8
    / 256), and the head over the held vocabulary slice."""
    seq = shapes["input_ids"][0]
    h = sizes["hidden_size"]
    kda, mla = mixer_counts(sizes)
    dense, expert = block_counts(sizes)
    per_token = (
        kda * (kda_projection_flops_per_token(sizes)
               + kda_core_flops_per_token(sizes))
        + mla * mla_projection_flops_per_token(sizes)
        + dense * 3 * 2 * h * sizes["intermediate_size"]
        + expert * (2 * h * sizes["router_experts"]
                    + sizes["n_shared_experts"] * 3 * 2 * h
                    * sizes["moe_intermediate_size"]
                    + held_expert_flops_per_token(sizes))
        + 2 * h * sizes["vocab_size"])
    return 3.0 * (seq * per_token + mla * mla_core_flops(sizes, seq))
