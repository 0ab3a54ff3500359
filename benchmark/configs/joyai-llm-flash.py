"""joyai-llm-flash: jdopensource/JoyAI-LLM-Flash (48B-A2.7B) at its published
widths, cut to ONE CHIP'S SHARE of a 16-chip expert-parallel deployment
(``joyai-llm-flash.json`` holds every size, the cut's arithmetic and what
it distorts): the dense layer, four expert layers and the multi-token-
prediction module, 16 of the 256 routed experts held in each expert layer,
an eighth of the vocabulary. Built with ``text.models.JoyAIFlashModel``:
latent attention (192-wide keys, 128-wide values) on the streaming flash
kernel, ``incubate.moe.MoELayer``'s sigmoid bias-balanced router over all
256 experts with the held range on the dropless sorted path, a shared
expert, per-block recomputation, and a two-term head loss that never holds
the logits. The train recipe lives here; what differs from the source is
listed under ``departures`` in the JSON."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

# The check has two halves, because a swapped expert does not stay where it
# happened: with five expert layers and attention between them, one token
# that takes another expert in layer 1 moves every later token of the row a
# little in every later layer.
#
# FLOAT32, whole model, at logit level. The framework's float32 forward
# (precision "highest") against the float32 reference: the main logits and
# the MTP logits token by token as a share of the largest reference logit,
# at the tokens whose router is DECIDED in every expert layer (the 8th and
# 9th biased scores further apart than F32_MARGIN; float32 scores differ by
# ~1e-7 between two summation orders; 0.6-0.9% of the tokens are under it),
# and both loss terms over ALL tokens. Readings (my chip runs, PR 29:
# tools/joyai_check.py and the cell's own runs, TPU v5 lite, 8,192 tokens a
# seed; 16 seeds): 1.3e-6 to 1.6e-6 worst token, 8.5e-7 to 9.2e-7 median, no token
# swapped. The float32 reference itself at the TPU's default precision
# (bf16 passes, the nearest precision below) is off by 0.11-0.14 at the
# worst decided token (its router scores shift by up to 5e-3: experts swap)
# and by 5.9e-3 to 6.0e-3 at the median token: it fails the bound on the
# worst token AND on the median by more than two orders.
#
# An undecided token that DOES take another expert (seed 1029817422, the
# driver's check of PR 29: one token, off by 6.2e-2) does not stay alone
# either: attention is causal, so every later token of its row reads it
# (with ~1 / position of its weight at initialisation), moves by a little
# (2.2e-5 at the worst decided one there: over the bound, with nothing
# wrong), and may swap in turn. Nothing before it can: a token's logits,
# main and MTP, depend on the tokens at or before it alone. So the worst
# token is taken over the row's CLEAN PREFIX — the decided tokens before
# the row's first undecided token that is over the bound
# (``f32_compared_share``; 1 - the undecided share on a seed without a
# swap) — and the median over ALL tokens, which no swap moves (9.2e-7 on
# that seed) and bf16 arithmetic fails by 300 times. A decided token over
# the bound before any swap still fails the check, wherever it is. Read on
# that seed (my chip run, PR 29): 73.6% of the row compared, worst token
# 1.53e-6, both loss terms within 1.1e-6; the six seeds after it, no swap:
# 99.2-99.5% compared, 1.27e-6 to 1.46e-6. The default-precision reference
# by the same rule (two seeds): 5.5e-2 and 8.3e-2 at the worst token of the
# 0.6% and 1.5% of the row before its first swap, 5.7e-3 and 5.8e-3 median.
F32_RTOL = 2e-5
F32_MARGIN = 1e-5
F32_UNDECIDED_MAX = 0.05
# AMP O1, block by block. bf16 operands move a router score by up to a few
# 1e-3 (read on the default-precision reference: 4.5e-3, 5.0e-3), which is
# more than most tokens' margin at initialisation (52% of the tokens are
# under 1e-3, 89-90% under 3e-3), so "leave out the undecided" over the
# whole model would leave out nearly all. Instead every decoder block and
# the MTP module is compared ALONE: the reference block in float32 is fed
# the program's own (amp) block input, and its output compared with the
# program's at the tokens decided IN THAT BLOCK by AMP_MARGIN (twice the
# largest shift read over the whole model, so a block's own bf16 attention
# cannot swap a compared token); the error is a share of the block's own largest contribution
# (output minus input), so the residual stream does not hide it. The dense
# block has no router: all its tokens count. Readings: worst decided token
# 1.64e-2 to 1.73e-2, a block's median over ALL tokens 1.9e-3 to 4.8e-3
# (the MTP module, whose input is a 4096 -> 2048 projection, 8.6e-3 to
# 9.3e-3); compared 20.3% to 22.9% of the tokens of each expert block
# (``amp_compared_share``), all of the dense one. A wrong block is off by
# O(1) of its contribution at every token.
AMP_RTOL = 4e-2
AMP_MARGIN = 1e-2
AMP_COMPARED_MIN = 0.05
# Both loss terms against the reference's, relative, over ALL tokens, in
# float32 and under amp: they hold the loss's own arithmetic (the shifts by
# one and by two, the means, the chunks, lambda) — a missing or misweighted
# term is off by 1e-2 or more. Readings: float32 0.0 to 9e-8, amp 2.6e-6 to
# 1.7e-5 (over 64 tokens at the CPU rehearsal's toy width: 1.4e-3). Rounding averages out in a mean, so this bound does not tell
# float32 from bf16 passes (the default-precision reference reads 7.7e-6 to
# 2.3e-5): the logits' bound above does that.
LOSS_F32_RTOL = 2e-5
LOSS_AMP_RTOL = 3e-3

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "intermediate_size",
              "moe_intermediate_size", "num_experts_per_tok",
              "n_shared_experts", "first_k_dense_replace", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "rms_norm_eps", "rope_theta", "norm_topk_prob",
              "routed_scaling_factor", "num_nextn_predict_layers",
              "bias_update_speed", "balance_loss_weight",
              "initializer_range", "held_rows_factor")

#: toy widths for the CPU tests and rehearsal (never a cell): the same
#: layer pattern, a share of 8 of 32 experts; the wider initialiser keeps
#: the router logits' spread (range x sqrt(hidden)) near the published 0.9
TOY = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
       "num_attention_heads": 4, "intermediate_size": 96,
       "moe_intermediate_size": 32, "n_routed_experts": 8,
       "router_experts": 32, "held_experts": [8, 8],
       "num_experts_per_tok": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "qk_head_dim": 24, "max_position_embeddings": 64,
       "initializer_range": 0.1, "held_rows_factor": 2.0}

PREFIX = "lm."   # the parameters' names under the train wrapper


def model_kwargs(sizes):
    """The constructor's arguments: the router is as wide as published
    (``router_experts``), ``n_routed_experts`` of them are held here."""
    kw = {k: sizes[k] for k in MODEL_KEYS if k in sizes}
    kw["n_routed_experts"] = sizes["router_experts"]
    kw["held_experts"] = tuple(sizes["held_experts"])
    return kw


def make_loss(sizes):
    def lm_loss(out, input_ids):
        """Both cross-entropies from (final hidden states, the MTP
        module's, the head's weight): lambda from the configuration."""
        from paddle_tpu.text.models import mtp_lm_loss

        hidden, mtp_hidden, head = out
        return mtp_lm_loss(hidden, list(mtp_hidden), head, input_ids,
                           sizes["mtp_loss_weight"])[0]._value

    return lm_loss


def build_train(seed, sizes, shapes):
    """The model, loss and optimizer of a train cell. The step's one input
    is the [rows, seq] token ids, and its label the same array."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.text.models import JoyAIFlashModel

    class CausalLM(nn.Layer):
        """What the loss needs instead of logits: the final hidden states
        of the main model and of the MTP module, and the head's weight."""

        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, input_ids):
            hidden, mtp_hidden = self.lm.training_features(input_ids)
            return hidden, tuple(mtp_hidden), self.lm.lm_head.weight

    paddle.seed(seed)
    # the eager copy of the parameters stays in host memory: the step
    # builder copies them onto the chip (donation), and 2.7 GB of unused
    # copy beside 10.9 GB of train state would not leave the step room
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = JoyAIFlashModel(use_recompute=True, **model_kwargs(sizes))
    opt = optimizer.AdamW(2.2e-4, beta1=0.9, beta2=0.95, epsilon=1e-8,
                          parameters=model.parameters(), weight_decay=0.1,
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    return {"layer": CausalLM(model), "loss_fn": make_loss(sizes),
            "optimizer": opt, "amp_level": "O1", "sizes": sizes}


# ------------------------------------------------------------ the check
def _traced(layer, params, buffers, amp_level, fn):
    """``fn()`` inside the trace the step builder makes: the functional
    state loaded, amp as the cell sets it, the auxiliary losses collected;
    returns (fn's result, the aux total, the buffers as the forward left
    them)."""
    from paddle_tpu.amp.auto_cast import auto_cast
    from paddle_tpu.core import dispatch
    from paddle_tpu.nn.aux_loss import collect_aux_losses, total_aux_loss

    saved = layer.functional_state()
    try:
        with contextlib.ExitStack() as stack:
            stack.enter_context(dispatch.trace_mode())
            if amp_level:
                stack.enter_context(auto_cast(enable=True, level=amp_level,
                                              dtype="bfloat16"))
            layer.load_functional_state(params, buffers)
            with collect_aux_losses() as auxes:
                out = fn()
            return out, total_aux_loss(auxes), layer.functional_state()[1]
    finally:
        layer.load_functional_state(*saved)


def overflow_total(buffers):
    """Held pairs that did not fit their layer's row buffer, over all
    expert layers (the ``held_overflow`` buffers)."""
    return sum(jnp.asarray(v, jnp.int32) for n, v in buffers.items()
               if n.endswith("held_overflow"))


def _framework(built, params, buffers, amp_level, x):
    """The framework's forward as the step runs it (train mode, recomputed
    blocks, the wrapper's forward, the cell's loss), tapped a block: (main
    logits, MTP logits, total loss, main term, MTP term, pairs dropped,
    [(block input(s), block output) a block])."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.text.models import mtp_lm_loss

    layer, sizes = built["layer"], built["sizes"]
    lm = layer.lm

    def fn():
        ids = Tensor(x, stop_gradient=True)
        with lm.tapped() as taps:
            hidden, mtp_hidden, head = layer.forward(ids)
        total, main, mtp = mtp_lm_loss(hidden, list(mtp_hidden), head, ids,
                                       sizes["mtp_loss_weight"])
        states = [([t._value for t in inputs],
                   (out[0] if isinstance(out, tuple) else out)._value)
                  for _, inputs, out in taps]
        return (lm.lm_head(hidden)._value,
                [lm.lm_head(h)._value for h in mtp_hidden],
                total._value, main._value, mtp._value, states)

    (logits, mtp_logits, total, main, mtp, states), aux, new_buffers = (
        _traced(layer, params, buffers, amp_level, fn))
    dropped = overflow_total(new_buffers) - overflow_total(buffers)
    return logits, mtp_logits, total + aux, main, mtp, dropped, states


def _strip(tree):
    return {k[len(PREFIX):]: v for k, v in tree.items()}


def reference_outputs(reference, sizes, precision="highest"):
    """(params, buffers, ids) -> the reference's (main logits, MTP logits,
    total loss, main term, MTP term, pairs dropped, router margins, pairs
    landed a block) on the wrapper's parameter names."""
    def fn(params, buffers, ids):
        w = {**_strip(params), **_strip(buffers)}
        logits, mtp_logits, total, main, mtp, _, dropped, margin, landed = (
            reference.outputs(w, ids, sizes, precision=precision))
        return logits, mtp_logits, total, main, mtp, dropped, margin, landed

    return fn


def _block_errors(reference, sizes, params, buffers, x, states):
    """The amp half: for every tapped block, the reference block in float32
    on the program's own input -> (each token's error as a share of the
    block's largest contribution, each token's router margin in it)."""
    w = reference._f32({**_strip(params), **_strip(buffers)})
    n_layers = sizes["num_hidden_layers"]
    out, ids = [], x
    with jax.default_matmul_precision("highest"):
        for i, (inputs, got) in enumerate(states):
            h = inputs[0].astype(jnp.float32)
            if i < n_layers:
                start = h
                ref, _, margin, _, _ = reference.block(
                    w, h, sizes, f"layers.{i}.",
                    i < sizes["first_k_dense_replace"])
            else:
                p = f"mtp.{i - n_layers}."
                ids = reference.shift_left(ids)
                start = reference.mtp_input(w, h, ids, sizes, p)
                ref, _, margin, _, _ = reference.block(w, start, sizes,
                                                       p + "block.", False)
            err = jnp.abs(got.astype(jnp.float32) - ref).max(axis=-1)
            out.append((err / jnp.abs(ref - start).max(), margin))
    return out


def check_train(built, reference, sizes, shapes, probe_x, rows=1):
    """What the timed program computes on the first probe row at the timed
    size (train mode, recomputation on; the reference's attention runs in
    query blocks so that its [heads, block, 8192] scores fit) against
    ``references/joyai-llm-flash.py``: in float32 at logit level over the
    whole model, under the cell's amp level block by block, both loss terms
    both ways, and the held share's overflow count — of this forward and of
    the train steps made so far (the layer's buffers)."""
    layer = built["layer"]
    layer.train()
    x = probe_x[:rows]
    device = next(iter(x.devices()))
    params, buffers = jax.device_put(layer.functional_state(), device)
    steps_overflow = int(overflow_total(buffers))
    # this forward's own count starts from nothing
    buffers = {n: jnp.zeros_like(v) if n.endswith("held_overflow") else v
               for n, v in buffers.items()}
    ref = jax.jit(reference_outputs(reference, sizes))(params, buffers, x)
    with jax.default_matmul_precision("highest"):
        got32 = jax.jit(lambda p, b, a: _framework(built, p, b, None, a)[:6])(
            params, buffers, x)

    def amp(p, b, a):
        got = _framework(built, p, b, built["amp_level"], a)
        return got[:6], _block_errors(reference, sizes, p, b, a, got[6])

    got_amp, blocks = jax.jit(amp)(params, buffers, x)
    out = compare(ref, got32, got_amp, blocks)
    out["overflow_train_steps"] = steps_overflow
    out["ok"] = out["ok"] and steps_overflow == 0
    return out


def token_errors(ref_logits, got):
    """Each token's largest logit error over the largest reference logit
    ([n, s]; inf where the shapes differ or a value is not finite)."""
    ref_logits = np.asarray(ref_logits, np.float32)
    got = np.asarray(got).astype(np.float32)
    if got.shape != ref_logits.shape or not np.isfinite(got).all():
        return np.full(ref_logits.shape[:-1], np.inf)
    return np.abs(got - ref_logits).max(axis=-1) / np.abs(ref_logits).max()


def clean_prefix(errs, decided, rtol):
    """[n, s] bool: the tokens before their row's first UNDECIDED token
    whose error is over ``rtol`` — a token that took another expert, which
    every later token of the row reads through causal attention."""
    swapped = (np.asarray(errs) > rtol) & ~np.asarray(decided)
    return np.cumsum(swapped, axis=-1) == 0


def compare(ref, got32, got_amp, blocks):
    """The check's dict from the reference's outputs, the framework's in
    float32 and under amp ((main logits, MTP logits, total, main term, MTP
    term, dropped) each) and the amp half's per-block (errors, margins)."""
    margin = np.asarray(ref[6], np.float32)
    out = {"ref_loss": float(ref[2]), "ref_main_ce": float(ref[3]),
           "ref_mtp_ce": float(ref[4]), "tokens": int(margin.size),
           "ref_dropped": int(ref[5]),
           "held_pairs_landed": [int(v) for v in ref[7]],
           "ref_max_abs": float(np.abs(np.asarray(ref[0])).max())}
    ok = bool(np.isfinite(np.asarray(ref[0])).all() and out["ref_dropped"] == 0)

    def loss_errors(tag, got, rtol):
        errs = {name: abs(float(got[i]) - float(ref[i])) / abs(float(ref[i]))
                for name, i in (("loss", 2), ("main_ce", 3), ("mtp_ce", 4))}
        out.update({f"{name}_{tag}_rel_err": e for name, e in errs.items()})
        out[f"loss_{tag}_rtol"] = rtol
        out[f"{tag}_dropped"] = int(got[5])
        return max(errs.values()) <= rtol and int(got[5]) == 0

    # float32: logits of both heads, worst decided token of the clean prefix
    decided = margin >= F32_MARGIN
    errs = np.maximum.reduce(
        [token_errors(ref[0], got32[0])]
        + [token_errors(r, g) for r, g in zip(ref[1], got32[1])])
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
def _held_share(sizes):
    return sizes["n_routed_experts"] / sizes["router_experts"]


def block_counts(sizes):
    """(dense blocks, expert blocks) the step runs, the MTP module's block
    among the expert ones."""
    dense = min(sizes["first_k_dense_replace"], sizes["num_hidden_layers"])
    return dense, (sizes["num_hidden_layers"] - dense
                   + sizes["num_nextn_predict_layers"])


def mla_projection_flops_per_token(sizes):
    """Forward FLOPs a token of one MLA sublayer's seven matrices."""
    h, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    return 2 * (h * sizes["q_lora_rank"]
                + sizes["q_lora_rank"] * heads * qk
                + h * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
                + sizes["kv_lora_rank"] * heads
                * (sizes["qk_nope_head_dim"] + sizes["v_head_dim"])
                + heads * sizes["v_head_dim"] * h)


def mla_core_flops(sizes, seq, passes=1.0):
    """Forward FLOPs of one MLA core on one sequence: QK^T over the
    (nope + rope)-wide keys and PV over the v-wide values, on the causal
    seq (seq + 1) / 2 pairs; ``passes`` scales (3 for forward + backward)."""
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    pairs = seq * (seq + 1) // 2
    return passes * 2 * sizes["num_attention_heads"] * pairs * (
        qk + sizes["v_head_dim"])


def held_expert_flops_per_token(sizes):
    """Forward FLOPs a token of one expert layer's HELD routed experts:
    a token's k choices land here with probability held / all."""
    return (sizes["num_experts_per_tok"] * _held_share(sizes) * 3 * 2
            * sizes["hidden_size"] * sizes["moe_intermediate_size"])


def flops_per_sample(sizes, shapes):
    """Floating-point operations one sample (one sequence) needs in a train
    step, from shapes: 2 per multiply-add of every matmul of the forward
    pass, times 3 for forward + backward; NOTHING recomputed (the blocks'
    second forward is the program's choice, not the model's work). Counted:
    MLA's seven projections and its causal core in every block, the dense
    block's SwiGLU, in every expert block the router over all 256, the
    shared expert and ONLY THE HELD experts' rows (tokens x 8 x 16 / 256),
    the MTP module's 4096 -> 2048 projection, and the head at every
    position twice (main and MTP) over the held vocabulary slice."""
    seq = shapes["input_ids"][0]
    h = sizes["hidden_size"]
    dense, expert = block_counts(sizes)
    per_token = (
        (dense + expert) * mla_projection_flops_per_token(sizes)
        + dense * 3 * 2 * h * sizes["intermediate_size"]
        + expert * (2 * h * sizes["router_experts"]
                    + sizes["n_shared_experts"] * 3 * 2 * h
                    * sizes["moe_intermediate_size"]
                    + held_expert_flops_per_token(sizes))
        + sizes["num_nextn_predict_layers"] * 2 * 2 * h * h
        + (1 + sizes["num_nextn_predict_layers"]) * 2 * h
        * sizes["vocab_size"])
    return 3.0 * (seq * per_token
                  + (dense + expert) * mla_core_flops(sizes, seq))
