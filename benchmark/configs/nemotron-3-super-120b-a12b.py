"""nemotron-3-super-120b-a12b: nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
(HF ``nemotron_h``) at its published widths, cut to ONE CHIP'S SHARE of a
deployment in which 64 chips share each layer
(``nemotron-3-super-120b-a12b.json`` holds every size, the cut's arithmetic
and what it distorts): published layers 27-37, ``MEMEMEMEM*E`` — five
Mamba-2 layers, five LatentMoE layers, one attention layer, each ONE
sublayer —, 8 of the 512 routed experts, 16 of the 128 state-space heads
with their one group of 8, 4 of the 32 query heads on 1 of the 2 key/value
heads, an eighth of the vocabulary, and the multi-token-prediction module
(an attention layer and an expert layer more). Built with
``text.models.NemotronHModel``: ``Mamba2Mixer(held_heads=)`` on the Mosaic
convolution and scan kernels, ``NemotronAttention(held_heads=)`` on the
streaming flash kernel at heads of 128, ``incubate.moe.MoELayer`` with
relu² experts in the 1,024-wide latent space on the held sorted path, a
relu² shared expert, per-layer recomputation, and a two-term head loss that
never holds the logits. Each layer computes its own heads' or experts'
part; that partial sum goes on. The train recipe lives here; what differs
from the source is listed under ``departures`` in the JSON."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import cells

# The check is the other LM configurations' — float32 over the whole model
# at logit level, amp O1 layer by layer, both loss terms both ways, the
# held share's overflow — run IN PIECES as granite-4.0-h-micro's, for its
# reason: when the check runs, the driver has made its first step, 10.06 GB
# of train state are resident, and a second whole copy of the parameters
# (3.35 GB) beside three chains' hidden states and two float32 logit arrays
# would not fit. The stem, thirteen layers (the module's two among them),
# the module's stem and the tail go through one at a time: one layer's
# parameters (0.39 GB an expert layer) on the chip, the float32 reference,
# the float32 program and the amp program in step, each handing its own
# hidden states on.
_joyai = cells.load_module("configs", "joyai-llm-flash")
_traced, token_errors, clean_prefix, overflow_total, make_loss = (
    _joyai._traced, _joyai.token_errors, _joyai.clean_prefix,
    _joyai.overflow_total, _joyai.make_loss)

# A token's ROUTER MARGIN here is about the HELD experts alone (the
# reference's ``experts``): how far the nearest of the 8 held experts'
# biased scores is from changing sides of the top-22's boundary. The plain
# margin of the other configurations (the k-th score over the (k + 1)-th)
# would not do at top-22 of 512: the boundary's two scores are 2.5e-3 apart
# on average, so bf16 arithmetic (shifts of a few 1e-3) leaves hardly a
# token decided — while a swap among ABSENT experts moves nothing here but
# the renormalising sum, continuously.
#
# FLOAT32, whole model, at logit level (JoyAI's rule): the main and the MTP
# logits token by token as a share of the largest reference logit, the
# worst token over the row's CLEAN PREFIX of decided tokens (a token that
# took another held expert moves every later token of its row through the
# scan's state and the attention), the median over all tokens, and both
# loss terms.
# Readings (my chip runs, PR 51, TPU v5 lite, 4,096 tokens a seed; 8 seeds):
# 7.3e-6 to 2.93e-5 worst decided token (six of the eight 9.5e-6 to 1.6e-5),
# 9.1e-7 to 1.10e-6 median; 0.05-0.15% of the tokens undecided; on one seed
# a held expert swapped (float32 against float32) and 50.5% of the row was
# its clean prefix, 9.5e-6 there. A float32 layer on its own input reads
# 4.5e-7 to 9e-7 (attention, experts) and 4.2e-6 to 2.0e-5 of its
# contribution in the state-space layers: the chunked scan takes ONE
# exponential of a difference of decay sums that reach a few hundred inside
# a chunk of 256 (dt A up to 1.6 a token), and float32 rounds a sum of 400
# by 2.4e-5 — in the exponent, so in the term — where the reference's
# recurrence multiplies token by token and never forms the sum. That, not a
# lower precision, is why the limit is not the other configurations' 2e-5:
# it stands 6.8 x over the largest reading. The float32 reference itself at
# the TPU's default precision (bf16 passes, the nearest precision below;
# tools/nemotron_check.py): 4.2e-2 at the worst decided token of the 15% of
# the row before its first swap and 6.8e-3 at the median token — it fails
# the bound by 210 x and by 34 x.
F32_RTOL = 2e-4
F32_MARGIN = 1e-5
F32_UNDECIDED_MAX = 0.05
# AMP O1, layer by layer: the reference layer in float32 is fed the
# program's own (amp) input and compared with the program's output at the
# tokens decided IN THAT LAYER by AMP_MARGIN; the error is a share of the
# layer's own largest contribution (output minus input). A layer without a
# router compares every token.
# Readings: worst decided token of a layer 1.35e-2 to 1.63e-2 (the MTP
# module's attention layer the highest), a layer's median 1.5e-3 to 8.2e-3;
# compared 78% to 91% of an expert layer's tokens. A program whose experts
# apply relu for relu² is off by O(1) of the layer's contribution at every
# token (benchmark/tests/test_nemotron_rehearsal.py at the toy's widths).
AMP_RTOL = 4e-2
AMP_MARGIN = 1e-2
AMP_COMPARED_MIN = 0.5
# Both loss terms against the reference's, relative, over ALL tokens: they
# hold the loss's own arithmetic (the shifts by one and by two, the means,
# the chunks, lambda); rounding averages out in a mean, so this bound does
# not tell float32 from bf16 passes (the default-precision reference reads
# 3.8e-6): the logits' does. Readings: float32 0.0 to 5.8e-7, amp 2.5e-7 to
# 2.3e-5; a missing or misweighted term is off by 1e-2 or more.
LOSS_F32_RTOL = 2e-5
LOSS_AMP_RTOL = 3e-3

LIMITS = ("F32_RTOL", "AMP_RTOL", "LOSS_F32_RTOL", "LOSS_AMP_RTOL")

#: JSON key -> ``NemotronHModel`` argument, where the two agree
MODEL_KEYS = ("vocab_size", "hidden_size", "head_dim", "intermediate_size",
              "moe_intermediate_size", "moe_latent_size",
              "moe_shared_expert_intermediate_size", "num_experts_per_tok",
              "norm_topk_prob", "routed_scaling_factor",
              "num_nextn_predict_layers", "mtp_hybrid_override_pattern",
              "layer_norm_epsilon", "use_conv_bias", "conv_kernel",
              "mamba_chunk", "mamba_segment", "bias_update_speed",
              "initializer_range", "held_rows_factor")

#: toy widths for the CPU tests and rehearsal (never a cell): the same
#: eleven layers and module; 4 of 8 state-space heads of 16 on a state of
#: 32 (one group of two), 2 of 8 query heads of 16 on 1 of 2 key/value
#: heads, 8 of 32 experts of 48 in a latent space of 32, top-4
TOY = {"vocab_size": 256, "hidden_size": 64, "head_dim": 16,
       "num_attention_heads": 2, "num_key_value_heads": 1,
       "held_attention_heads": [0, 2], "mamba_num_heads": 4, "n_groups": 1,
       "held_mamba_heads": [0, 4], "mamba_n_heads": 4, "mamba_d_head": 16,
       "mamba_head_dim": 16, "mamba_d_state": 32, "ssm_state_size": 32,
       "mamba_n_groups": 1, "mamba_chunk": 16, "mamba_segment": 32,
       "moe_intermediate_size": 48, "intermediate_size": 48,
       "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
       "n_routed_experts": 8, "router_experts": 32, "held_experts": [8, 8],
       "num_experts_per_tok": 4, "initializer_range": 0.1,
       "held_rows_factor": 2.0, "reference_q_block": 16,
       "published": {"mamba_num_heads": 8, "n_groups": 2,
                     "num_attention_heads": 8, "num_key_value_heads": 2}}

PREFIX = "lm."   # the parameters' names under the train wrapper
#: parameters AdamW does not decay: the decay rate, the step's bias, the
#: skip and every norm's weight
NO_DECAY = ("A_log", "dt_bias", ".D", "norm_weight")


def decays(name):
    return not (name.endswith(NO_DECAY) or name.startswith("rmsnorm_"))


def layer_types(sizes):
    """The kinds of the layers run: ``run_layers`` of the published
    pattern."""
    return list(sizes["layer_types"][:sizes["num_hidden_layers"]])


def mtp_layer_types(sizes):
    from paddle_tpu.text.models import nemotron_layer_types

    return (nemotron_layer_types(sizes["mtp_hybrid_override_pattern"])
            * sizes["num_nextn_predict_layers"])


def model_kwargs(sizes):
    """The constructor's arguments: every layer is told the WHOLE layer's
    counts (``published``: 128 heads in 8 groups, 32 query heads on 2, a
    router of 512) and the range it holds; the JSON's reduced keys state
    what is held, for the readers and the reference."""
    first, stop = sizes["run_layers"]
    whole = sizes["published"]
    kw = {k: sizes[k] for k in MODEL_KEYS if k in sizes}
    kw.update(
        hybrid_override_pattern=sizes["hybrid_override_pattern"][first:stop],
        mamba_num_heads=whole["mamba_num_heads"], n_groups=whole["n_groups"],
        mamba_head_dim=sizes["mamba_d_head"],
        ssm_state_size=sizes["mamba_d_state"],
        num_attention_heads=whole["num_attention_heads"],
        num_key_value_heads=whole["num_key_value_heads"],
        n_routed_experts=sizes["router_experts"],
        held_experts=tuple(sizes["held_experts"]),
        held_mamba_heads=tuple(sizes["held_mamba_heads"]),
        held_attention_heads=tuple(sizes["held_attention_heads"]))
    held = (sizes["held_mamba_heads"][1], sizes["held_attention_heads"][1],
            sizes["held_experts"][1], stop - first)
    stated = (sizes["mamba_n_heads"], sizes["num_attention_heads"],
              sizes["n_routed_experts"], sizes["num_hidden_layers"])
    if held != stated:
        raise ValueError(f"the held ranges {held} are not the counts the "
                         f"file states {stated}")
    return kw


def build_train(seed, sizes, shapes):
    """The model, loss and optimizer of a train cell. The step's one input
    is the [rows, seq] token ids, and its label the same array."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.text.models import NemotronHModel

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
    # builder copies them onto the chip (donation), and 3.35 GB of unused
    # copy beside 13.4 GB of step would leave it no room
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = NemotronHModel(use_recompute=True, **model_kwargs(sizes))
    opt = optimizer.AdamW(
        2.2e-4, beta1=0.9, beta2=0.95, epsilon=1e-8,
        parameters=model.parameters(), weight_decay=0.1,
        apply_decay_param_fun=decays,
        grad_clip=nn.ClipGradByGlobalNorm(1.0))
    return {"layer": CausalLM(model), "loss_fn": make_loss(sizes),
            "optimizer": opt, "amp_level": "O1", "sizes": sizes}


# ------------------------------------------------------------ the check
def _within(fn, precision):
    """``fn`` under ``jax.default_matmul_precision(precision)`` (None: the
    platform's default)."""
    if not precision:
        return fn

    def run(*args, **kw):
        with jax.default_matmul_precision(precision):
            return fn(*args, **kw)
    return run


def reference_pieces(reference, sizes, precision="highest"):
    """The reference as jitted pieces on a piece's own parameter names:
    ``stem(w, ids) -> h``; ``layer(w, h, kind) -> (h, margins, dropped,
    landed)``; ``mtp_stem(w, h, ids) -> x`` (ids already shifted);
    ``tail(w, h, h_mtp, ids) -> (logits, MTP logits, total, main, MTP
    term)``. float32 at ``precision`` (None: the platform's default, the
    must-fail reading)."""
    def f32(fn):
        return jax.jit(_within(
            lambda w, *a, **kw: fn(reference._f32(w), *a, **kw), precision),
            static_argnames=("kind",))

    def tail(w, h, h_mtp, ids):
        logits = reference.head(w, h, sizes, "norm.weight")
        mtp = ([] if h_mtp is None else
               [reference.head(w, h_mtp, sizes, "mtp.0.norm.weight")])
        return (logits, mtp) + reference.losses(logits, mtp, ids, sizes)

    return {
        "stem": f32(lambda w, ids: reference.embed(w, ids)),
        "layer": f32(lambda w, h, kind: reference.layer(
            w, h.astype(jnp.float32), sizes, "", kind)),
        "mtp_stem": f32(lambda w, h, ids: reference.mtp_input(
            w, h.astype(jnp.float32), ids, sizes, "mtp.0.")),
        "tail": f32(tail)}


def framework_pieces(built, amp_level):
    """The program as the same pieces, each traced as the step builder
    traces (train mode, amp as given, a layer through ``lm._block``: under
    recompute), at precision "highest" without amp. One module a kind of
    layer — the first of its kind, given each layer's parameters and
    buffers in turn — so that five state-space layers are one program."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.text.models import mtp_lm_loss

    lm, sizes = built["layer"].lm, built["sizes"]
    modules = {kind: lm.layers[lm.layer_types.index(kind)]
               for kind in set(lm.layer_types)}

    def split(w):
        """A piece's (parameters, buffers)."""
        buffers = {n: v for n, v in w.items()
                   if n.endswith(("held_overflow", "e_score_correction_bias"))}
        return {n: v for n, v in w.items() if n not in buffers}, buffers

    def stem(w, ids):
        return _traced(lm, w, {}, amp_level, lambda: lm.embed_tokens(
            Tensor(ids, stop_gradient=True))._value)[0]

    def layer(w, h, kind):
        module = modules[kind]
        params, buffers = split(w)
        out, _, after = _traced(module, params, buffers, amp_level,
                                lambda: lm._block(module, Tensor(h))._value)
        return out, overflow_total(after) - overflow_total(buffers)

    def mtp_stem(w, h, ids):
        from paddle_tpu import tensor as pt

        def fn():
            module = lm.mtp[0]
            emb = lm.embed_tokens(Tensor(ids, stop_gradient=True))
            return module.eh_proj(pt.concat(
                [module.hnorm(Tensor(h)), module.enorm(emb)], axis=-1))._value

        return _traced(lm, w, {}, amp_level, fn)[0]

    def tail(w, h, h_mtp, ids):
        def fn():
            hidden = lm.norm(Tensor(h))
            mtp_hidden = ([] if h_mtp is None
                          else [lm.mtp[0].norm(Tensor(h_mtp))])
            total, main, mtp = mtp_lm_loss(
                hidden, mtp_hidden, lm.lm_head.weight,
                Tensor(ids, stop_gradient=True), sizes["mtp_loss_weight"])
            return (lm.lm_head(hidden)._value,
                    [lm.lm_head(m)._value for m in mtp_hidden], total._value,
                    main._value, 0.0 if mtp is None else mtp._value)

        return _traced(lm, w, {}, amp_level, fn)[0]

    precision = None if amp_level else "highest"
    return {"stem": jax.jit(_within(stem, precision)),
            "layer": jax.jit(_within(layer, precision),
                             static_argnames=("kind",)),
            "mtp_stem": jax.jit(_within(mtp_stem, precision)),
            "tail": jax.jit(_within(tail, precision))}


def _layer_error(reference, sizes):
    """The layer-by-layer half's one layer: the reference layer in float32
    on the program's own input -> (each token's error as a share of the
    layer's largest contribution, each token's router margin in it)."""
    def errors(w, start, got, kind):
        with jax.default_matmul_precision("highest"):
            start = start.astype(jnp.float32)
            ref, margin, _, _ = reference.layer(reference._f32(w), start,
                                                sizes, "", kind)
            err = jnp.abs(got.astype(jnp.float32) - ref).max(axis=-1)
            return err / jnp.abs(ref - start).max(), margin

    return jax.jit(errors, static_argnames=("kind",))


class Weights:
    """The eager (host) copy of the parameters and buffers, a piece at a
    time on the chip: ``ends()`` the embedding, the head, the final norm
    and the module's small parts; ``layer(prefix)`` one layer's under its
    own names."""

    def __init__(self, layer, device):
        params, buffers = layer.functional_state()
        self.state, self.device = {**params, **buffers}, device

    def _put(self, prefix, strip):
        return jax.device_put(
            {n[len(strip):]: v for n, v in self.state.items()
             if n.startswith(prefix)}, self.device)

    def ends(self):
        out = {}
        for part in ("embed_tokens.", "lm_head.", "norm.", "mtp.0.hnorm.",
                     "mtp.0.enorm.", "mtp.0.eh_proj.", "mtp.0.norm."):
            out.update(self._put(PREFIX + part, PREFIX))
        return out

    def layer(self, prefix):
        return self._put(PREFIX + prefix, PREFIX + prefix)

    def overflow_so_far(self):
        return int(overflow_total(self.state))


def zeroed_overflow(w):
    """A piece's weights with its overflow count from nothing."""
    return {n: jnp.zeros_like(v) if n.endswith("held_overflow") else v
            for n, v in w.items()}


def in_pieces(sides, weights, sizes, x, each_layer=None):
    """Every side's (main logits, MTP logits, total, main term, MTP term,
    pairs dropped) on ids x, the sides in step so that a layer's parameters
    cross to the chip once. ``sides[0]`` is the reference: its layers give
    (h, margins, dropped, landed), a program's (h, dropped).
    ``each_layer(i, kind, w, inputs, outputs)`` sees every layer's hidden
    states. Returns (the sides' outputs, the reference's margins a layer,
    its landed pairs an expert layer)."""
    ends = weights.ends()
    hs = [side["stem"](ends, x) for side in sides]
    dropped = [0] * len(sides)
    margins, landed = [], []

    def run(prefix, kind, hs, i):
        w = zeroed_overflow(weights.layer(prefix))
        outs = [side["layer"](w, h, kind=kind) for side, h in zip(sides, hs)]
        margins.append(outs[0][1])
        if kind == "moe":
            landed.append(int(outs[0][3]))
        for j, out in enumerate(outs):     # a reference side gives four
            dropped[j] += int(out[2] if len(out) == 4 else out[1])
        outs = [out[0] for out in outs]
        if each_layer is not None:
            each_layer(i, kind, w, hs, outs)
        return outs

    kinds = layer_types(sizes)
    for i, kind in enumerate(kinds):
        hs = run(f"layers.{i}.", kind, hs, i)
    mtp_hs = [None] * len(sides)
    if sizes["num_nextn_predict_layers"]:
        shifted = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)
        mtp_hs = [side["mtp_stem"](ends, h, shifted)
                  for side, h in zip(sides, hs)]
        for j, kind in enumerate(mtp_layer_types(sizes)):
            mtp_hs = run(f"mtp.0.block.{j}.", kind, mtp_hs, len(kinds) + j)
    # a side's logits leave the chip before the next side's arrive
    outs = [jax.device_get(side["tail"](ends, h, m, x)) + (d,)
            for side, h, m, d in zip(sides, hs, mtp_hs, dropped)]
    return outs, margins, landed


def check_train(built, reference, sizes, shapes, probe_x, rows=1):
    """What the timed program computes on the first probe row at the timed
    size (train mode, recomputation on, the kernels the step runs) against
    ``references/nemotron-3-super-120b-a12b.py`` (the recurrence token by
    token, attention in query blocks under an explicit mask, dense routing
    over the held experts): in float32 at logit level over the whole model,
    under the cell's amp level layer by layer, both loss terms both ways,
    and the held share's overflow count — of this forward and of the train
    steps made so far (the layers' buffers) — in pieces, beside the train
    state."""
    layer = built["layer"]
    layer.train()
    x = probe_x[:rows]
    weights = Weights(layer, next(iter(x.devices())))
    error_of = _layer_error(reference, sizes)
    blocks, blocks32 = [], []

    def each_layer(i, kind, w, inputs, outputs):
        # the reference layer on the program's own input, both programs
        blocks32.append(float(error_of(w, inputs[1], outputs[1],
                                       kind=kind)[0].max()))
        blocks.append(tuple(np.asarray(v) for v in error_of(
            w, inputs[2], outputs[2], kind=kind)))

    (ref, got32, got_amp), margins, landed = in_pieces(
        [reference_pieces(reference, sizes), framework_pieces(built, None),
         framework_pieces(built, built["amp_level"])],
        weights, sizes, x, each_layer)
    margin = np.min(np.stack([np.asarray(m) for m in margins]), axis=0)
    out = compare(ref, got32, got_amp, blocks, margin)
    out["held_pairs_landed"] = landed
    # where a float32 error comes from, a layer: a reading, not a limit
    out["f32_block_worst"] = blocks32
    out["overflow_train_steps"] = weights.overflow_so_far()
    out["ok"] = out["ok"] and out["overflow_train_steps"] == 0
    return out


def compare(ref, got32, got_amp, blocks, margin):
    """The check's dict from the reference's (main logits, MTP logits,
    total, main term, MTP term, dropped), the framework's in float32 and
    under amp, the amp half's per-layer (errors, margins) and the
    reference's router margins over the whole model."""
    margin = np.asarray(margin, np.float32)
    out = {"ref_loss": float(ref[2]), "ref_main_ce": float(ref[3]),
           "ref_mtp_ce": float(ref[4]), "tokens": int(margin.size),
           "ref_dropped": int(ref[5]),
           "ref_max_abs": float(np.abs(np.asarray(ref[0])).max())}
    ok = bool(np.isfinite(np.asarray(ref[0])).all()
              and out["ref_dropped"] == 0)
    terms = [("loss", 2), ("main_ce", 3)] + (
        [("mtp_ce", 4)] if ref[1] else [])

    def loss_errors(tag, got, rtol):
        errs = {name: abs(float(got[i]) - float(ref[i])) / abs(float(ref[i]))
                for name, i in terms}
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

    # amp: layer by layer
    worst, shares, medians = [], [], []
    for errs, layer_margin in blocks:
        decided = layer_margin >= AMP_MARGIN
        shares.append(float(decided.mean()))
        medians.append(float(np.median(errs)))
        worst.append(float(errs[decided].max()) if decided.any()
                     else float("inf"))
    out.update({"amp_rel_err": max(worst), "amp_rtol": AMP_RTOL,
                "amp_block_worst": worst, "amp_block_medians": medians,
                "amp_compared_share": shares,
                "amp_compared_min": AMP_COMPARED_MIN})
    losses_ok = loss_errors("amp", got_amp, LOSS_AMP_RTOL)
    ok = (ok and max(worst) <= AMP_RTOL and max(medians) <= AMP_RTOL
          and min(shares) >= AMP_COMPARED_MIN and losses_ok)
    out["ok"] = bool(ok)
    return out


# ------------------------------------------------------------ FLOPs
def kind_counts(sizes):
    """{kind: layers the step runs}, the MTP module's among them."""
    kinds = layer_types(sizes) + mtp_layer_types(sizes)
    return {kind: kinds.count(kind) for kind in ("mamba", "attention", "moe")}


def mamba_projection_flops_per_token(sizes):
    """Forward FLOPs a token of one HELD state-space share's two matrices
    (hidden -> [z | xBC | dt] and inner -> hidden)."""
    h = sizes["hidden_size"]
    inner = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    state = sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return 2 * (h * (2 * inner + 2 * state + sizes["mamba_n_heads"])
                + inner * h)


def ssd_core_flops_per_token(sizes):
    """Forward FLOPs a token of one state-space layer's chunked scan, the
    held heads (the reader's function)."""
    reader = cells.load_module("layer_metrics", "ssd_core_roofline")
    return reader.ssd_core_flops(
        1, sizes["mamba_n_heads"], sizes["mamba_d_head"],
        sizes["mamba_d_state"], sizes["mamba_n_groups"], sizes["mamba_chunk"])


def attention_flops_per_token(sizes, seq):
    """Forward FLOPs a token of one attention layer's HELD heads: four
    matrices and the core over the causal (seq + 1) / 2 keys a query."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return (2 * (2 * h * heads * d + 2 * h * kv * d)
            + heads * 2 * 2 * d * (seq + 1) / 2)


def latent_moe_flops_per_token(sizes):
    """Forward FLOPs a token of one LatentMoE layer: (the router over all
    512 and the two latent projections, the shared expert's two matrices,
    ONLY THE HELD experts' two matrices on the pairs that land here: tokens
    x 22 x 8 / 512)."""
    h, latent = sizes["hidden_size"], sizes["moe_latent_size"]
    held = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
            / sizes["router_experts"])
    return (2 * h * sizes["router_experts"] + 2 * 2 * h * latent,
            2 * 2 * h * sizes["moe_shared_expert_intermediate_size"],
            held * 2 * 2 * latent * sizes["moe_intermediate_size"])


def flops_per_sample(sizes, shapes):
    """Floating-point operations one sample (one sequence) needs in a train
    step, from shapes: 2 per multiply-add of every matmul of the forward
    pass, times 3 for forward + backward; NOTHING recomputed, and only what
    is HELD here (16 heads, 4 query heads, 8 experts' rows, 16,384 rows of
    the vocabulary). Counted: every state-space share's two matrices and
    its scan's own products, the attention shares' four matrices and cores,
    every expert layer's router, latent projections, shared expert and held
    experts, the MTP module's 8,192 -> 4,096 projection, and the head at
    every position once a head (main and MTP)."""
    seq = shapes["input_ids"][0]
    h = sizes["hidden_size"]
    n, mtp = kind_counts(sizes), sizes["num_nextn_predict_layers"]
    per_token = (
        n["mamba"] * (mamba_projection_flops_per_token(sizes)
                      + ssd_core_flops_per_token(sizes))
        + n["attention"] * attention_flops_per_token(sizes, seq)
        + n["moe"] * sum(latent_moe_flops_per_token(sizes))
        + mtp * 2 * 2 * h * h
        + (1 + mtp) * 2 * h * sizes["vocab_size"])
    return 3.0 * seq * per_token
