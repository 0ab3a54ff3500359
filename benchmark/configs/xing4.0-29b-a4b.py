"""xing4.0-29b-a4b: XingChen-AGI/Xing4.0-29B-A4B (HF ``xing4_0``) at its
published widths, cut to ONE CHIP'S SHARE of a deployment in which 8 chips
share each layer (``xing4.0-29b-a4b.json`` holds every size, the cut's
arithmetic and what it distorts): one dense block, four expert blocks and the
multi-token-prediction module, 8 of the 64 routed experts, 16 of the 32
latent-attention heads, an eighth of the vocabulary. Built with
``text.models.Xing4Model``: every block carries ``hc_mult`` 4 residual
streams through manifold-constrained hyper-connections
(``HyperConnection``, ``ops/hyper_connections.py``: three maps a sublayer,
the residual one projected onto the doubly stochastic matrices by 20
Sinkhorn-Knopp rounds), ``MLAttention(held_heads=, rope_scaling=)`` on the
streaming flash kernel with YaRN's frequencies and softmax scale,
``incubate.moe.MoELayer``'s sigmoid bias-balanced router over all 64 with
the held range on the dropless sorted path, a shared expert, per-block
recomputation, and a two-term head loss that never holds the logits. Each
sublayer computes its own heads' or experts' part; that partial sum goes
on. The train recipe lives here; what differs from the source is listed
under ``departures`` in the JSON."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import cells

# The check is JoyAI's — float32 over the whole model at logit level on the
# tokens whose router is decided, amp O1 block by block against the
# reference fed the program's own block input, both loss terms both ways,
# the held share's overflow — run IN PIECES as nemotron-3-super's, for its
# reason: when the check runs, the driver has made its first step, 10.1 GB
# of train state are resident, and a second whole copy of the parameters
# (3.4 GB) beside three chains' streams (235 MB each) and two float32 logit
# arrays would not fit. The stem, five blocks, the module's stem, its block
# and the tail go through one at a time: one block's parameters (0.47 GB)
# on the chip, the float32 reference, the float32 program and the amp
# program in step, each handing its own streams on.
_joyai = cells.load_module("configs", "joyai-llm-flash")
_nemotron = cells.load_module("configs", "nemotron-3-super-120b-a12b")
_traced, token_errors, clean_prefix, overflow_total, make_loss = (
    _joyai._traced, _joyai.token_errors, _joyai.clean_prefix,
    _joyai.overflow_total, _joyai.make_loss)
Weights, zeroed_overflow, _within = (
    _nemotron.Weights, _nemotron.zeroed_overflow, _nemotron._within)

# FLOAT32, whole model, at logit level (JoyAI's rule): the main and the MTP
# logits token by token as a share of the largest reference logit, the
# worst token over the row's CLEAN PREFIX of decided tokens (the k-th and
# (k + 1)-th biased router scores further apart than F32_MARGIN in every
# expert block), the median over all tokens, and both loss terms.
# Readings (my chip runs, PR 53, TPU v5 lite, 4,096 tokens a seed; 8 seeds
# of tools/xing4_check.py): 1.84e-6 to 2.17e-6 worst decided token, 9.6e-7
# to 1.07e-6 median, 0.15-0.37% of the tokens undecided, no expert swapped;
# a float32 block on its own input 3.7e-7 to 1.9e-6 of its contribution (the
# twenty Sinkhorn rounds are element-wise float32 on both sides: they add
# nothing). The reference built otherwise (seed 2147483501), by the same
# rule: at the TPU's DEFAULT precision (bf16 passes, the nearest precision
# below) 9.87e-3 at the worst decided token of the 0.3% of the row before
# its first swap and 7.28e-3 at the median token — fails both by 360 x;
# with 19 Sinkhorn rounds 1.19e-4 at the worst decided token (the tokens
# whose H~_res converges slowest; the median, 2.6e-6, does not see a round)
# — fails by 6 x; with H_res left unprojected 1.34 — fails by five orders.
F32_RTOL = 2e-5
F32_MARGIN = 1e-5
F32_UNDECIDED_MAX = 0.05
# AMP O1, block by block: the reference block in float32 is fed the
# program's own (amp) streams and compared with the program's output
# streams at the tokens decided IN THAT BLOCK by AMP_MARGIN; the error is a
# share of the block's own largest contribution (streams out minus streams
# in, all n x C features), so the residual path does not hide it. The dense
# block has no router: all its tokens count.
# Readings: worst decided token of a block 9.4e-3 to 1.09e-2 (the dense
# block the highest), a block's median 1.9e-3 to 4.9e-3; compared 56.5% to
# 58.5% of an expert block's tokens. An unprojected H_res is off by 0.54 to
# 1.0 of a block's contribution; a round fewer is NOT seen here (the
# float32 half's worst token sees it).
AMP_RTOL = 4e-2
AMP_MARGIN = 1e-2
AMP_COMPARED_MIN = 0.05
# Both loss terms against the reference's, relative, over ALL tokens: they
# hold the loss's own arithmetic (the shifts by one and by two, the means,
# the chunks, lambda, the balance terms' sum); rounding averages out in a
# mean, so this bound does not tell float32 from bf16 passes: the logits'
# does. Readings: float32 0.0 to 1.8e-7, amp 4.4e-6 to 1.4e-4 (over 32
# tokens at the CPU rehearsal's toy width: 2.1e-3); a missing or
# misweighted term is off by 1e-2 or more.
LOSS_F32_RTOL = 2e-5
LOSS_AMP_RTOL = 3e-3

LIMITS = ("F32_RTOL", "AMP_RTOL", "LOSS_F32_RTOL", "LOSS_AMP_RTOL")

#: JSON key -> ``Xing4Model`` argument, where the two agree
MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "n_shared_experts",
              "first_k_dense_replace", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "rms_norm_eps", "rope_theta", "rope_scaling", "norm_topk_prob",
              "routed_scaling_factor", "num_nextn_predict_layers", "hc_mult",
              "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
              "mhc_h_res_clamp_max", "bias_update_speed",
              "balance_loss_weight", "initializer_range", "held_rows_factor")

#: toy widths for the CPU tests and rehearsal (never a cell): the same
#: layer pattern and residual path, 2 of 4 heads, 8 of 32 experts; YaRN over
#: 16 original positions so that a row of 32 crosses the ramp
TOY = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 3,
       "num_attention_heads": 2, "num_key_value_heads": 2,
       "attention_heads": 4, "held_attention_heads": [0, 2],
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "n_routed_experts": 8, "router_experts": 32, "held_experts": [8, 8],
       "num_experts_per_tok": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "rope_scaling": {"type": "yarn", "factor": 8, "beta_fast": 4,
                        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 16},
       "max_position_embeddings": 128, "initializer_range": 0.1,
       "held_rows_factor": 2.0, "reference_q_block": 16,
       "published": {"num_attention_heads": 4, "num_key_value_heads": 4}}

PREFIX = "lm."   # the parameters' names under the train wrapper
#: parameters AdamW does not decay: every norm's weight and the
#: hyper-connections' gates (alpha) and biases (b)
NO_DECAY = (".alpha", ".b")


def decays(name):
    return not (name.startswith("rmsnorm_")
                or (name.startswith("mhc_") and name.endswith(NO_DECAY)))


def model_kwargs(sizes):
    """The constructor's arguments: the router and the attention are told
    the WHOLE layer's counts (``router_experts`` 64, ``attention_heads`` 32)
    and the ranges held; the JSON's reduced keys state what is held, for the
    readers and the reference."""
    kw = {k: sizes[k] for k in MODEL_KEYS if k in sizes}
    kw.update(n_routed_experts=sizes["router_experts"],
              held_experts=tuple(sizes["held_experts"]),
              num_attention_heads=sizes["attention_heads"],
              held_attention_heads=tuple(sizes["held_attention_heads"]))
    held = (sizes["held_experts"][1], sizes["held_attention_heads"][1])
    stated = (sizes["n_routed_experts"], sizes["num_attention_heads"])
    if held != stated:
        raise ValueError(f"the held ranges {held} are not the counts the "
                         f"file states {stated}")
    return kw


def build_train(seed, sizes, shapes):
    """The model, loss and optimizer of a train cell. The step's one input
    is the [rows, seq] token ids, and its label the same array."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.text.models import Xing4Model

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
    # builder copies them onto the chip (donation), and 3.4 GB of unused
    # copy beside 13.5 GB of step would leave it no room
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = Xing4Model(use_recompute=True, **model_kwargs(sizes))
    opt = optimizer.AdamW(
        2.2e-4, beta1=0.9, beta2=0.95, epsilon=1e-8,
        parameters=model.parameters(), weight_decay=0.1,
        apply_decay_param_fun=decays,
        grad_clip=nn.ClipGradByGlobalNorm(1.0))
    return {"layer": CausalLM(model), "loss_fn": make_loss(sizes),
            "optimizer": opt, "amp_level": "O1", "sizes": sizes}


# ------------------------------------------------------------ the check
def block_kinds(sizes):
    """[(parameter prefix, kind)] of the blocks the step runs, in order:
    ``dense`` | ``expert`` in the trunk, then the module's ``mtp`` block."""
    dense = min(sizes["first_k_dense_replace"], sizes["num_hidden_layers"])
    trunk = [(f"layers.{i}.", "dense" if i < dense else "expert")
             for i in range(sizes["num_hidden_layers"])]
    return trunk, [(f"mtp.{j}.block.", "mtp")
                   for j in range(sizes["num_nextn_predict_layers"])]


def reference_pieces(reference, sizes, precision="highest"):
    """The reference as jitted pieces on a piece's own parameter names:
    ``stem(w, ids) -> streams``; ``layer(w, x, kind) -> (x, margins,
    dropped, landed, balance term)`` on the streams (``mtp``: on the
    module's one hidden state); ``reduce(x) -> h``; ``mtp_stem(w, h, ids) ->
    x`` (ids already shifted); ``tail(w, h, h_mtp, ids) -> (logits, MTP
    logits, total, main, MTP term)`` without the balance terms. float32 at
    ``precision`` (None: the platform's default, the must-fail reading).
    The reference's layout of the streams is [n, s, hc_mult, C]; the pieces
    take and give the program's [n, s, hc_mult x C]."""
    mult = sizes["hc_mult"]

    def f32(fn):
        return jax.jit(_within(
            lambda w, *a, **kw: fn(reference._f32(w), *a, **kw), precision),
            static_argnames=("kind",))

    def split(x):
        return x.astype(jnp.float32).reshape(*x.shape[:-1], mult, -1)

    def join(x):
        return x.reshape(*x.shape[:-2], -1)

    def layer(w, x, kind):
        if kind == "mtp":
            out = reference.mtp_block(w, x.astype(jnp.float32), sizes, "")
            return out[:1] + out[2:] + out[1:2]
        x, balance, margin, dropped, landed = reference.block(
            w, split(x), sizes, "", kind == "dense")
        return join(x), margin, dropped, landed, balance

    def tail(w, h, h_mtp, ids):
        logits = reference.head(w, h, sizes, "norm.weight")
        mtp = ([] if h_mtp is None else
               [reference.head(w, h_mtp, sizes, "mtp.0.norm.weight")])
        return (logits, mtp) + reference.losses(logits, mtp, ids, sizes)

    return {
        "stem": f32(lambda w, ids: join(reference.expand(
            reference.embed(w, ids), sizes))),
        "layer": f32(layer),
        "reduce": jax.jit(lambda x: reference.reduce(split(x))),
        "mtp_stem": f32(lambda w, h, ids: reference.mtp_input(
            w, h.astype(jnp.float32), ids, sizes, "mtp.0.")),
        "tail": f32(tail)}


def framework_pieces(built, amp_level):
    """The program as the same pieces, each traced as the step builder
    traces (train mode, amp as given, a block through ``lm._block``: under
    recompute), at precision "highest" without amp. One module a kind of
    block — the first of its kind, given each block's parameters and
    buffers in turn. A block gives (streams, pairs dropped, its weighted
    balance term)."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.text.models import mtp_lm_loss

    lm, sizes = built["layer"].lm, built["sizes"]
    dense = min(sizes["first_k_dense_replace"], len(lm.layers))
    modules = {"dense": lm.layers[0], "expert": lm.layers[dense],
               "mtp": lm.mtp[0].block if len(lm.mtp) else None}

    def split(w):
        """A piece's (parameters, buffers)."""
        buffers = {n: v for n, v in w.items()
                   if n.endswith(("held_overflow", "e_score_correction_bias"))}
        return {n: v for n, v in w.items() if n not in buffers}, buffers

    def stem(w, ids):
        return _traced(lm, w, {}, amp_level, lambda: lm.embed(
            Tensor(ids, stop_gradient=True))._value)[0]

    def layer(w, x, kind):
        module = modules[kind]
        params, buffers = split(w)
        out, aux, after = _traced(module, params, buffers, amp_level,
                                  lambda: lm._block(module, Tensor(x))._value)
        return out, overflow_total(after) - overflow_total(buffers), aux

    def reduce(x):
        return _traced(lm, {}, {}, amp_level,
                       lambda: lm.reduce(Tensor(x))._value)[0]

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
            "reduce": jax.jit(reduce),
            "mtp_stem": jax.jit(_within(mtp_stem, precision)),
            "tail": jax.jit(_within(tail, precision))}


def _block_error(reference_side):
    """The block-by-block half's one block: the reference block in float32
    on the program's own input -> (each token's error as a share of the
    block's largest contribution over all its streams' features, each
    token's router margin in it)."""
    def errors(w, start, got, kind):
        ref, margin = reference_side["layer"](w, start, kind=kind)[:2]
        err = jnp.abs(got.astype(jnp.float32) - ref).max(axis=-1)
        return err / jnp.abs(ref - start.astype(jnp.float32)).max(), margin

    return errors


def in_pieces(sides, weights, sizes, x, each_block=None):
    """Every side's (main logits, MTP logits, total, main term, MTP term,
    pairs dropped) on ids x, the sides in step so that a block's parameters
    cross to the chip once. ``sides[0]`` is the reference: its blocks give
    (x, margins, dropped, landed, balance term), a program's (x, dropped,
    weighted balance term). ``each_block(i, kind, w, inputs, outputs)`` sees
    every block's streams. Returns (the sides' outputs, the reference's
    margins a block, its landed pairs an expert block)."""
    ends = weights.ends()
    xs = [side["stem"](ends, x) for side in sides]
    dropped, aux = [0] * len(sides), [0.0] * len(sides)
    margins, landed = [], []

    def run(prefix, kind, xs, i):
        w = zeroed_overflow(weights.layer(prefix))
        outs = [side["layer"](w, h, kind=kind) for side, h in zip(sides, xs)]
        margins.append(outs[0][1])
        if kind != "dense":
            landed.append(int(outs[0][3]))
        dropped[0] += int(outs[0][2])
        aux[0] += sizes["balance_loss_weight"] * float(outs[0][4])
        for j, out in enumerate(outs[1:], 1):
            dropped[j] += int(out[1])
            aux[j] += float(out[2])
        outs = [out[0] for out in outs]
        if each_block is not None:
            each_block(i, kind, w, xs, outs)
        return outs

    trunk, module = block_kinds(sizes)
    for i, (prefix, kind) in enumerate(trunk):
        xs = run(prefix, kind, xs, i)
    hs = [side["reduce"](h) for side, h in zip(sides, xs)]
    mtp_hs = [None] * len(sides)
    if module:
        shifted = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)
        mtp_hs = [side["mtp_stem"](ends, h, shifted)
                  for side, h in zip(sides, hs)]
        for j, (prefix, kind) in enumerate(module):
            mtp_hs = run(prefix, kind, mtp_hs, len(trunk) + j)
    # a side's logits leave the chip before the next side's arrive
    outs = []
    for side, h, m, d, a in zip(sides, hs, mtp_hs, dropped, aux):
        logits, mtp_logits, total, main, mtp = jax.device_get(
            side["tail"](ends, h, m, x))
        outs.append((logits, mtp_logits, total + a, main, mtp, d))
    return outs, margins, landed


def check_train(built, reference, sizes, shapes, probe_x, rows=1,
                reference_sizes=None, reference_precision="highest"):
    """What the timed program computes on the first probe row at the timed
    size (train mode, recomputation on, the kernels the step runs) against
    ``references/xing4.0-29b-a4b.py`` (the streams as [.., n, C], every
    Sinkhorn round, attention in query blocks under an explicit mask, dense
    routing over the held experts): in float32 at logit level over the whole
    model, under the cell's amp level block by block, both loss terms both
    ways, and the held share's overflow count — of this forward and of the
    train steps made so far (the layers' buffers) — in pieces, beside the
    train state. ``reference_sizes`` / ``reference_precision``: the
    reference alone built otherwise (fewer Sinkhorn rounds, a lower
    precision), which has to FAIL (``tools/xing4_check.py``)."""
    layer = built["layer"]
    layer.train()
    x = probe_x[:rows]
    weights = Weights(layer, next(iter(x.devices())))
    ref_side = reference_pieces(reference, reference_sizes or sizes,
                                reference_precision)
    error_of = _block_error(ref_side)
    blocks, blocks32 = [], []

    def each_block(i, kind, w, inputs, outputs):
        # the reference block on the program's own input, both programs
        blocks32.append(float(error_of(w, inputs[1], outputs[1],
                                       kind)[0].max()))
        blocks.append(tuple(np.asarray(v) for v in error_of(
            w, inputs[2], outputs[2], kind)))

    (ref, got32, got_amp), margins, landed = in_pieces(
        [ref_side, framework_pieces(built, None),
         framework_pieces(built, built["amp_level"])],
        weights, sizes, x, each_block)
    margin = np.min(np.stack([np.asarray(m) for m in margins]), axis=0)
    out = compare(ref, got32, got_amp, blocks, margin)
    out["held_pairs_landed"] = landed
    # where a float32 error comes from, a block: a reading, not a limit
    out["f32_block_worst"] = blocks32
    out["overflow_train_steps"] = weights.overflow_so_far()
    out["ok"] = out["ok"] and out["overflow_train_steps"] == 0
    return out


def compare(ref, got32, got_amp, blocks, margin):
    """The check's dict from the reference's (main logits, MTP logits,
    total, main term, MTP term, dropped), the framework's in float32 and
    under amp, the amp half's per-block (errors, margins) and the
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

    # amp: block by block
    worst, shares, medians = [], [], []
    for errs, block_margin in blocks:
        decided = block_margin >= AMP_MARGIN
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
def _held_share(sizes):
    return sizes["n_routed_experts"] / sizes["router_experts"]


def block_counts(sizes):
    """(dense blocks, expert blocks) the step runs, the MTP module's block
    among the expert ones."""
    dense = min(sizes["first_k_dense_replace"], sizes["num_hidden_layers"])
    return dense, (sizes["num_hidden_layers"] - dense
                   + sizes["num_nextn_predict_layers"])


def mla_projection_flops_per_token(sizes):
    """Forward FLOPs a token of one latent-attention share's matrices: the
    two low-rank ones whole, q_b, kv_b and o_proj for the heads HELD."""
    h, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    return 2 * (h * sizes["q_lora_rank"]
                + sizes["q_lora_rank"] * heads * qk
                + h * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
                + sizes["kv_lora_rank"] * heads
                * (sizes["qk_nope_head_dim"] + sizes["v_head_dim"])
                + heads * sizes["v_head_dim"] * h)


def mla_core_flops(sizes, seq):
    """Forward FLOPs of one share's core on one sequence: QK^T over the
    (nope + rope)-wide keys and PV over the v-wide values of the heads
    HELD, on the causal seq (seq + 1) / 2 pairs."""
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    return 2 * sizes["num_attention_heads"] * (seq * (seq + 1) // 2) * (
        qk + sizes["v_head_dim"])


def held_expert_flops_per_token(sizes):
    """Forward FLOPs a token of one expert layer's HELD routed experts:
    a token's k choices land here with probability held / all."""
    return (sizes["num_experts_per_tok"] * _held_share(sizes) * 3 * 2
            * sizes["hidden_size"] * sizes["moe_intermediate_size"])


def mhc_flops_per_token(sizes):
    """Forward FLOPs a token of ONE hyper-connected sublayer: the maps'
    product (n C x (2 n + n^2)), the read (n C multiply-adds) and the
    write-back (H_res X: n^2 C, H_post^T y: n C). The Sinkhorn rounds'
    divisions (2 n^2 a round) are left out: a few thousand a token."""
    n, c = sizes["hc_mult"], sizes["hidden_size"]
    return 2 * (n * c * (2 * n + n * n) + n * c + n * n * c + n * c)


def flops_per_sample(sizes, shapes):
    """Floating-point operations one sample (one sequence) needs in a train
    step, from shapes: 2 per multiply-add of every product of the forward
    pass, times 3 for forward + backward; NOTHING recomputed, and only what
    is HELD here (16 heads, 8 experts' rows, 16,384 rows of the vocabulary).
    Counted: every block's latent-attention share (seven matrices, the
    core), two hyper-connections a block (maps, read, write-back), the
    dense block's SwiGLU, in every expert block the router over all 64, the
    shared expert and the held experts' rows, the MTP module's 7,168 ->
    3,584 projection, and the head at every position once a head."""
    seq = shapes["input_ids"][0]
    h = sizes["hidden_size"]
    dense, expert = block_counts(sizes)
    mtp = sizes["num_nextn_predict_layers"]
    per_token = (
        (dense + expert) * (mla_projection_flops_per_token(sizes)
                            + 2 * mhc_flops_per_token(sizes))
        + dense * 3 * 2 * h * sizes["intermediate_size"]
        + expert * (2 * h * sizes["router_experts"]
                    + sizes["n_shared_experts"] * 3 * 2 * h
                    * sizes["moe_intermediate_size"]
                    + held_expert_flops_per_token(sizes))
        + mtp * 2 * 2 * h * h
        + (1 + mtp) * 2 * h * sizes["vocab_size"])
    return 3.0 * (seq * per_token
                  + (dense + expert) * mla_core_flops(sizes, seq))
