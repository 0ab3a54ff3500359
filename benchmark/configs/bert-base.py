"""bert-base: google-bert/bert-base-uncased at its published sizes (the
sizes are ``bert-base.json``; nothing is reduced), built with the
framework's ``text.models`` classes. The train recipe and the served
wrapper live here; what differs from the source is listed under
``departures`` in the JSON."""
import jax
import jax.numpy as jnp

# framework f32 (precision "highest") against the float32 reference, as a
# share of the largest reference logit: both sides compute the same
# equations in float32 and differ in summation order only, which stays at
# the 1e-7 level per layer and compounds over 12 layers and a 768-wide tied
# decoder. Observed on the v5e (PR 22, 15 seeds): 3.6e-7 to 4.0e-7. A bf16
# computation (eps 2^-8 = 3.9e-3 per rounding; the amp forward below is
# off by 1.1e-2) misses this by more than two orders.
F32_RTOL = 2e-5
# amp O1 (bf16 matmul inputs, f32 accumulation, f32 layer norm/softmax):
# each of ~75 matmuls rounds its inputs to bf16 (4e-3 relative) and the
# errors add like a random walk through 12 post-LN layers. Observed on the
# v5e (PR 22, 15 seeds): 1.1e-2 to 1.5e-2; a wrong program is off by O(1)
# of the scale.
AMP_RTOL = 5e-2

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "intermediate_size", "hidden_act",
              "hidden_dropout_prob", "attention_probs_dropout_prob",
              "max_position_embeddings", "type_vocab_size",
              "initializer_range", "pad_token_id")

#: toy widths for the CPU rehearsal in benchmark/tests (never a cell)
TOY = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
       "num_attention_heads": 4, "intermediate_size": 128,
       "max_position_embeddings": 64}


def model_kwargs(sizes):
    return {k: sizes[k] for k in MODEL_KEYS if k in sizes}


def mlm_loss(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def build_train(seed, sizes, shapes):
    """The model, loss and optimizer of a train cell. ``shapes`` are the
    traffic file's field shapes: the step's one input array is
    [input_ids | masked_positions] packed along dim 1."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.text.models import BertForPretraining

    seq = shapes["input_ids"][0]

    class PackedMLM(nn.Layer):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, packed):
            mlm_logits, _ = self.inner(
                packed[:, :seq], masked_positions=packed[:, seq:])
            return mlm_logits

    paddle.seed(seed)
    model = BertForPretraining(**model_kwargs(sizes))
    opt = optimizer.AdamW(1e-4, parameters=model.parameters(),
                          weight_decay=0.01,
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    return {"layer": PackedMLM(model), "loss_fn": mlm_loss, "optimizer": opt,
            "amp_level": "O1"}


def check_train(built, reference, sizes, shapes, probe_x):
    """Eval-mode MLM logits of the framework model on the first 8 probe
    rows against ``references/bert-base.py``."""
    from benchmark.harness.framework import compare_with_reference

    seq = shapes["input_ids"][0]

    def ref_fn(params, buffers, packed):
        w = {k[len("inner."):]: v for k, v in params.items()}
        return reference.forward_mlm(w, packed[:, :seq], packed[:, seq:],
                                     sizes)

    return compare_with_reference(built["layer"], ref_fn, probe_x[:8],
                                  built["amp_level"], F32_RTOL, AMP_RTOL)


def flops_per_sample(sizes, shapes):
    """Floating-point operations one sample (one sequence) needs in a train
    step, from shapes: 2 per multiply-add of every matmul of the forward
    pass, times 3 for forward + backward (the backward pass computes two
    matmuls for each of the forward's). Elementwise work, the pooler and
    the NSP head (not in the loss) are not counted; nothing is recomputed."""
    seq = shapes["input_ids"][0]
    masked = shapes["masked_positions"][0]
    h, ffn = sizes["hidden_size"], sizes["intermediate_size"]
    layers, vocab = sizes["num_hidden_layers"], sizes["vocab_size"]
    per_token = layers * (2 * (4 * h * h + 2 * h * ffn)   # projections, FFN
                          + 4 * seq * h)                  # QK^T and PV
    per_masked = 2 * h * h + 2 * h * vocab                # transform, decoder
    return 3.0 * (seq * per_token + masked * per_masked)


def build_serve(seed, sizes, shapes):
    """The served model: ``BertModel`` whose output is the pooled [n, hidden]
    vector (what an embedding / reranking backend returns)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.static import InputSpec
    from paddle_tpu.text.models import BertModel

    class PooledEncoder(nn.Layer):
        def __init__(self, bert):
            super().__init__()
            self.bert = bert

        def forward(self, input_ids):
            _, pooled = self.bert(input_ids)
            return pooled

    paddle.seed(seed)
    layer = PooledEncoder(BertModel(**model_kwargs(sizes)))
    layer.eval()
    seq = shapes["input_ids"][0]
    return {"layer": layer,
            "input_spec": [InputSpec([None, seq], "int32")]}



# Served reply against the float32 reference, absolute, on tanh-pooled
# outputs in [-1, 1]. On the TPU the served float32 program runs its
# matmuls as bf16 MXU passes at jax's default precision: an f32-level
# difference in one layer's output can flip the bf16 rounding of the next
# matmul's input (4e-3 relative), and 12 layers compound it. Observed on
# the v5e (PR 21, chip_smoke.py SERVE_ATOL; served against the eager
# forward): 0.0 or 7.4e-3 by bucket on one chip; a wrong program is off by
# O(1). On the CPU both sides are float32.
SERVE_ATOL = {"tpu": 5e-2, "cpu": 1e-4}


def check_serve(built, reference, sizes, rows, served, platform):
    """``served``: the replies for ``rows`` through the socket. Checks them
    against the reference, and the framework's own float32 forward (matmul
    precision "highest") against the reference at F32_RTOL."""
    import numpy as np

    from benchmark.harness.framework import functional_forward

    layer = built["layer"]
    params, buffers = layer.functional_state()
    ref = np.asarray(jax.jit(
        lambda w, x: reference.forward_pooled(w, x, sizes, "bert."))(
            dict(params), rows))
    with jax.default_matmul_precision("highest"):
        got32 = np.asarray(jax.jit(lambda p, b, x: functional_forward(
            layer, p, b, x))(params, buffers, rows))
    atol = SERVE_ATOL.get(platform, SERVE_ATOL["tpu"])
    scale = float(np.abs(ref).max())
    e32 = float(np.abs(got32 - ref).max()) / scale
    served_err = (float(np.abs(served - ref).max())
                  if served.shape == ref.shape else float("inf"))
    return {"ok": bool(np.isfinite(served).all() and e32 <= F32_RTOL
                       and served_err <= atol),
            "f32_rel_err": e32, "f32_rtol": F32_RTOL,
            "served_abs_err": served_err, "served_atol": atol}
