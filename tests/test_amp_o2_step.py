"""amp O2 (pure bf16) through spmd.build_train_step.

``amp_level="O2"`` is part of build_train_step's surface; a broken O2
path must fail here (CPU, tiny BERT), not on chip time. O1
and O2 train the same seeded model: both must converge, and their loss
trajectories must stay close (bf16 master weights cost ~3 decimal
digits, not convergence).
"""
import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed import spmd, topology
from paddle_tpu.text.models import BertForPretraining

B, SEQ, MAXP = 8, 32, 5


def _train(amp_level, steps=8):
    paddle.seed(0)
    model = BertForPretraining(
        vocab_size=512, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=128,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    opt = optimizer.AdamW(1e-3, parameters=model.parameters(),
                          weight_decay=0.01)
    vocab = model.bert.vocab_size

    class W(nn.Layer):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, packed):
            mlm, _ = self.inner(packed[:, :SEQ],
                                masked_positions=packed[:, SEQ:])
            return mlm

    def loss_fn(mlm, labels):
        logp = jax.nn.log_softmax(mlm.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None],
                                     axis=-1)[..., 0]
        return -jnp.mean(picked)

    mesh = topology.build_mesh(dp=1)
    topology.set_global_mesh(mesh)
    step_fn, init_fn = spmd.build_train_step(W(model), loss_fn, opt,
                                             mesh=mesh,
                                             amp_level=amp_level,
                                             donate=False)
    params, opt_state = init_fn()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (B, SEQ)).astype(np.int32)
    pos = np.stack([rng.choice(SEQ, MAXP, replace=False)
                    for _ in range(B)]).astype(np.int32)
    packed = jnp.asarray(np.concatenate([ids, pos], axis=1))
    labels = jnp.asarray(rng.randint(0, vocab, (B, MAXP)).astype(np.int32))
    key = jax.random.PRNGKey(0)
    losses = []
    for i in range(steps):
        loss, params, opt_state = step_fn(params, opt_state, packed, labels,
                                          key=jax.random.fold_in(key, i))
        losses.append(float(loss))
    return losses


def test_o2_converges_and_tracks_o1():
    l1 = _train("O1")
    l2 = _train("O2")
    assert l1[-1] < l1[0] * 0.8, l1
    assert l2[-1] < l2[0] * 0.8, l2
    # same seeded run: trajectories agree to bf16-class tolerance
    for a, b in zip(l1, l2):
        assert abs(a - b) / max(abs(a), 1e-6) < 0.05, (l1, l2)
