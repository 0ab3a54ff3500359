"""Shared decode-model fixture + subprocess server entry.

``toy_decode_model`` builds a deterministic single-layer masked-
attention decoder (embedding -> one attention layer over the KV cache
-> tanh mlp -> logits) that honours the DecodeModel contract:
invalid/padded kv positions are masked to exact ``-inf`` before
softmax and zeroed after, which is what makes decode bitwise stable
across batch buckets, seq buckets, and neighbor content (the
continuous-batching determinism contract, tests/test_decode.py).

Run as ``python tests/decode_worker.py`` (env-configured) it serves
the model through a PredictorServer with a warmed DecodeEngine and
prints one ``PORT <n>`` line — the subprocess replica that
tests/test_decode_resume.py and tests/test_disagg.py drive. Env:

    DECODE_WORKER_SEED        model weights seed          (0)
    DECODE_WORKER_HIDDEN      hidden width                (32)
    DECODE_WORKER_VOCAB       vocab size                  (64)
    DECODE_WORKER_MAX_SLOTS   concurrent sequences        (8)
    DECODE_WORKER_MAX_SEQ     max prompt+generated length (64)
    DECODE_WORKER_MAX_PROMPT  admission cap on prompts    (16)
    DECODE_WORKER_PHASE       replica pool ("prefill" | "decode";
                              empty = both) — shapes the warmup
                              ladder and the health/stats phase field
    PADDLE_TPU_ARTIFACT_DIR   artifact store (zero-cold-start rewarm)

The ladder is warmed before ``PORT`` prints.
"""
import os
import sys

import numpy as np


def toy_decode_model(hidden=32, vocab=64, seed=0, feature_spec=(),
                     eos_token_id=None, anchor=0.0, draft=None):
    """Deterministic toy decoder following the DecodeModel contract.

    ``feature_spec``: optional per-sequence feature arrays (any wire
    dtype). Each feature is reduced to one scalar (cast to f32) and
    added to the pre-logits hidden state, so every feature byte
    influences every generated token — a bitwise-equivalence test
    over features is therefore a real test, not a dead input.

    ``anchor``: strength of a shared token-transition bias — a fixed
    (vocab, vocab) matrix drawn from ``RandomState(777)`` regardless
    of ``seed``/``hidden``, added to the logits as
    ``anchor * A[last_token]``. Two models with different widths or
    seeds but the same nonzero anchor mostly agree on the greedy next
    token, which is exactly the draft/target correlation speculative
    decoding needs (>0.5 acceptance on the toy). anchor=0 (default)
    adds NOTHING: existing models stay byte-identical.

    ``draft``: optional companion DecodeModel (same vocab + feature
    spec) attached as ``model.draft`` for speculative decoding.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.decode import DecodeModel

    rng = np.random.RandomState(seed)

    def mk(*shape):
        return jnp.asarray((rng.randn(*shape) * 0.5).astype(np.float32))

    params = [
        mk(vocab, hidden),   # E   token embedding
        mk(hidden, hidden),  # Wq
        mk(hidden, hidden),  # Wk
        mk(hidden, hidden),  # Wv
        mk(hidden, hidden),  # Wo
        mk(hidden, vocab),   # U   unembedding
    ]
    if anchor:
        A = jnp.asarray(
            (np.random.RandomState(777).randn(vocab, vocab)
             * 0.5).astype(np.float32))
        params = params + [A * float(anchor)]

    def _feat_bias(feats):
        # one scalar per row from each feature array: mean over the
        # trailing dims after an exact cast to f32 (bool -> {0,1},
        # ints exact within f32 range for the small test values)
        bias = 0.0
        for f in feats:
            ff = f.astype(jnp.float32)
            bias = bias + jnp.mean(ff.reshape(ff.shape[0], -1), axis=-1)
        # small scale: the bias must nudge logits, not saturate every
        # row to the same argmax
        return bias * 0.1

    def prefill_fn(p, tokens, lengths, *feats):
        E, Wq, Wk, Wv, Wo, U = p[:6]
        emb = E[tokens]                       # [b,s,h]
        q, k, v = emb @ Wq, emb @ Wk, emb @ Wv
        s = tokens.shape[1]
        pos = jnp.arange(s)
        causal = pos[None, :, None] >= pos[None, None, :]
        valid = pos[None, None, :] < lengths[:, None, None]
        mask = causal & valid
        scores = jnp.einsum("bph,bsh->bps", q, k)
        scores = jnp.where(mask, scores, -jnp.inf)
        prob = jnp.where(mask, jax.nn.softmax(scores, axis=-1), 0.0)
        ctx = jnp.einsum("bps,bsh->bph", prob, v)
        h = jnp.tanh(ctx @ Wo + emb)          # [b,s,h]
        last = h[jnp.arange(tokens.shape[0]), lengths - 1]
        if feats:
            last = last + _feat_bias(feats)[:, None]
        logits = last @ U
        if anchor:
            last_tok = tokens[jnp.arange(tokens.shape[0]), lengths - 1]
            logits = logits + p[6][last_tok]
        return (logits, k, v)

    def step_fn(p, tokens, positions, kv_k, kv_v, *feats):
        E, Wq, Wk, Wv, Wo, U = p[:6]
        emb = E[tokens]                       # [b,h]
        q, k, v = emb @ Wq, emb @ Wk, emb @ Wv
        b = tokens.shape[0]
        rows = jnp.arange(b)
        kv_k = kv_k.at[rows, positions].set(k)
        kv_v = kv_v.at[rows, positions].set(v)
        s = kv_k.shape[1]
        mask = jnp.arange(s)[None, :] <= positions[:, None]
        scores = jnp.einsum("bh,bsh->bs", q, kv_k)
        scores = jnp.where(mask, scores, -jnp.inf)
        prob = jnp.where(mask, jax.nn.softmax(scores, axis=-1), 0.0)
        ctx = jnp.einsum("bs,bsh->bh", prob, kv_v)
        h = jnp.tanh(ctx @ Wo + emb)
        if feats:
            h = h + _feat_bias(feats)[:, None]
        logits = h @ U
        if anchor:
            logits = logits + p[6][tokens]
        return (logits, k, v)

    return DecodeModel(
        params, prefill_fn, step_fn,
        kv_spec=(((hidden,), np.float32), ((hidden,), np.float32)),
        vocab_size=vocab, feature_spec=feature_spec,
        eos_token_id=eos_token_id, draft=draft)


def reference_decode(model, prompt, max_new_tokens, features=(),
                     max_seq_len=64, min_seq_bucket=8):
    """Oracle: decode ONE sequence through a fresh single-slot engine
    (slot bucket 2 = the gemm regime, own seq-bucket ladder). The
    continuous-batching bitwise contract is measured against this."""
    from paddle_tpu.inference.decode import DecodeEngine

    eng = DecodeEngine(model, max_slots=1, max_seq_len=max_seq_len,
                       min_seq_bucket=min_seq_bucket,
                       watchdog_interval=0, name="decode-ref")
    try:
        return eng.generate(prompt, max_new_tokens=max_new_tokens,
                            features=features, timeout=120)
    finally:
        eng.close()


def _env_int(name, default):
    return int(os.environ.get(name, default))


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # run directly (python tests/decode_worker.py): the repo root is
    # the script dir's parent, not on sys.path
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.inference.decode import DecodeEngine
    from paddle_tpu.inference.server import PredictorServer

    model = toy_decode_model(
        hidden=_env_int("DECODE_WORKER_HIDDEN", 32),
        vocab=_env_int("DECODE_WORKER_VOCAB", 64),
        seed=_env_int("DECODE_WORKER_SEED", 0))
    engine = DecodeEngine(
        model,
        phase=os.environ.get("DECODE_WORKER_PHASE") or None,
        max_slots=_env_int("DECODE_WORKER_MAX_SLOTS", 8),
        max_seq_len=_env_int("DECODE_WORKER_MAX_SEQ", 64),
        max_prompt_len=_env_int("DECODE_WORKER_MAX_PROMPT", 16),
        max_queue=256)
    engine.warmup()

    def run_fn(*arrays):  # non-decode cmd-1 traffic: echo
        return list(arrays)

    server = PredictorServer(run_fn, decode_engine=engine,
                             own_decode_engine=True)
    print(f"PORT {server.port}", flush=True)
    try:
        server._thread.join()
    except KeyboardInterrupt:
        pass
    server.stop()


if __name__ == "__main__":
    main()
