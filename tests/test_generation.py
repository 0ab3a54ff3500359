"""Generation tests (reference capability: PaddleNLP GenerationMixin).

Key oracle: the KV-cached lax.scan decode must emit the exact same tokens
as the cache-free full-forward decode (greedy), which itself must match an
argmax chain computed by hand with repeated full forwards.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.text import GPTModel, LlamaModel, generation


@pytest.fixture(scope="module")
def tiny_llama():
    paddle.seed(3)
    return LlamaModel(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=4, intermediate_size=64, max_seq_len=64)


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(4)
    return GPTModel(vocab_size=61, hidden_size=32, num_layers=2, num_heads=4,
                    max_seq_len=64)


def _manual_greedy(model, ids, n):
    """Oracle: repeated full forwards + argmax, no padding tricks."""
    ids = np.array(ids, np.int32)
    for _ in range(n):
        logits = np.asarray(model(paddle.to_tensor(ids))._value)
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        ids = np.concatenate([ids, nxt[:, None]], axis=1)
    return ids


class TestGenericGenerate:
    def test_greedy_matches_manual(self, tiny_gpt):
        rng = np.random.RandomState(0)
        prompt = rng.randint(0, 61, (2, 5)).astype(np.int32)
        out = tiny_gpt.generate(prompt, max_new_tokens=6)
        ref = _manual_greedy(tiny_gpt, prompt, 6)
        np.testing.assert_array_equal(out, ref)

    def test_1d_prompt_promoted(self, tiny_gpt):
        out = tiny_gpt.generate(np.array([1, 2, 3], np.int32), max_new_tokens=3)
        assert out.shape == (1, 6)

    def test_eos_early_stop(self, tiny_gpt):
        prompt = np.array([[1, 2, 3]], np.int32)
        ref = _manual_greedy(tiny_gpt, prompt, 8)
        eos = int(ref[0, 3])  # first generated token == eos -> stop right away
        out = tiny_gpt.generate(prompt, max_new_tokens=8, eos_token_id=eos)
        assert out.shape[1] == 4
        assert out[0, 3] == eos

    def test_sampling_valid_and_seeded(self, tiny_gpt):
        prompt = np.array([[5, 6]], np.int32)
        a = tiny_gpt.generate(prompt, max_new_tokens=5, do_sample=True,
                              top_k=10, temperature=0.8, seed=11)
        b = tiny_gpt.generate(prompt, max_new_tokens=5, do_sample=True,
                              top_k=10, temperature=0.8, seed=11)
        np.testing.assert_array_equal(a, b)
        assert ((a >= 0) & (a < 61)).all()


class TestLlamaCachedDecode:
    def test_cached_equals_uncached_greedy(self, tiny_llama):
        rng = np.random.RandomState(1)
        prompt = rng.randint(0, 97, (2, 4)).astype(np.int32)
        cached = tiny_llama.generate(prompt, max_new_tokens=6)
        uncached = tiny_llama.generate(prompt, max_new_tokens=6,
                                       use_cache=False)
        np.testing.assert_array_equal(cached, uncached)

    def test_cached_matches_manual(self, tiny_llama):
        prompt = np.array([[7, 11, 13]], np.int32)
        out = tiny_llama.generate(prompt, max_new_tokens=5)
        ref = _manual_greedy(tiny_llama, prompt, 5)
        np.testing.assert_array_equal(out, ref)

    def test_gqa_cached_decode(self):
        paddle.seed(9)
        m = LlamaModel(vocab_size=53, hidden_size=32, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=64)
        prompt = np.array([[3, 1, 4, 1, 5]], np.int32)
        cached = m.generate(prompt, max_new_tokens=4)
        ref = _manual_greedy(m, prompt, 4)
        np.testing.assert_array_equal(cached, ref)

    def test_single_new_token(self, tiny_llama):
        prompt = np.array([[2, 3]], np.int32)
        out = tiny_llama.generate(prompt, max_new_tokens=1)
        ref = _manual_greedy(tiny_llama, prompt, 1)
        np.testing.assert_array_equal(out, ref)

    def test_sampling_runs(self, tiny_llama):
        prompt = np.array([[2, 3, 5]], np.int32)
        out = tiny_llama.generate(prompt, max_new_tokens=4, do_sample=True,
                                  top_p=0.9, temperature=1.2, seed=5)
        assert out.shape == (1, 7)
        assert ((out >= 0) & (out < 97)).all()


class TestSamplingOps:
    def test_top_k_keeps_k(self):
        import jax.numpy as jnp

        logits = jnp.asarray(np.random.RandomState(0).randn(2, 20),
                             jnp.float32)
        f = generation._apply_top_k(logits, 5)
        kept = np.sum(np.asarray(f) > np.finfo(np.float32).min / 2, axis=-1)
        np.testing.assert_array_equal(kept, [5, 5])

    def test_top_p_keeps_prefix(self):
        import jax.numpy as jnp

        logits = jnp.asarray([[10.0, 9.0, 1.0, 0.0, -3.0]], jnp.float32)
        f = np.asarray(generation._apply_top_p(logits, 0.9))
        # two dominant tokens cover >0.9 prob -> rest filtered
        assert np.isfinite(f[0, 0]) and np.isfinite(f[0, 1])
        assert (f[0, 2:] < np.finfo(np.float32).min / 2).all()


class TestBf16Decode:
    """``m.to(dtype="bfloat16")`` is the serving precision for the
    cached generate — pin that path on CPU so a dtype bug fails here,
    not on chip time."""

    def test_bf16_cached_decode_runs_and_is_deterministic(self):
        paddle.seed(5)
        m = LlamaModel(vocab_size=97, hidden_size=32, num_layers=2,
                       num_heads=4, intermediate_size=64, max_seq_len=64)
        m.eval()
        m.to(dtype="bfloat16")
        rng = np.random.RandomState(2)
        prompt = rng.randint(0, 97, (2, 4)).astype(np.int32)
        a = m.generate(prompt, max_new_tokens=6)
        b = m.generate(prompt, max_new_tokens=6)
        np.testing.assert_array_equal(a, b)  # greedy = deterministic
        assert a.shape == (2, 10)
        assert a.min() >= 0 and a.max() < 97
        np.testing.assert_array_equal(a[:, :4], prompt)
