"""Quantized serving contracts (ISSUE 13 tentpole): quant modes as
first-class, artifact-store-native serving modes.

Covers: jit.save(quant=)/load round trips per mode (meta + distinct
fingerprints + documented accuracy bounds), the batching engine over a
quantized model (bitwise batch-vs-direct, store-backed zero-compile
rewarm, quant-mode store isolation), the decode engine's quantized
bitwise solo-vs-batch determinism contract, the
``PADDLE_TPU_SERVING_QUANT`` deployment knob on both engines and
``serve_model``, and the mode label on stats/metrics surfaces.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.inference.batching import BatchingEngine
from paddle_tpu.inference.decode import DecodeEngine
from paddle_tpu.jit import load as jit_load
from paddle_tpu.quantization import ACCURACY_BOUNDS, QUANT_MODES
from paddle_tpu.quantization.serving import quantize_decode_model
from paddle_tpu.serialize.artifact_store import ArtifactStore
from paddle_tpu.static import InputSpec

from decode_worker import reference_decode, toy_decode_model

pytestmark = pytest.mark.quant

HID = 16


class _MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(HID, 24)
        self.fc2 = nn.Linear(24, 6)

    def forward(self, x):
        return self.fc2(nn.functional.relu(self.fc1(x)))


def _fresh_mlp():
    paddle.seed(0)
    m = _MLP()
    m.eval()
    return m


def _save(tmp_path, mode, name=None):
    prefix = str(tmp_path / (name or f"mlp_{mode or 'f32'}"))

    def calib():
        rng = np.random.RandomState(1)
        for _ in range(4):
            yield rng.randn(3, HID).astype(np.float32)

    kw = {}
    if mode is not None:
        kw["quant"] = mode
        if mode == "w8a8":
            kw["quant_calib"] = calib
    paddle.jit.save(_fresh_mlp(), prefix,
                    input_spec=[InputSpec([None, HID], "float32")], **kw)
    return prefix


X = np.random.RandomState(0).randn(3, HID).astype(np.float32)


class TestQuantExport:
    def test_all_modes_roundtrip_within_bounds(self, tmp_path):
        import json

        ref = None
        fingerprints = {}
        for mode in (None,) + QUANT_MODES:
            prefix = _save(tmp_path, mode)
            layer = jit_load(prefix)
            out = np.asarray(layer(X)._value)
            if mode is None:
                ref = out
            else:
                rel = (np.max(np.abs(out - ref))
                       / (np.max(np.abs(ref)) + 1e-9))
                assert rel < ACCURACY_BOUNDS[mode], (mode, rel)
            assert layer._polymorphic  # quant keeps the bucket enabler
            assert getattr(layer, "_quant_mode", None) == mode
            fingerprints[mode] = layer._model_fingerprint
            meta = json.load(open(prefix + ".pdmeta.json"))
            assert meta["quant"] == mode
            if mode in ("w8", "w8a8"):
                assert "fc1" in meta["quant_meta"]["weight_scale_layers"]
            if mode == "w8a8":
                assert meta["quant_meta"]["act_scales"]["fc1"] > 0
        # every mode is a DISTINCT artifact-store identity
        assert len(set(fingerprints.values())) == len(fingerprints)

    def test_w8a8_needs_calib(self, tmp_path):
        with pytest.raises(ValueError, match="quant_calib"):
            paddle.jit.save(_fresh_mlp(), str(tmp_path / "m"),
                            input_spec=[InputSpec([None, HID], "float32")],
                            quant="w8a8")

    def test_unknown_mode_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="unknown quant mode"):
            paddle.jit.save(_fresh_mlp(), str(tmp_path / "m"),
                            input_spec=[InputSpec([None, HID], "float32")],
                            quant="int4")

    def test_f32_spelling_is_plain_save(self, tmp_path):
        """quant="f32" (the spelling serve_model / the env knob / the
        ArtifactKey accept) must be a plain f32 save — sidecar records
        None, nothing quantized, and the fingerprint fold treats both
        f32 spellings identically (one templated mode string works on
        every knob)."""
        import json

        from paddle_tpu.serialize.export import model_fingerprint

        prefix = str(tmp_path / "f32_spelled")
        paddle.jit.save(_fresh_mlp(), prefix,
                        input_spec=[InputSpec([None, HID], "float32")],
                        quant="f32")
        meta = json.load(open(prefix + ".pdmeta.json"))
        assert meta["quant"] is None
        layer = jit_load(prefix)
        assert layer._quant_mode is None
        assert {str(np.asarray(p._value).dtype)
                for p in layer._parameters.values()} == {"float32"}
        # the hash level: both f32 spellings are the historical hash
        blob = b"module-bytes"
        assert (model_fingerprint(blob) == model_fingerprint(blob, "f32"))
        assert model_fingerprint(blob) != model_fingerprint(blob, "w8")

    def test_bf16w_params_stored_half_width(self, tmp_path):
        layer = jit_load(_save(tmp_path, "bf16w"))
        dts = {str(np.asarray(p._value).dtype)
               for p in layer._parameters.values()}
        assert dts == {"bfloat16"}

    def test_resave_of_mutated_model_records_true_mode(self, tmp_path):
        """jit.save(quant='w8') converts IN PLACE — a later quant-less
        re-save of the same object must record the mode it actually
        carries (never stamp an int8 program f32), and a CONFLICTING
        mode must be rejected."""
        import json

        paddle.seed(0)
        m = _MLP()
        m.eval()
        p1 = str(tmp_path / "first")
        paddle.jit.save(m, p1, input_spec=[InputSpec([None, HID],
                                                     "float32")],
                        quant="w8")
        p2 = str(tmp_path / "resave")
        paddle.jit.save(m, p2, input_spec=[InputSpec([None, HID],
                                                     "float32")])
        meta = json.load(open(p2 + ".pdmeta.json"))
        assert meta["quant"] == "w8"
        assert meta["quant_meta"]["detected"] is True
        assert jit_load(p2)._quant_mode == "w8"
        with pytest.raises(ValueError, match="already carries 'w8'"):
            paddle.jit.save(m, str(tmp_path / "conflict"),
                            input_spec=[InputSpec([None, HID],
                                                  "float32")],
                            quant="bf16w")

    def test_ptq_save_flow_records_mode(self, tmp_path):
        """PostTrainingQuantization.save_quantized_model (which calls
        jit.save WITHOUT quant=) now records the frozen model's true
        mode via detection — the reference slim flow gets correctly
        labelled artifacts for free."""
        import json

        from paddle_tpu.quantization import PostTrainingQuantization

        paddle.seed(0)
        ptq = PostTrainingQuantization(_MLP())
        ptq.quantize()
        prefix = str(tmp_path / "ptq")
        ptq.save_quantized_model(
            prefix, input_spec=[InputSpec([None, HID], "float32")])
        assert json.load(open(prefix + ".pdmeta.json"))["quant"] == "w8"


class TestQuantEngine:
    def test_batched_bitwise_equals_direct(self, tmp_path):
        """The PR 4 contract holds per quant mode: a >= 2-row request
        through the engine is BITWISE the direct layer call — the
        quantized program is one program, batching must not change
        its math."""
        for mode in ("w8", "bf16w"):
            layer = jit_load(_save(tmp_path, mode))
            direct = np.asarray(layer(X)._value)
            eng = BatchingEngine.for_layer(layer, max_batch_size=4,
                                           max_wait_ms=1.0,
                                           watchdog_interval=0,
                                           name=f"quant-eng-{mode}")
            try:
                out = eng.infer([X], timeout=60)[0]
                assert eng.stats()["quant"] == mode
            finally:
                eng.close()
            assert np.array_equal(out, direct), mode

    def test_store_rewarm_zero_compiles(self, tmp_path):
        """Tentpole acceptance: a fresh engine over a QUANTIZED model
        warms its full bucket ladder from the artifact store with zero
        inline XLA compiles, bitwise-identically."""
        store = ArtifactStore(str(tmp_path / "store"))
        prefix = _save(tmp_path, "w8")

        def run_once():
            layer = jit_load(prefix)
            eng = BatchingEngine.for_layer(layer, artifact_store=store,
                                           max_batch_size=4,
                                           max_wait_ms=1.0,
                                           watchdog_interval=0,
                                           name="quant-store")
            try:
                eng.warmup()
                out = eng.infer([X], timeout=60)[0]
                st = eng.stats()
                return out, st["compiles"], st["store_loads"]
            finally:
                eng.close()

        out1, compiles1, loads1 = run_once()
        assert compiles1 == 3 and loads1 == 0  # buckets 1, 2, 4
        out2, compiles2, loads2 = run_once()
        assert compiles2 == 0 and loads2 == 3
        assert np.array_equal(out1, out2)

    def test_quant_mode_store_isolation(self, tmp_path):
        """Satellite: a w8 artifact must never be served to an f32
        request (and vice versa) — the key mismatch is a clean miss,
        so the f32 engine compiles its own ladder and the store shows
        zero corruption."""
        store = ArtifactStore(str(tmp_path / "store"))
        # one save per mode, loaded repeatedly — the fleet workflow
        # (every replica serves the SAME exported artifact; jax module
        # bytes are only guaranteed stable for one export)
        prefixes = {m: _save(tmp_path, m) for m in ("w8", None)}

        def warm(mode):
            layer = jit_load(prefixes[mode])
            eng = BatchingEngine.for_layer(layer, artifact_store=store,
                                           max_batch_size=4,
                                           max_wait_ms=1.0,
                                           watchdog_interval=0,
                                           name=f"iso-{mode or 'f32'}")
            try:
                eng.warmup()
                st = eng.stats()
                return np.asarray(eng.infer([X], timeout=60)[0]), \
                    st["compiles"], st["store_loads"]
            finally:
                eng.close()

        w8_out, w8_compiles, _ = warm("w8")
        assert w8_compiles == 3
        f32_out, f32_compiles, f32_loads = warm(None)
        # every f32 lookup was a clean miss: no quantized artifact can
        # satisfy it, nothing got quarantined, outputs differ (the w8
        # program genuinely quantizes)
        assert f32_compiles == 3 and f32_loads == 0
        assert store.stats()["corrupt"] == 0
        assert not np.array_equal(w8_out, f32_out)
        # and a SECOND w8 engine still loads the w8 ladder untouched
        _, again_compiles, again_loads = warm("w8")
        assert again_compiles == 0 and again_loads == 3


class TestQuantDecode:
    def _model(self):
        return toy_decode_model(hidden=HID, vocab=32, seed=0)

    @pytest.mark.parametrize("mode", ["w8", "bf16w"])
    def test_solo_vs_batch_bitwise(self, mode):
        """The load-bearing determinism contract, per quant mode: a
        sequence decoded inside a continuous batch (staggered joins,
        different-length neighbors) emits EXACTLY its solo tokens."""
        qm = quantize_decode_model(self._model(), mode)
        prompt = np.array([3, 1, 4, 1, 5], np.int32)
        short = np.array([9, 2], np.int32)
        solo_main = reference_decode(qm, prompt, 10, max_seq_len=32)
        solo_short = reference_decode(qm, short, 4, max_seq_len=32)
        eng = DecodeEngine(qm, max_slots=4, max_seq_len=32,
                           min_seq_bucket=8, watchdog_interval=0,
                           name=f"qdec-{mode}")
        try:
            reqs = [eng.submit(prompt, max_new_tokens=10),
                    eng.submit(short, max_new_tokens=4),
                    eng.submit(prompt, max_new_tokens=10)]
            outs = [r.result(timeout=120) for r in reqs]
            assert eng.stats()["quant"] == mode
        finally:
            eng.close()
        assert outs[0].tolist() == solo_main.tolist()
        assert outs[1].tolist() == solo_short.tolist()
        assert outs[2].tolist() == solo_main.tolist()

    def test_env_knob_quantizes_engine(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_QUANT", "w8")
        eng = DecodeEngine(self._model(), max_slots=2, max_seq_len=16,
                           watchdog_interval=0, name="qdec-env")
        try:
            assert eng.stats()["quant"] == "w8"
            assert eng._model.quant == "w8"
        finally:
            eng.close()

    def test_mode_mismatch_rejected(self):
        qm = quantize_decode_model(self._model(), "w8")
        with pytest.raises(ValueError, match="quantized as 'w8'"):
            DecodeEngine(qm, max_slots=2, max_seq_len=16,
                         watchdog_interval=0, quant="bf16w",
                         name="qdec-mismatch")

    def test_store_rewarm_zero_compiles_quant(self, tmp_path):
        """Decode tentpole acceptance: the quantized decode ladder
        persists — a fresh engine warms every (phase, rows, seq) rung
        from the store with zero inline compiles and decodes bitwise
        the same."""
        store = ArtifactStore(str(tmp_path / "store"))
        prompt = np.array([3, 1, 4], np.int32)

        def run_once():
            qm = quantize_decode_model(self._model(), "w8")
            eng = DecodeEngine(qm, max_slots=2, max_seq_len=16,
                               min_seq_bucket=8, store=store,
                               watchdog_interval=0, name="qdec-store")
            try:
                eng.warmup()
                toks = eng.generate(prompt, max_new_tokens=6,
                                    timeout=120)
                st = eng.stats()
                return toks.tolist(), st["compiles"], st["store_loads"]
            finally:
                eng.close()

        t1, c1, l1 = run_once()
        assert c1 > 0 and l1 == 0
        t2, c2, l2 = run_once()
        assert c2 == 0 and l2 == c1
        assert t1 == t2


class TestServeModelKnob:
    def test_mismatch_fails_fast(self, tmp_path):
        from paddle_tpu.inference.server import serve_model

        prefix = _save(tmp_path, None, name="f32_model")
        with pytest.raises(ValueError, match="does not match"):
            serve_model(prefix, quant="w8")

    def test_invalid_mode_fails_at_entry(self, tmp_path):
        """A typo'd deployment knob ('W8', 'int8') must name the valid
        mode set immediately — not surface later as a misleading
        're-save your model' mismatch."""
        from paddle_tpu.inference.server import serve_model

        prefix = _save(tmp_path, None, name="f32_model2")
        with pytest.raises(ValueError, match="unknown quant mode"):
            serve_model(prefix, quant="W8")

    def test_matching_mode_serves(self, tmp_path):
        import json
        import socket
        import struct

        from paddle_tpu.inference.server import (_encode_arrays,
                                                 _read_all, serve_model)

        prefix = _save(tmp_path, "w8", name="w8_model")
        server = serve_model(prefix, dynamic_batching=True,
                             max_batch_size=4, quant="w8",
                             watchdog_interval=0)
        try:
            body = struct.pack("<B", 1) + _encode_arrays([X])
            with socket.create_connection(("127.0.0.1",
                                           server.port)) as s:
                s.sendall(struct.pack("<I", len(body)) + body)
                (blen,) = struct.unpack("<I", _read_all(s, 4))
                resp = _read_all(s, blen)
            assert resp[0] == 0
            # cmd-5 stats carries the mode for fleet observability
            with socket.create_connection(("127.0.0.1",
                                           server.port)) as s:
                s.sendall(struct.pack("<IB", 1, 5))
                (blen,) = struct.unpack("<I", _read_all(s, 4))
                stats = json.loads(_read_all(s, blen)[1:].decode())
            assert stats["quant"] == "w8"
        finally:
            server.stop()


class TestQuantMetrics:
    def test_exposition_carries_mode_label(self, tmp_path):
        from paddle_tpu.obs import metrics as obs_metrics
        from paddle_tpu.obs import prometheus as obs_prometheus

        layer = jit_load(_save(tmp_path, "w8"))
        eng = BatchingEngine.for_layer(layer, max_batch_size=2,
                                       max_wait_ms=1.0,
                                       watchdog_interval=0,
                                       name="quant-metrics")
        try:
            eng.infer([X[:2]], timeout=60)
            text = obs_prometheus.render(obs_metrics.REGISTRY)
        finally:
            eng.close()
        hits = [l for l in text.splitlines()
                if l.startswith("paddle_serving_compiles_total")
                and 'engine="quant-metrics"' in l]
        assert hits and all('quant="w8"' in l for l in hits)

    @pytest.mark.parametrize("mode,stored", [("bf16w", "parameter:bf16"),
                                             ("w8", "parameter:s8")])
    def test_ledger_events_carry_mode(self, tmp_path, mode, stored):
        from paddle_tpu.obs.ledger import LEDGER

        layer = jit_load(_save(tmp_path, mode))
        LEDGER.reset()
        eng = BatchingEngine.for_layer(layer, max_batch_size=2,
                                       max_wait_ms=1.0,
                                       watchdog_interval=0,
                                       name="quant-ledger")
        try:
            eng.infer([X[:2]], timeout=60)
        finally:
            eng.close()
        evs = LEDGER.events("serving/")
        assert evs and all(e.get("quant") == mode for e in evs)
        # the dtype evidence rides in the typed counts: the reduced-
        # precision weights reached XLA, not an f32 promotion of them
        assert any(stored in e.get("typed_op_counts", {}) for e in evs)
