"""KV-cache reuse ladder (PR 19): content-addressed prefix caching +
speculative decoding.

The load-bearing contracts, in order of how expensive they are to get
wrong:

- BITWISE equality everywhere. A prefix-cache hit must emit exactly
  the tokens the same prompt emits cold (engine and wire level, per
  quant mode, per mesh), and speculative greedy must emit exactly the
  tokens plain greedy emits — cache/speculation are latency ladders,
  never sampling changes.
- Copy-on-write isolation: two sequences sharing prefix pages then
  diverging can never see each other's writes.
- Skew refusal: a persistent-tier prefix block published by a foreign
  model (different weights) is refused, never installed.
- Lifecycle: shared pages survive slot release / watchdog restart
  without double-frees, and everything drains to a zero restrace
  census at close.
"""
import socket
import struct
import threading
import time

import numpy as np
import pytest

from paddle_tpu.inference import batching, wire_spec as ws
from paddle_tpu.inference.decode import DecodeEngine, _KVSlots
from paddle_tpu.inference.prefix_cache import (PrefixCache, feature_seed,
                                               prefix_hashes)
from paddle_tpu.inference.server import (PredictorServer, STATUS_STREAM,
                                         _decode_arrays, _encode_arrays,
                                         _read_all)
from paddle_tpu.obs import prometheus as obs_prometheus
from paddle_tpu.resilience import chaos

from decode_worker import reference_decode, toy_decode_model

pytestmark = pytest.mark.prefix

HID, VOCAB = 16, 32
PAGE = 8  # min_seq_bucket == page_len
# a two-page shared prefix: the system-prompt stand-in
PREFIX = np.arange(1, 17, dtype=np.int32)
SUFFIXES = [np.array([21, 22], np.int32),
            np.array([23, 24, 25], np.int32),
            np.array([26], np.int32)]


def prompt_with(suffix):
    return np.concatenate([PREFIX, np.asarray(suffix, np.int32)])


@pytest.fixture(scope="module")
def model():
    return toy_decode_model(hidden=HID, vocab=VOCAB, seed=0)


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for k in ("PADDLE_TPU_PREFIX_DIR", "PADDLE_TPU_PREFIX_DISABLE",
              "PADDLE_TPU_PREFIX_MAX_BYTES", "PADDLE_TPU_SPEC_K",
              "PADDLE_TPU_SERVING_QUANT", "PADDLE_TPU_SERVING_MESH"):
        monkeypatch.delenv(k, raising=False)
    chaos.reset()
    yield
    chaos.reset()


@pytest.fixture()
def traced_resources():
    from paddle_tpu.analysis import restrace

    was = restrace.enabled()
    restrace.enable(raise_on_leak=False)
    restrace.reset()
    yield restrace
    restrace.reset()
    if not was:
        restrace.disable()


def make_engine(model, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("min_seq_bucket", PAGE)
    kw.setdefault("watchdog_interval", 0)
    kw.setdefault("name", "prefix-test")
    return DecodeEngine(model, **kw)


def spec_model(anchor=4.0):
    """Target + draft pair biased by a shared token-transition anchor
    so draft proposals land (> 0.5 acceptance) while the models stay
    genuinely different (hidden 16 vs 8, different seeds)."""
    draft = toy_decode_model(hidden=8, vocab=VOCAB, seed=1,
                             anchor=anchor)
    return toy_decode_model(hidden=HID, vocab=VOCAB, seed=0,
                            anchor=anchor, draft=draft)


# ------------------------------------------------------- engine level


class TestPrefixEngine:
    @pytest.mark.parametrize("quant,mesh", [
        (None, None), ("w8", None), ("bf16w", None), (None, "tp2")])
    def test_hit_vs_cold_bitwise(self, quant, mesh):
        """A prefix-cache hit emits exactly the cold tokens — per
        quant mode and per mesh, because the cached KV rows and the
        programs that consume them are mode-specific."""
        base = toy_decode_model(hidden=HID, vocab=VOCAB, seed=0)
        with make_engine(base, quant=quant, mesh=mesh) as hot, \
                make_engine(toy_decode_model(hidden=HID, vocab=VOCAB,
                                             seed=0),
                            quant=quant, mesh=mesh, prefix=False) as cold:
            for sfx in SUFFIXES:
                p = prompt_with(sfx)
                a = hot.generate(p, max_new_tokens=6, timeout=60)
                b = cold.generate(p, max_new_tokens=6, timeout=60)
                assert a.tolist() == b.tolist(), \
                    f"hit != cold under quant={quant} mesh={mesh}"
            st = hot.stats()
            assert st["prefix"]["hits"] >= len(SUFFIXES) - 1
            assert st["prefix"]["misses"] >= 1
            assert cold.stats()["prefix"] is None

    def test_cow_page_isolation_unit(self, model, traced_resources):
        """Two slots sharing pages then diverging: the write path
        clones (copy-on-write), the reader's bytes never move, and
        every page drains through exactly-once decrements."""
        slots = _KVSlots(2, 32, model.kv_spec, min_bucket=PAGE)
        kv = [np.random.RandomState(7).standard_normal(
            (16,) + tr).astype(dt) for tr, dt in model.kv_spec]
        pages = slots.pages_from_arrays(kv, 16)
        s1, s2 = slots.alloc(), slots.alloc()
        slots.install_shared(s1, pages)
        slots.install_shared(s2, pages)
        assert slots.shared_pages() == len(pages)
        # diverge: write into s2 mid-prefix — lands in a CLONE
        entry = [np.full(tr, 9.0, dt) for tr, dt in model.kv_spec]
        slots.write_entry(s2, 3, entry)
        for got, want in zip(slots.snapshot(s1, 16), kv):
            assert np.array_equal(got, want), "COW leaked into reader"
        snap2 = slots.snapshot(s2, 16)
        for got, want, e in zip(snap2, kv, entry):
            assert np.array_equal(got[3], e)
            assert np.array_equal(got[4:], want[4:])
        # exactly-once teardown: releases decrement, cache drop frees
        slots.release(s1)
        slots.release(s2)
        for pid in pages:
            slots.drop_page(pid)
        assert slots.live_pages() == 0
        assert traced_resources.census()["kv_page"] == 0
        assert traced_resources.violations() == []

    def test_concurrent_shared_prefix_bitwise(self, model):
        """Sequences sharing prefix pages inside one continuous batch
        each emit their solo tokens — COW isolation end-to-end."""
        with make_engine(model) as eng:
            eng.generate(prompt_with(SUFFIXES[0]), max_new_tokens=2,
                         timeout=60)  # seed the cache
            reqs = [eng.submit(prompt_with(sfx), max_new_tokens=6 + i)
                    for i, sfx in enumerate(SUFFIXES)]
            outs = [r.result(timeout=60) for r in reqs]
            for i, (sfx, out) in enumerate(zip(SUFFIXES, outs)):
                ref = reference_decode(model, prompt_with(sfx), 6 + i,
                                       max_seq_len=32)
                assert out.tolist() == ref.tolist()
            assert eng.stats()["prefix"]["hits"] >= len(SUFFIXES)

    def test_eviction_under_pressure(self, model):
        """A page budget forces LRU eviction; a cache under pressure
        still never changes tokens."""
        page_bytes = _KVSlots(1, 32, model.kv_spec,
                              min_bucket=PAGE).page_bytes()
        with make_engine(model, prefix_max_bytes=3 * page_bytes) as eng, \
                make_engine(model, prefix=False, name="evict-ref") as ref:
            rng = np.random.RandomState(3)
            for _ in range(4):
                p = rng.randint(1, VOCAB, size=17).astype(np.int32)
                a = eng.generate(p, max_new_tokens=4, timeout=60)
                b = ref.generate(p, max_new_tokens=4, timeout=60)
                assert a.tolist() == b.tolist()
            st = eng.stats()["prefix"]
            assert st["evictions"] >= 1
            assert st["pages"] <= st["max_pages"]

    def test_foreign_model_store_artifact_refused(self, tmp_path):
        """A persistent-tier block hand-planted under another model's
        key is refused on header identity — wrong-weights KV must
        never install (the PR 17 skew discipline, applied to the
        prefix tier)."""
        model_a = toy_decode_model(hidden=HID, vocab=VOCAB, seed=0)
        model_b = toy_decode_model(hidden=HID, vocab=VOCAB, seed=5)
        p = prompt_with(SUFFIXES[0])
        hx = prefix_hashes(p, PAGE, feature_seed(()))[-1][1]
        with make_engine(model_a, prefix_dir=str(tmp_path / "a"),
                         name="pfx-a") as ea:
            ea.generate(p, max_new_tokens=2, timeout=60)
            ident_a = ea._prefix._identity()
            blob = ea._prefix._store.get(
                ea._prefix._store_key(hx, 16, ident_a))
            assert blob is not None, "publisher never shipped"
        with make_engine(model_b, prefix_dir=str(tmp_path / "b"),
                         name="pfx-b") as eb:
            ident_b = eb._prefix._identity()
            assert ident_b["weights"] != ident_a["weights"]
            # plant A's payload under B's key: only the header check
            # stands between B and foreign KV
            assert eb._prefix._store.put(
                eb._prefix._store_key(hx, 16, ident_b), blob)
            out = eb.generate(p, max_new_tokens=4, timeout=60)
            ref = reference_decode(model_b, p, 4, max_seq_len=32)
            assert out.tolist() == ref.tolist()
            st = eb.stats()["prefix"]
            assert st["store_refused"] >= 1
            assert st["store_hits"] == 0
            assert eb.stats()["prefills"] >= 1  # decoded cold

    def test_fresh_replica_inherits_warm_prefix(self, model, tmp_path):
        """A fresh replica sharing PADDLE_TPU_PREFIX_DIR decodes a
        page-aligned cached prompt with ZERO prefill programs — the
        store hit installs the pages and only the finishing step
        runs."""
        d = str(tmp_path / "prefixes")
        p = PREFIX  # exactly 2 pages: the whole prompt is cacheable
        with make_engine(model, prefix_dir=d, name="warm-a") as ea:
            ref = ea.generate(p, max_new_tokens=5, timeout=60)
            assert ea._prefix.stats()["persistent"]
        with make_engine(model, prefix_dir=d, name="warm-b") as eb:
            out = eb.generate(p, max_new_tokens=5, timeout=60)
            assert out.tolist() == ref.tolist()
            st = eb.stats()
            assert st["prefills"] == 0, st["programs"]
            assert not any(k.startswith("prefill")
                           for k in st["programs"])
            assert st["prefix"]["store_hits"] >= 1
            assert st["prefix_fill_steps"] >= 1  # the finishing step

    def test_fresh_replica_on_both_stores_compiles_nothing(
            self, tmp_path):
        """Warm-prefix inheritance composed with the artifact store: a
        fresh replica sharing both serves a cached prefix with zero
        prefill programs AND zero inline compiles."""
        from paddle_tpu.serialize.artifact_store import ArtifactStore

        store = ArtifactStore(str(tmp_path / "store"))
        d = str(tmp_path / "prefixes")
        refs = {}
        for name in ("pub", "fresh"):
            m = toy_decode_model(hidden=HID, vocab=VOCAB, seed=0)
            with make_engine(m, prefix_dir=d, store=store,
                             name=f"both-{name}") as eng:
                eng.warmup()
                refs[name] = eng.generate(PREFIX, max_new_tokens=5,
                                          timeout=60).tolist()
                st = eng.stats()
        assert refs["fresh"] == refs["pub"]
        assert st["prefills"] == 0 and st["compiles"] == 0, st["programs"]
        assert st["store_loads"] > 0
        assert st["prefix"]["store_hits"] >= 1

    def test_restart_sweep_never_double_frees_shared_pages(
            self, model, traced_resources):
        """A watchdog restart's slot sweep DECREMENTS shared pages
        (the cache still holds them) — the PR 12 double-free audit
        extended to refcounted sharing. Close then drains the cache:
        zero census."""
        with make_engine(model, watchdog_interval=0.05) as eng:
            eng.generate(prompt_with(SUFFIXES[0]), max_new_tokens=2,
                         timeout=60)  # cache now shares these pages
            with chaos.fault("serving.decode.loop",
                             exc=RuntimeError("sched-death"),
                             at=chaos.visits("serving.decode.loop") + 1):
                req = eng.submit(prompt_with(SUFFIXES[1]),
                                 max_new_tokens=30)
                with pytest.raises(batching.RetryableError):
                    req.result(timeout=30)
            out = eng.generate(prompt_with(SUFFIXES[2]),
                               max_new_tokens=4, timeout=60)
            ref = reference_decode(model, prompt_with(SUFFIXES[2]), 4,
                                   max_seq_len=32)
            assert out.tolist() == ref.tolist()
            assert eng.stats()["scheduler_restarts"] >= 1
            assert traced_resources.census()["kv_slot"] == 0
            assert traced_resources.violations() == []
        assert traced_resources.census()["kv_page"] == 0
        assert traced_resources.census()["prefix_entry"] == 0


# -------------------------------------------------------- speculative


class TestSpeculative:
    def test_spec_vs_plain_bitwise(self):
        """Speculative greedy == plain greedy, token for token, on
        the SAME engine — the opt-in changes latency, never output."""
        with make_engine(spec_model(), spec_k=4) as eng:
            assert eng.spec_enabled
            for i, sfx in enumerate(SUFFIXES):
                p = prompt_with(sfx)
                spec = eng.generate(p, max_new_tokens=8 + i,
                                    speculative=True, timeout=60)
                plain = eng.generate(p, max_new_tokens=8 + i,
                                     timeout=60)
                assert spec.tolist() == plain.tolist()
            st = eng.stats()["spec"]
            assert st["iterations"] >= 1 and st["verify_steps"] >= 1
            assert st["accepted"] >= 1, "anchored draft never accepted"

    def test_verify_rung_is_one_batched_program(self):
        """The compile ledger's witness of batched verify: warmup
        compiles each verify rung exactly once, its dot count is
        spec_k x a step's (k positions fused into one dispatch, not k
        dispatches), and mixed speculative / plain / shared-prefix
        traffic inside the warmed ladder compiles nothing more."""
        from paddle_tpu.obs.ledger import LEDGER

        spec_k = 4
        LEDGER.reset()
        with make_engine(spec_model(), spec_k=spec_k,
                         max_prompt_len=PAGE) as eng:
            eng.warmup()
            warm = eng.stats()["compiles"]
            dots = {"verify": set(), "step": set()}
            rungs = {}
            for ev in LEDGER.events("decode/"):
                name = ev["key"].split("/", 1)[1]
                for phase in dots:
                    if name.startswith(phase):
                        dots[phase].add(ev["op_counts"].get("dot", 0))
                if name.startswith("verify"):
                    rungs[name] = rungs.get(name, 0) + 1
            assert rungs and set(rungs.values()) == {1}, rungs
            # target and draft toys share the per-position op
            # structure, so the unroll ratio is exact
            (verify_dots,), (step_dots,) = dots["verify"], dots["step"]
            assert verify_dots == spec_k * step_dots
            shared = PREFIX[:PAGE]
            eng.generate(shared, max_new_tokens=2, timeout=60)
            reqs = [eng.submit(shared, max_new_tokens=12,
                               speculative=True),
                    eng.submit(shared, max_new_tokens=6),
                    eng.submit(np.array([4, 5], np.int32),
                               max_new_tokens=4),
                    eng.submit(shared, max_new_tokens=9,
                               speculative=True)]
            for r in reqs:
                r.result(timeout=60)
            st = eng.stats()
            assert st["compiles"] == warm
            assert st["prefix"]["hits"] >= 1
            assert st["spec"]["iterations"] >= 1

    def test_spec_disabled_without_draft_or_k(self, model):
        """No draft companion or k < 2 -> speculation quietly off;
        opted requests just decode plainly."""
        with make_engine(model, spec_k=4) as eng:
            assert not eng.spec_enabled
            p = prompt_with(SUFFIXES[0])
            out = eng.generate(p, max_new_tokens=4, speculative=True,
                               timeout=60)
            ref = reference_decode(model, p, 4, max_seq_len=32)
            assert out.tolist() == ref.tolist()
            assert eng.stats()["spec"]["iterations"] == 0

    def test_goodput_counts_accepted_tokens_once(self):
        """A verify burst that accepts several tokens moves the token
        counter by exactly the emitted count — no double counting."""
        with make_engine(spec_model(), spec_k=4) as eng:
            before = eng.stats()["tokens"]
            out = eng.generate(prompt_with(SUFFIXES[0]),
                               max_new_tokens=10, speculative=True,
                               timeout=60)
            assert eng.stats()["tokens"] - before == out.size == 10

    def test_quantized_spec_bitwise(self):
        """The draft follows the target's quant mode; spec-vs-plain
        bitwise equality holds under w8 serving too."""
        with make_engine(spec_model(), spec_k=4, quant="w8") as eng:
            assert eng.spec_enabled
            p = prompt_with(SUFFIXES[1])
            spec = eng.generate(p, max_new_tokens=8, speculative=True,
                                timeout=60)
            plain = eng.generate(p, max_new_tokens=8, timeout=60)
            assert spec.tolist() == plain.tolist()


# ------------------------------------------------------- observability


class TestObservability:
    def test_metrics_health_and_exposition(self):
        with make_engine(spec_model(), spec_k=4) as eng:
            eng.generate(prompt_with(SUFFIXES[0]), max_new_tokens=4,
                         timeout=60)
            eng.generate(prompt_with(SUFFIXES[1]), max_new_tokens=6,
                         speculative=True, timeout=60)
            h = eng.health()
            assert h["spec_enabled"] is True
            assert h["prefix_entries"] >= 1
            st = eng.stats()
            assert st["prefix"]["hits"] + st["prefix"]["misses"] >= 2
            assert st["shared_pages"] >= 1  # cache-held prefix pages
            text = obs_prometheus.render()
            for fam in ("paddle_prefix_hits_total",
                        "paddle_prefix_misses_total",
                        "paddle_prefix_evictions_total",
                        "paddle_decode_shared_pages",
                        "paddle_decode_live_pages",
                        "paddle_spec_accept_ratio"):
                assert fam in text, f"{fam} missing from /metrics"


# --------------------------------------------------------- wire level


def decode_frame(prompt, max_new, speculative=False):
    body = (struct.pack("<B", 1) + _encode_arrays([prompt])
            + ws.encode_decode_opts(max_new, speculative=speculative))
    return struct.pack("<I", len(body)) + body


def raw_stream(port, frame):
    """-> (terminal_status, tokens, raw reply bytes)."""
    chunks, raw = [], b""
    with socket.create_connection(("127.0.0.1", port)) as s:
        s.sendall(frame)
        while True:
            hdr = _read_all(s, 4)
            (blen,) = struct.unpack("<I", hdr)
            resp = _read_all(s, blen)
            raw += hdr + resp
            if len(resp) > 1 and resp[0] in (0, STATUS_STREAM):
                arrs = _decode_arrays(resp[1:])
                if arrs and arrs[0].size:
                    chunks.append(arrs[0])
            if resp[0] != STATUS_STREAM:
                toks = (np.concatenate(chunks) if chunks
                        else np.array([], np.int32))
                return resp[0], toks, raw


def make_server(model, **eng_kw):
    eng_kw.setdefault("max_slots", 4)
    eng_kw.setdefault("max_seq_len", 32)
    eng_kw.setdefault("min_seq_bucket", PAGE)
    eng_kw.setdefault("watchdog_interval", 0)
    eng_kw.setdefault("name", "prefix-wire")
    engine = DecodeEngine(model, **eng_kw)
    server = PredictorServer(lambda *a: list(a), decode_engine=engine,
                             own_decode_engine=True)
    return server, engine


class TestWire:
    def test_opt_in_bit_and_field_compat(self):
        """Bit 61 is the ONLY moving part of the 0x5C field: omitting
        speculative encodes byte-identically to speculative=False, and
        opting in flips exactly DECODE_SPEC_BIT."""
        plain = ws.encode_decode_opts(8)
        assert plain == ws.encode_decode_opts(8, speculative=False)
        opted = ws.encode_decode_opts(8, speculative=True)
        (a,) = struct.unpack("<Q", plain[-8:])
        (b,) = struct.unpack("<Q", opted[-8:])
        assert b ^ a == ws.DECODE_SPEC_BIT
        assert plain[:-8] == opted[:-8]

    def test_non_opted_stream_byte_identical(self, model):
        """A non-opted client's reply BYTES are identical whether the
        replica runs the full reuse ladder or none of it."""
        ladder_srv, _ = make_server(spec_model(), spec_k=4)
        plain_srv, _ = make_server(
            toy_decode_model(hidden=HID, vocab=VOCAB, seed=0,
                             anchor=4.0),
            prefix=False, name="plain-wire")
        try:
            frame = decode_frame(prompt_with(SUFFIXES[0]), 8)
            st_a, toks_a, raw_a = raw_stream(ladder_srv.port, frame)
            st_b, toks_b, raw_b = raw_stream(plain_srv.port, frame)
            assert (st_a, st_b) == (0, 0)
            assert toks_a.tolist() == toks_b.tolist()
            assert raw_a == raw_b, "non-opted byte stream changed"
        finally:
            ladder_srv.stop()
            plain_srv.stop()

    def test_prefix_hit_bitwise_over_wire(self, model):
        server, engine = make_server(model)
        try:
            p_cold = prompt_with(SUFFIXES[0])
            p_hit = prompt_with(SUFFIXES[1])
            st, toks, _ = raw_stream(server.port,
                                     decode_frame(p_cold, 6))
            assert st == 0
            st, toks, _ = raw_stream(server.port, decode_frame(p_hit, 6))
            assert st == 0
            ref = reference_decode(model, p_hit, 6, max_seq_len=32)
            assert toks.tolist() == ref.tolist()
            assert engine.stats()["prefix"]["hits"] >= 1
        finally:
            server.stop()

    def test_spec_opt_in_bitwise_over_wire(self):
        server, engine = make_server(spec_model(), spec_k=4)
        try:
            p = prompt_with(SUFFIXES[0])
            st_s, spec, _ = raw_stream(server.port,
                                       decode_frame(p, 8, True))
            st_p, plain, _ = raw_stream(server.port, decode_frame(p, 8))
            assert (st_s, st_p) == (0, 0)
            assert spec.tolist() == plain.tolist()
            assert engine.stats()["spec"]["iterations"] >= 1
        finally:
            server.stop()

    def test_solo_vs_batch_contract_with_sharing_and_spec(self):
        """The PR 12 determinism contract over the real wire with the
        whole ladder live: staggered joins/leaves, shared prefixes,
        mixed opted/non-opted traffic, i32/i64 prompts, lengths that
        cross seq buckets — every stream bitwise equals its solo
        reference."""
        target = spec_model()
        server, engine = make_server(target, spec_k=4, max_slots=4)
        jobs = [
            (prompt_with(SUFFIXES[0]), 4, False, np.int32),
            (prompt_with(SUFFIXES[1]), 12, True, np.int32),  # crosses
            (prompt_with(SUFFIXES[2]), 9, True, np.int64),
            (np.array([9, 8, 7], np.int32), 6, False, np.int32),
            (prompt_with(SUFFIXES[0]), 11, True, np.int32),
        ]
        results = [None] * len(jobs)

        def run(i, prompt, n, spec, dt):
            time.sleep(0.02 * i)  # staggered joins
            results[i] = raw_stream(
                server.port, decode_frame(prompt.astype(dt), n, spec))

        try:
            threads = [threading.Thread(target=run, args=(i, *j))
                       for i, j in enumerate(jobs)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            for (prompt, n, _, dt), res in zip(jobs, results):
                assert res is not None, "stream never finished"
                st, toks, _ = res
                assert st == 0
                ref = reference_decode(target, prompt, n,
                                       max_seq_len=32)
                assert toks.tolist() == ref.tolist()
                assert toks.dtype == dt
            assert engine.stats()["prefix"]["hits"] >= 1
        finally:
            server.stop()
