"""bench.py contract tests: the driver parses EXACTLY ONE json line
from stdout, within its own command timeout. Round 3 was lost to a
bench that blew the budget without printing (rc=124, parsed: null) —
these tests pin the guarantees that prevent a repeat."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(env_extra, timeout, argv=()):
    env = dict(os.environ)
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *argv],
        capture_output=True, text=True, env=env, timeout=timeout,
        cwd=REPO)


def _one_json_line(stdout):
    lines = [l for l in stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"expected exactly one line, got {lines}"
    return json.loads(lines[0])


class TestBenchContract:
    def test_cpu_smoke_emits_one_json_line(self):
        r = _run({"BENCH_CPU": "1", "BENCH_STEPS": "1",
                  "BENCH_WARMUP": "1"}, timeout=420)
        assert r.returncode == 0, r.stderr[-500:]
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == "bert_base_pretrain_tokens_per_sec_per_chip"
        assert rec["value"] > 0 and rec["smoke"] is True
        assert set(rec) >= {"metric", "value", "unit", "vs_baseline"}

    def test_deadline_always_produces_failure_json(self):
        """With no TPU (and no BENCH_CPU=1) the bench prints the one
        failure record at once and exits non-zero — no retry, no CPU
        number, never a silent rc-124."""
        r = _run({"JAX_PLATFORMS": "cpu", "BENCH_DEADLINE": "25"},
                 timeout=90)
        assert r.returncode != 0
        rec = _one_json_line(r.stdout)
        assert rec["value"] == 0.0
        assert rec["error"].startswith("tpu_unavailable")
        assert rec["metric"] == "bert_base_pretrain_tokens_per_sec_per_chip"

    def test_flash_mode_metric_fields(self):
        r = _run({"BENCH_CPU": "1", "BENCH_STEPS": "1",
                  "BENCH_WARMUP": "1", "BENCH_MODEL": "flash"},
                 timeout=420)
        assert r.returncode == 0, r.stderr[-500:]
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == "flash_attention_fwd_bwd_tflops_per_chip"
        assert rec["unit"] == "TFLOP/s"

    def test_llama_mode_metric_fields(self):
        r = _run({"BENCH_CPU": "1", "BENCH_STEPS": "1",
                  "BENCH_WARMUP": "1", "BENCH_MODEL": "llama"},
                 timeout=420)
        assert r.returncode == 0, r.stderr[-500:]
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == "llama_374m_pretrain_tokens_per_sec_per_chip"
        assert rec["unit"] == "tokens/s"
        # vs_baseline doubles as MFU for this config (no published
        # per-chip baseline; see run_llama docstring) — and a CPU smoke
        # run has no device peak to be a fraction of
        assert rec["vs_baseline"] is None and rec["mfu"] is None
        assert rec["smoke"] is True and rec["params_m"] > 0

    @pytest.mark.slow  # subprocess bench run; tier-1 is near its
    @pytest.mark.serving  # timeout cap — ci_gate --serving runs this
    def test_serving_mode_metric_fields(self):
        r = _run({"BENCH_CPU": "1", "BENCH_MODEL": "serving",
                  "BENCH_CLIENTS": "4", "BENCH_SERVING_SECS": "1"},
                 timeout=420)
        assert r.returncode == 0, r.stderr[-500:]
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == "serving_infer_qps_dynamic_batching"
        assert rec["unit"] == "req/s"
        # the serving schema: QPS + latency percentiles + load shedding
        assert set(rec) >= {"qps", "p50_ms", "p99_ms", "shed_count",
                            "baseline_qps", "clients"}
        assert rec["value"] == rec["qps"] > 0
        assert rec["p50_ms"] > 0 and rec["p99_ms"] >= rec["p50_ms"]
        assert rec["shed_count"] >= 0
        # vs_baseline = QPS speedup over the unbatched per-request path
        assert rec["vs_baseline"] == pytest.approx(
            rec["qps"] / rec["baseline_qps"], rel=1e-3)
        assert rec["smoke"] is True

    @pytest.mark.slow  # subprocess bench run
    @pytest.mark.serving
    @pytest.mark.chaos  # ci_gate --serving-chaos runs this
    def test_serving_chaos_mode_metric_fields(self):
        r = _run({"BENCH_CPU": "1", "BENCH_MODEL": "serving",
                  "BENCH_SERVING_CHAOS": "1", "BENCH_CLIENTS": "4",
                  "BENCH_SERVING_SECS": "1"}, timeout=420)
        assert r.returncode == 0, r.stderr[-500:]
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == "serving_goodput_qps_under_chaos"
        assert rec["unit"] == "req/s"
        # the goodput-under-faults schema
        assert set(rec) >= {"healthy_qps", "chaos_qps", "chaos_shed",
                            "scheduler_restarts", "reload_dropped",
                            "reload_cold_compiles",
                            "quarantine_healthy_ratio",
                            "quarantine_recovered"}
        assert rec["value"] == rec["chaos_qps"] > 0
        # self-healing: the injected deaths were observed and recovered
        assert rec["scheduler_restarts"] >= 1
        # the acceptance invariants the chaos e2e pins
        assert rec["reload_dropped"] == 0
        assert rec["reload_cold_compiles"] == 0
        assert rec["quarantine_healthy_ratio"] >= 0.8
        assert rec["quarantine_recovered"] is True
        assert rec["smoke"] is True

    @pytest.mark.slow  # subprocess bench run; ci_gate --perfproxy is
    # the per-PR gate, these pin the contract it relies on
    def test_perfproxy_green_against_committed_baseline(self):
        """The acceptance invariant: `bench.py perfproxy` runs green on
        CPU against the committed baseline, one JSON line, schema
        intact."""
        r = _run({"JAX_PLATFORMS": "cpu"}, timeout=420,
                 argv=("perfproxy",))
        assert r.returncode == 0, r.stderr[-800:]
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == "perfproxy_compile_ledger_check"
        assert rec["unit"] == "ok"
        assert rec["ok"] is True and rec["value"] == 1.0
        assert set(rec) >= {"metric", "value", "unit", "vs_baseline",
                            "checks", "baseline_file", "jax"}
        by_name = {c["check"]: c for c in rec["checks"]}
        # the three gated dimensions: compile counts, FLOPs, op counts
        assert by_name["serving.warmup_compiles"]["ok"]
        assert by_name["serving.post_warmup_compiles"]["baseline"] == 0
        assert by_name["serving.flops"]["measured"] > 0
        assert by_name["train_step.flops"]["measured"] > 0
        assert by_name["train_step.op_counts"]["ok"]
        # the quant ladder (ISSUE 13): every mode gated on exact
        # compile counts, zero post-warmup compiles, and the
        # opcode:dtype mix that proves reduced precision reached XLA
        for mode in ("w8", "w8a8", "bf16w"):
            assert by_name[f"quant.{mode}.warmup_compiles"]["ok"]
            assert by_name[
                f"quant.{mode}.post_warmup_compiles"]["baseline"] == 0
            assert by_name[f"quant.{mode}.dtype_mix"]["ok"]
        # the sharded ladder (ISSUE 15): exact per-mesh compile counts,
        # zero post-warmup compiles, and the opcode contract (chk_ops
        # fails if all-gather/all-reduce vanish — the proof the
        # sharding actually reached the HLO)
        for sec in ("serving", "decode"):
            assert by_name[f"sharded.{sec}.warmup_compiles"]["ok"]
            assert by_name[
                f"sharded.{sec}.post_warmup_compiles"]["baseline"] == 0
            assert by_name[f"sharded.{sec}.op_counts"]["ok"]
        assert by_name["sharded.mesh"]["ok"]

    @pytest.mark.slow  # subprocess bench run
    def test_perfproxy_fails_loudly_on_injected_regression(self):
        """An extra post-warmup compile (or a FLOP delta beyond
        tolerance) must exit non-zero with the failing check named —
        never a silent pass."""
        r = _run({"JAX_PLATFORMS": "cpu",
                  "BENCH_PERFPROXY_INJECT": "extra_compile"},
                 timeout=420, argv=("perfproxy",))
        assert r.returncode != 0
        rec = _one_json_line(r.stdout)
        assert rec["ok"] is False and rec["value"] == 0.0
        assert "post_warmup_compiles" in rec["error"]

        r = _run({"JAX_PLATFORMS": "cpu",
                  "BENCH_PERFPROXY_INJECT": "flops"},
                 timeout=420, argv=("perfproxy",))
        assert r.returncode != 0
        rec = _one_json_line(r.stdout)
        assert rec["ok"] is False
        assert "flops" in rec["error"]

    @pytest.mark.slow  # subprocess bench run
    def test_perfproxy_update_baseline_roundtrip(self, tmp_path):
        """--update-baseline writes a baseline the very next check run
        passes against (the recipe a jax upgrade will follow)."""
        baseline = str(tmp_path / "baseline.json")
        env = {"JAX_PLATFORMS": "cpu",
               "BENCH_PERFPROXY_BASELINE": baseline}
        r = _run(env, timeout=420, argv=("perfproxy",
                                         "--update-baseline"))
        assert r.returncode == 0, r.stderr[-800:]
        payload = json.load(open(baseline))
        assert payload["format"] == 1
        assert payload["serving"]["warmup_compiles"] > 0
        r = _run(env, timeout=420, argv=("perfproxy",))
        assert r.returncode == 0, r.stderr[-800:]
        assert _one_json_line(r.stdout)["ok"] is True
        # ISSUE 13 discipline: regenerating with the quant section must
        # leave the pre-existing sections BYTE-IDENTICAL to the
        # committed baseline (sort_keys-canonical compare) — the quant
        # ladder is additive, never an excuse to re-baseline f32 perf
        committed = json.load(open(os.path.join(REPO,
                                                "PERFPROXY_BASELINE.json")))
        for section in ("serving", "decode", "train_step"):
            assert (json.dumps(payload[section], sort_keys=True)
                    == json.dumps(committed[section], sort_keys=True)), \
                f"{section} section drifted under --update-baseline"
        for mode in ("w8", "w8a8", "bf16w"):
            q = payload["quant"][mode]
            assert q["warmup_compiles"] > 0
            assert q["post_warmup_compiles"] == 0
            marker = "parameter:bf16" if mode == "bf16w" else "parameter:s8"
            assert q["dtype_mix"].get(marker, 0) > 0
        # ISSUE 15: the sharded section regenerates with the same
        # discipline — additive, with the collective ops present (the
        # sharding-reached-the-HLO witness)
        sh = payload["sharded"]
        assert sh["mesh"] == "tp2"
        for sec in ("serving", "decode"):
            assert sh[sec]["warmup_compiles"] > 0
            assert sh[sec]["post_warmup_compiles"] == 0
            assert sh[sec]["op_counts"].get("all-gather", 0) > 0
        # ISSUE 19: the KV-reuse ladder regenerates additively too,
        # with the batched-verify witness intact (one program per
        # verify rung, dot count spec_k x a step's) and the storm
        # adding zero compiles past warmup
        ps = payload["prefix_spec"]
        assert ps["warmup_compiles"] > 0
        assert ps["post_warmup_compiles"] == 0
        assert ps["spec_k"] >= 2
        assert ps["verify_one_program_per_rung"] is True
        assert ps["verify_dot_unroll_ratio"] == ps["spec_k"]
        assert any(n.startswith("verify") for n in ps["programs"])

    @pytest.mark.slow  # subprocess pod launches; ci_gate --elastic
    @pytest.mark.elastic  # runs these as its own stage
    def test_goodput_mode_metric_fields(self):
        """The elastic goodput bench under chaos: one JSON line with
        useful-steps/hour, the goodput ratio, the injected host-kill
        counts echoed, straggler flags, and the exported
        paddle_goodput_seconds_total ledger."""
        r = _run({"JAX_PLATFORMS": "cpu", "BENCH_GOODPUT_PROCS": "3",
                  "BENCH_GOODPUT_STEPS": "12",
                  "BENCH_GOODPUT_STEP_MS": "40"},
                 timeout=420, argv=("goodput",))
        assert r.returncode == 0, r.stderr[-1500:]
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == \
            "training_goodput_steps_per_hour_under_chaos"
        assert rec["unit"] == "steps/h"
        assert set(rec) >= {"goodput_ratio", "healthy_steps_per_hour",
                            "chaos_steps_per_hour", "injected_host_kills",
                            "injected_sigterm", "injected_sigkill",
                            "consensus_saves", "stragglers_flagged",
                            "goodput_seconds_total", "goodput_exported"}
        assert rec["value"] == rec["chaos_steps_per_hour"] > 0
        assert rec["healthy_steps_per_hour"] > 0
        # the goodput ratio is present and is vs_baseline
        assert 0 < rec["goodput_ratio"] <= rec["vs_baseline"] + 1e-9
        # the injected host kills are echoed: one SIGTERM preemption +
        # one SIGKILL host loss, each ending in a consensus save
        assert rec["injected_sigterm"] >= 1
        assert rec["injected_sigkill"] >= 1
        assert rec["injected_host_kills"] == \
            rec["injected_sigterm"] + rec["injected_sigkill"]
        assert rec["consensus_saves"] == rec["injected_host_kills"]
        # the chaos-delayed rank was flagged, and the pod survived it
        assert rec["stragglers_flagged"] == [1]
        # obs.goodput fed the bench and was exported as
        # paddle_goodput_seconds_total
        assert rec["goodput_seconds_total"]["step"] > 0
        assert rec["ledger_steps"] == 12
        assert rec["goodput_exported"] is True
        assert rec["smoke"] is True

    @pytest.mark.slow
    @pytest.mark.elastic
    def test_goodput_chaos_off_ratio_near_one(self):
        """BENCH_GOODPUT_CHAOS=0 is the control: zero injected kills
        and a goodput ratio ~= 1.0 (two identical healthy pods; the
        wide tolerance absorbs shared-box startup noise)."""
        r = _run({"JAX_PLATFORMS": "cpu", "BENCH_GOODPUT_PROCS": "3",
                  "BENCH_GOODPUT_STEPS": "12",
                  "BENCH_GOODPUT_STEP_MS": "40",
                  "BENCH_GOODPUT_CHAOS": "0"},
                 timeout=420, argv=("goodput",))
        assert r.returncode == 0, r.stderr[-1500:]
        rec = _one_json_line(r.stdout)
        assert rec["chaos"] is False
        assert rec["injected_host_kills"] == 0
        assert rec["consensus_saves"] == 0
        assert rec["stragglers_flagged"] == []
        assert 0.4 <= rec["goodput_ratio"] <= 2.5
        assert rec["ledger_steps"] == 12

    def test_decode_roofline_mode_metric_fields(self):
        # the pre-ISSUE-12 `decode` mode, renamed: single-model
        # KV-cached decode throughput vs the HBM roofline
        r = _run({"BENCH_CPU": "1", "BENCH_STEPS": "4",
                  "BENCH_MODEL": "decode-roofline"}, timeout=420)
        assert r.returncode == 0, r.stderr[-500:]
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == "llama_374m_decode_tokens_per_sec_per_chip"
        assert rec["unit"] == "tokens/s"
        # vs_baseline = fraction of the device's HBM-bandwidth roofline:
        # a CPU smoke run reports none (no peaks for an unknown device)
        assert rec["vs_baseline"] is None
        assert rec["roofline_tokens_per_sec"] is None
        assert rec["smoke"] is True


class TestDecodeContract:
    """`bench.py decode` JSON contract (ISSUE 12 acceptance): the
    continuous-batching storm must report tokens/s + p99 inter-token
    latency for BOTH sides, and a fresh replica must warm its decode
    ladder from the artifact store with zero inline compiles (the
    bench itself exits non-zero when that contract breaks)."""

    @pytest.mark.slow  # three decode-replica subprocesses + storms
    @pytest.mark.decode  # ci_gate --decode runs this as its own stage
    def test_decode_mode_metric_fields(self):
        r = _run({"JAX_PLATFORMS": "cpu", "BENCH_DECODE_SECS": "2.0",
                  "BENCH_DECODE_CLIENTS": "8"},
                 timeout=420, argv=("decode",))
        assert r.returncode == 0, r.stderr[-1500:]
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == \
            "serving_decode_tokens_per_sec_continuous_batching"
        assert rec["unit"] == "tokens/s"
        assert rec["tokens_per_sec"] > 0
        assert rec["baseline_tokens_per_sec"] > 0
        assert rec["p99_intertoken_ms"] > 0
        assert rec["baseline_p99_intertoken_ms"] > 0
        # vs_baseline = tokens/s speedup over the one-shot (slots=1)
        # decode of the same storm — the structural win continuous
        # batching exists for (kept loose: shared-box noise)
        assert rec["vs_baseline"] == pytest.approx(
            rec["tokens_per_sec"] / rec["baseline_tokens_per_sec"],
            rel=1e-3)
        assert rec["vs_baseline"] > 1.0
        assert rec["p99_intertoken_ms"] < rec["baseline_p99_intertoken_ms"]
        # zero-cold-start for decode replicas (hard-failed by the
        # bench itself, re-asserted here)
        assert rec["coldstart_inline_compiles"] == 0
        assert rec["coldstart_store_loads"] > 0
        assert rec["streams"] > 0 and rec["baseline_streams"] > 0

    @pytest.mark.slow  # four decode-replica subprocesses + storms
    @pytest.mark.sharded  # ci_gate --sharded runs this as its own stage
    def test_sharded_mode_metric_fields(self):
        """`bench.py sharded` (ISSUE 15 acceptance): the A/B against
        the single-chip replica must report tokens/s + p99 per side
        and the per-mesh weight-bytes proxy, and hard-fail unless (a)
        the sharded replica's wire streams equal its solo decode
        bitwise, (b) its tokens greedily agree with the single-chip
        side, and (c) a fresh sharded replica rewarms its whole
        (bucket, mesh) ladder with zero inline compiles."""
        r = _run({"JAX_PLATFORMS": "cpu", "BENCH_SHARDED_SECS": "2.0",
                  "BENCH_SHARDED_CLIENTS": "6"},
                 timeout=540, argv=("sharded",))
        assert r.returncode == 0, r.stderr[-1500:]
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == \
            "serving_decode_tokens_per_sec_sharded_mesh"
        assert rec["unit"] == "tokens/s"
        assert rec["mesh"] == "tp2" and rec["n_shards"] == 2
        assert rec["tokens_per_sec"] > 0
        assert rec["single_tokens_per_sec"] > 0
        assert rec["p99_intertoken_ms"] > 0
        assert rec["vs_baseline"] == pytest.approx(
            rec["tokens_per_sec"] / rec["single_tokens_per_sec"],
            rel=1e-3)
        # the contracts the bench hard-fails on, re-asserted
        assert rec["bitwise_solo_vs_batch"] is True
        assert rec["tokens_agree_with_single_chip"] is True
        assert rec["coldstart_inline_compiles"] == 0
        assert rec["coldstart_store_loads"] > 0
        # the point of sharding: per-device resident weight bytes
        # shrink by the shard count (the toy model divides evenly)
        assert rec["weight_bytes_per_device"] * rec["n_shards"] \
            == rec["weight_bytes_total"]
        assert rec["weight_bytes_ratio"] == pytest.approx(2.0)
        assert rec["streams"] > 0 and rec["single_streams"] > 0

    @pytest.mark.slow  # eight phase-replica subprocesses + storms
    @pytest.mark.disagg  # ci_gate --disagg runs this as its own stage
    def test_disagg_mode_metric_fields(self):
        """`bench.py disagg` (ISSUE 18 acceptance): the mixed
        long/short-prompt storm A/B colocated vs disaggregated must
        report p99 inter-token latency under prefill bursts for both
        sides, prove the disaggregated side actually handed off, and
        hard-fail (inside the bench) on any non-retryable client
        error, torn stream, or duplicate/lost token across the
        per-pool SIGKILL chaos arm and the pool-at-zero degraded arm.
        The ratio's DIRECTION is not asserted: on the toy CPU model
        the handoff round-trip can outweigh the trivial prefill work
        it offloads — the structural contracts are the acceptance."""
        r = _run({"JAX_PLATFORMS": "cpu", "BENCH_DISAGG_SECS": "2.0",
                  "BENCH_DISAGG_CLIENTS": "6"},
                 timeout=540, argv=("disagg",))
        assert r.returncode == 0, r.stderr[-1500:]
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == \
            "serving_decode_p99_intertoken_ms_under_prefill_bursts"
        assert rec["unit"] == "ms"
        assert rec["value"] == rec["p99_intertoken_ms"] > 0
        assert rec["colocated_p99_intertoken_ms"] > 0
        # vs_baseline = colocated p99 / disaggregated p99 under the
        # same bursts (lower-is-better metric, so >1 = disagg wins)
        assert rec["vs_baseline"] == pytest.approx(
            rec["colocated_p99_intertoken_ms"]
            / rec["p99_intertoken_ms"], rel=1e-3)
        assert rec["prefill_replicas"] == rec["decode_replicas"] == 2
        assert rec["tokens_per_sec"] > 0
        assert rec["colocated_tokens_per_sec"] > 0
        assert rec["streams"] > 0 and rec["colocated_streams"] > 0
        # the bursts actually exercised prefill on BOTH sides
        assert rec["burst_admissions"] > 0
        assert rec["colocated_burst_admissions"] > 0
        # the disaggregated side really disaggregated
        assert rec["handoffs_ok"] > 0
        assert rec["handoffs_failed"] == 0
        # chaos arm: one SIGKILL per pool, zero client-visible damage
        ch = rec["chaos"]
        assert len(ch["killed"]) == 2
        assert ch["killed_decode_inflight"] >= 1
        assert ch["resumes_ok"] >= 1
        assert ch["client_visible_nonretryable"] == 0
        assert ch["duplicate_or_lost_tokens"] == 0
        assert ch["bitwise_ok_vs_solo"] is True
        assert ch["ok_streams"] + ch["retryable_sheds"] \
            == ch["streams"] == 12
        # degraded arm: decode pool at zero stays byte-identical and
        # is counted
        assert rec["degraded"]["degraded_count"] >= 1
        assert rec["degraded"]["bitwise_vs_solo"] is True
        assert rec["smoke"] is True

    @pytest.mark.slow  # nine decode-replica subprocesses + storms
    @pytest.mark.decode
    @pytest.mark.quant  # ci_gate --decode runs 'decode or quant'
    def test_decode_quant_mode_metric_fields(self):
        """`bench.py decode --quant` (ISSUE 13 acceptance): per quant
        mode (w8, bf16w) the bench must prove the bitwise
        solo-vs-batch contract over the wire, report the storm A/B vs
        the f32 continuous side, report the weight-bytes proxy, and
        hard-fail unless a fresh quantized replica re-warms from the
        store with zero inline compiles."""
        r = _run({"JAX_PLATFORMS": "cpu", "BENCH_DECODE_SECS": "1.5",
                  "BENCH_DECODE_CLIENTS": "6"},
                 timeout=540, argv=("decode", "--quant"))
        assert r.returncode == 0, r.stderr[-1500:]
        rec = _one_json_line(r.stdout)
        assert set(rec["quant"]) == {"w8", "bf16w"}
        for mode, q in rec["quant"].items():
            assert q["tokens_per_sec"] > 0
            assert q["p99_intertoken_ms"] > 0
            assert q["bitwise_solo_vs_batch"] is True
            assert q["coldstart_inline_compiles"] == 0
            assert q["coldstart_store_loads"] > 0
            assert q["tokens_vs_f32"] > 0
            assert q["weight_bytes"] < q["weight_bytes_f32"]
        # the bandwidth lever the modes exist for: int8 ~4x on matrix
        # params (minus scales), bf16 exactly 2x
        assert rec["quant"]["w8"]["weight_bytes_ratio"] > 3.0
        assert rec["quant"]["bf16w"]["weight_bytes_ratio"] == 2.0


class TestColdstartContract:
    """`bench.py coldstart` JSON contract (ISSUE 10 acceptance): a
    warm-store fresh-process serve_model reaches its first healthy
    reply with zero inline engine compiles, and a poisoned store
    degrades to inline compiles with bitwise-identical replies."""

    @pytest.mark.slow  # three serve_model subprocesses
    @pytest.mark.artifacts  # ci_gate --artifacts runs this
    def test_coldstart_mode_metric_fields(self):
        r = _run({"BENCH_COLDSTART_TIMEOUT": "120"}, timeout=420,
                 argv=("coldstart",))
        assert r.returncode == 0, r.stdout + r.stderr
        rec = _one_json_line(r.stdout)
        assert rec["metric"] == \
            "serving_coldstart_first_healthy_reply_seconds"
        assert rec["unit"] == "s" and rec["value"] > 0
        phases = rec["phases"]
        assert set(phases) == {"cold", "warm", "quant_cold",
                               "quant_warm", "poisoned"}
        for ph in phases.values():
            for k in ("t_first_healthy_reply_s", "compiles",
                      "store_loads", "store_corrupt"):
                assert k in ph
        # cold: every bucket compiled inline, nothing to load
        assert phases["cold"]["compiles"] > 0
        assert phases["cold"]["store_loads"] == 0
        # warm: the zero-cold-start contract — ZERO engine compiles
        assert phases["warm"]["compiles"] == 0
        assert phases["warm"]["store_loads"] > 0
        assert rec["warm_zero_engine_compiles"] is True
        # poisoned: every artifact quarantined, inline fallback, and
        # the reply still bitwise-identical across all three phases
        assert phases["poisoned"]["store_corrupt"] > 0
        assert phases["poisoned"]["compiles"] > 0
        assert rec["poisoned_degraded_inline"] is True
        assert rec["replies_bitwise_equal"] is True
        assert rec["poisoned_artifacts"] > 0
        # ISSUE 13: the coldstart contract extended to a quantized (w8)
        # replica sharing the same store, with the deployment knob
        # (PADDLE_TPU_SERVING_QUANT=w8) declared end to end. Its cold
        # phase compiled its OWN ladder — the already-published f32
        # artifacts can never satisfy a w8 key — and its warm phase
        # loaded everything with zero inline compiles.
        assert rec["quant_mode"] == "w8"
        assert phases["quant_cold"]["compiles"] > 0
        assert phases["quant_cold"]["store_loads"] == 0
        assert phases["quant_warm"]["compiles"] == 0
        assert phases["quant_warm"]["store_loads"] > 0
        assert rec["quant_warm_zero_engine_compiles"] is True
        assert rec["quant_cold_compiled_own_ladder"] is True
        assert rec["quant_replies_bitwise_equal"] is True
