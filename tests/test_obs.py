"""Unified telemetry (paddle_tpu/obs): metrics registry, Prometheus
text exposition, span tracing, goodput accounting, compile ledger —
plus the resilience runtime's registry-backed counters."""
import json
import re
import threading

import numpy as np
import pytest

from paddle_tpu.obs import goodput, ledger, metrics, prometheus, tracing


# ------------------------------------------------------------ registry

class TestMetricsRegistry:
    def test_counter_inc_and_value(self):
        r = metrics.Registry()
        c = r.counter("t_requests_total", "help")
        c.inc()
        c.inc(4)
        assert c.value() == 5

    def test_counter_labels_are_independent(self):
        r = metrics.Registry()
        c = r.counter("t_shed_total", "", labelnames=("reason",))
        c.inc(reason="queue_full")
        c.inc(2, reason="quarantine")
        assert c.value(reason="queue_full") == 1
        assert c.value(reason="quarantine") == 2

    def test_counter_rejects_negative_and_bad_labels(self):
        r = metrics.Registry()
        c = r.counter("t_total", "", labelnames=("a",))
        with pytest.raises(ValueError):
            c.inc(-1, a="x")
        with pytest.raises(ValueError):
            c.inc(b="x")  # label schema mismatch
        with pytest.raises(ValueError):
            r.counter("0bad name", "")

    def test_get_or_create_dedupes_and_checks_kind(self):
        r = metrics.Registry()
        a = r.counter("t_x_total", "")
        assert r.counter("t_x_total", "different help") is a
        with pytest.raises(ValueError):
            r.gauge("t_x_total", "")  # same name, different kind

    def test_gauge_set_inc_dec(self):
        r = metrics.Registry()
        g = r.gauge("t_depth", "")
        g.set(7)
        g.inc()
        g.dec(3)
        assert g.value() == 5

    def test_histogram_buckets_cumulative(self):
        r = metrics.Registry()
        h = r.histogram("t_lat_seconds", "", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.05, 0.5, 5.0):
            h.observe(v)
        fam = h.collect()
        rows = {(s, d.get("le")): v for s, d, v in fam.samples}
        assert rows[("_bucket", "0.01")] == 1
        assert rows[("_bucket", "0.1")] == 3
        assert rows[("_bucket", "1")] == 4
        assert rows[("_bucket", "+Inf")] == 5
        assert rows[("_count", None)] == 5
        assert rows[("_sum", None)] == pytest.approx(5.605)
        assert h.value() == {"count": 5,
                             "sum": pytest.approx(5.605)}

    def test_log_buckets_shape(self):
        bs = metrics.log_buckets(0.001, 10.0, 4)
        assert bs == (0.001, 0.01, 0.1, 1.0)
        with pytest.raises(ValueError):
            metrics.log_buckets(0, 2, 4)

    def test_collector_runs_outside_registry_lock(self):
        # a collector that itself touches the registry must not
        # deadlock (the engine's collector takes the engine lock and
        # collects instruments; registry lock is NOT held around it)
        r = metrics.Registry()
        c = r.counter("t_seen_total", "")

        def coll():
            c.inc()  # touches a registered metric during collect
            return [metrics.Counter("t_extra_total", "x").collect()]

        r.register_collector(coll)
        fams = r.collect()
        assert any(f.name == "t_extra_total" for f in fams)
        assert c.value() == 1
        r.unregister_collector(coll)
        assert not any(f.name == "t_extra_total"
                       for f in r.collect())

    def test_collector_returning_none_auto_unregisters(self):
        # the weakref-collector contract: a GC'd engine's collector
        # returns None and the registry prunes it on the next collect
        r = metrics.Registry()
        dead = lambda: None  # noqa: E731 - the contract under test
        r.register_collector(dead)
        r.collect()
        assert dead not in r._collectors

    def test_snapshot_is_jsonable(self):
        r = metrics.Registry()
        r.counter("t_a_total", "").inc()
        r.histogram("t_b", "", buckets=(1.0,)).observe(0.5)
        snap = r.snapshot()
        json.dumps(snap)  # must not raise
        assert snap["t_a_total"][0]["value"] == 1

    def test_concurrent_increments_do_not_lose_counts(self):
        r = metrics.Registry()
        c = r.counter("t_par_total", "")

        def worker():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value() == 8000


# ---------------------------------------------------------- exposition

class TestPrometheusExposition:
    def test_help_type_and_sample_lines(self):
        r = metrics.Registry()
        c = r.counter("t_reqs_total", "requests served",
                      labelnames=("code",))
        c.inc(3, code="200")
        text = prometheus.render(r)
        assert "# HELP t_reqs_total requests served\n" in text
        assert "# TYPE t_reqs_total counter\n" in text
        assert 't_reqs_total{code="200"} 3\n' in text

    def test_escaping_help_and_label_values(self):
        r = metrics.Registry()
        c = r.counter("t_esc_total", 'line1\nline2 \\ backslash',
                      labelnames=("p",))
        c.inc(p='va"l\nue\\x')
        text = prometheus.render(r)
        assert "# HELP t_esc_total line1\\nline2 \\\\ backslash" in text
        assert 'p="va\\"l\\nue\\\\x"' in text
        # the exposition itself must stay newline-clean per sample
        for line in text.splitlines():
            assert line.startswith(("#", "t_esc_total"))

    def test_metric_and_label_name_validation(self):
        with pytest.raises(ValueError):
            metrics.Counter("has space", "")
        with pytest.raises(ValueError):
            metrics.Counter("ok_total", "", labelnames=("le",))
        with pytest.raises(ValueError):
            metrics.Counter("ok_total", "", labelnames=("0digit",))
        assert metrics.Counter("a:b_total", "").name == "a:b_total"

    def test_histogram_exposition_format(self):
        r = metrics.Registry()
        h = r.histogram("t_h_seconds", "hist", buckets=(0.5, 2.0))
        h.observe(1.0)
        text = prometheus.render(r)
        assert "# TYPE t_h_seconds histogram" in text
        assert 't_h_seconds_bucket{le="0.5"} 0' in text
        assert 't_h_seconds_bucket{le="2"} 1' in text
        assert 't_h_seconds_bucket{le="+Inf"} 1' in text
        assert "t_h_seconds_sum 1" in text
        assert "t_h_seconds_count 1" in text

    def test_same_name_families_merge_and_sum(self):
        # two engines expose the same family via collectors: one
        # HELP/TYPE header, duplicate label sets summed
        r = metrics.Registry()
        a = metrics.Counter("t_m_total", "h",
                            const_labels={"engine": "e1"})
        b = metrics.Counter("t_m_total", "h",
                            const_labels={"engine": "e1"})
        a.inc(2)
        b.inc(3)
        r.register_collector(lambda: [a.collect(), b.collect()])
        text = prometheus.render(r)
        assert text.count("# TYPE t_m_total counter") == 1
        assert 't_m_total{engine="e1"} 5' in text

    def test_conflicting_kinds_raise(self):
        r = metrics.Registry()
        r.register_collector(
            lambda: [metrics.Counter("t_k", "").collect(),
                     metrics.Gauge("t_k", "").collect()])
        with pytest.raises(ValueError, match="conflicting kinds"):
            prometheus.render(r)

    def test_output_parses_line_shape(self):
        # every non-comment line: name{labels}? value
        r = metrics.Registry()
        r.counter("t_shape_total", "x", labelnames=("a",)).inc(a="1")
        r.histogram("t_shape_s", "y").observe(0.2)
        line_re = re.compile(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+$")
        for line in prometheus.render(r).splitlines():
            if not line.startswith("#"):
                assert line_re.match(line), line


# ------------------------------------------------------------- tracing

class TestTracing:
    def test_span_records_duration_and_attrs(self):
        tid = tracing.new_trace_id()
        with tracing.span("t.span", trace_id=tid, rows=4):
            pass
        (sp,) = tracing.finished(trace_id=tid)
        assert sp["name"] == "t.span"
        assert sp["attrs"]["rows"] == 4
        assert sp["duration_s"] >= 0

    def test_ambient_trace_id_inherited_and_restored(self):
        tid = tracing.new_trace_id()
        assert tracing.current_trace_id() is None
        with tracing.trace(tid):
            assert tracing.current_trace_id() == tid
            with tracing.span("t.ambient"):
                pass
        assert tracing.current_trace_id() is None
        assert tracing.finished(trace_id=tid, name="t.ambient")

    def test_explicit_id_wins_over_ambient(self):
        amb, exp = tracing.new_trace_id(), tracing.new_trace_id()
        with tracing.trace(amb):
            with tracing.span("t.explicit", trace_id=exp):
                pass
        assert tracing.finished(trace_id=exp, name="t.explicit")
        assert not tracing.finished(trace_id=amb, name="t.explicit")

    def test_cross_thread_finish(self):
        tid = tracing.new_trace_id()
        sp = tracing.start_span("t.crossthread", trace_id=tid)
        t = threading.Thread(target=sp.finish)
        t.start()
        t.join()
        assert tracing.finished(trace_id=tid, name="t.crossthread")

    def test_record_span_and_summary_share_table(self):
        tracing.reset_summary()
        tracing.record_span("t.pre", 0.25)
        with tracing.span("t.pre"):
            pass
        rows = {r["name"]: r for r in tracing.summary_rows()}
        assert rows["t.pre"]["calls"] == 2
        assert rows["t.pre"]["max"] >= 0.25

    def test_trace_id_format(self):
        tid = tracing.new_trace_id()
        assert tid != 0
        assert re.fullmatch(r"[0-9a-f]{16}",
                            tracing.format_trace_id(tid))

    def test_profiler_recordevent_routes_through_span_layer(self):
        # the satellite: RecordEvent and serving spans share one table
        from paddle_tpu.utils import profiler

        profiler.reset_summary()
        with profiler.RecordEvent("t.legacy_span"):
            pass
        tracing.record_span("t.serving_like", 0.01)
        rows = profiler.summary(printer=None)
        names = {r["name"] for r in rows}
        assert {"t.legacy_span", "t.serving_like"} <= names
        # and a RecordEvent inside a trace inherits the trace id
        tid = tracing.new_trace_id()
        with tracing.trace(tid):
            with profiler.RecordEvent("t.traced_legacy"):
                pass
        assert tracing.finished(trace_id=tid, name="t.traced_legacy")


# ------------------------------------------------------------- goodput

class TestGoodput:
    def test_report_math(self):
        acct = goodput.GoodputAccountant(export=False)
        acct.account("step", 3.0)
        acct.account("checkpoint", 1.0)
        rep = acct.report()
        assert rep["step_s"] == 3.0
        assert rep["checkpoint_s"] == 1.0
        assert rep["steps"] == 1
        assert rep["total_s"] >= 4.0
        assert 0 < rep["goodput"] <= 0.75

    def test_context_managers(self):
        acct = goodput.GoodputAccountant(export=False)
        with acct.step():
            pass
        with acct.retry():
            pass
        rep = acct.report()
        assert rep["steps"] == 1
        assert rep["retry_s"] >= 0

    def test_unknown_category_raises(self):
        with pytest.raises(ValueError):
            goodput.GoodputAccountant(export=False).account("nap", 1)

    def test_quiet_accountant_reports_zero(self):
        assert goodput.GoodputAccountant(export=False).report()[
            "goodput"] == 0.0

    def test_default_accountant_exports_to_registry(self):
        before = goodput._SECONDS.value(category="checkpoint")
        goodput.account("checkpoint", 2.0)
        assert goodput._SECONDS.value(
            category="checkpoint") == pytest.approx(before + 2.0)


# -------------------------------------------------------------- ledger

_HLO_SAMPLE = """\
HloModule jit_f, entry_computation_layout={()->f32[4]}

%fused_computation (param_0: f32[4], param_1: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %param_1 = f32[4]{0} parameter(1)
  ROOT %add.1 = f32[4]{0} add(f32[4]{0} %param_0, f32[4]{0} %param_1)
}

ENTRY %main (a: f32[4], b: f32[4]) -> (f32[4], f32[]) {
  %a = f32[4]{0} parameter(0)
  %b = f32[4]{0} parameter(1)
  %fusion = f32[4]{0} fusion(f32[4]{0} %a, f32[4]{0} %b), kind=kLoop
  %pair = (f32[4]{0}, f32[]) tuple(%fusion, f32[] constant(0))
  ROOT %out = f32[4]{0} get-tuple-element(%pair), index=0
}
"""


class TestCompileLedger:
    def test_hlo_opcode_parse_handles_tuple_types(self):
        ops = ledger.hlo_opcodes(_HLO_SAMPLE)
        # the tuple-typed %pair line must parse as 'tuple', not as part
        # of its type; computation headers must not count
        assert ops.count("parameter") == 4
        assert ops.count("add") == 1
        assert ops.count("fusion") == 1
        assert ops.count("tuple") == 1
        assert ops.count("get-tuple-element") == 1

    def test_fingerprint_is_structural(self):
        ops = ledger.hlo_opcodes(_HLO_SAMPLE)
        assert ledger.hlo_fingerprint(ops) == ledger.hlo_fingerprint(
            list(ops))
        assert ledger.hlo_fingerprint(ops) != ledger.hlo_fingerprint(
            ops + ["dot"])

    def test_record_and_totals_with_real_compile(self):
        import jax
        import jax.numpy as jnp

        led = ledger.CompileLedger()
        f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
        compiled = f.lower(
            jax.ShapeDtypeStruct((8, 8), np.float32)).compile()
        ev = led.record("t/unit", duration_s=0.5, compiled=compiled)
        assert ev["flops"] > 0
        assert ev["op_counts"].get("dot", 0) >= 1
        assert re.fullmatch(r"[0-9a-f]{16}", ev["fingerprint"])
        tot = led.totals()
        assert tot["compiles"] == 1
        assert tot["flops"] == ev["flops"]
        assert tot["n_ops"] == ev["n_ops"]

    def test_key_prefix_filter_and_reset(self):
        led = ledger.CompileLedger()
        led.record("a/one", kind="aot")
        led.record("b/two", kind="aot")
        assert led.totals("a/")["compiles"] == 1
        assert led.totals()["compiles"] == 2
        led.reset()
        assert led.totals()["compiles"] == 0

    def test_bounded_event_list(self):
        led = ledger.CompileLedger(cap=4)
        for i in range(10):
            led.record(f"k{i}")
        evs = led.events()
        assert len(evs) == 4
        assert evs[-1]["key"] == "k9"

    def test_analyze_tolerates_opaque_compiled(self):
        class Opaque:
            def cost_analysis(self):
                raise RuntimeError("backend says no")

            def as_text(self):
                raise RuntimeError("no text either")

        assert ledger.analyze_compiled(Opaque()) == {}


# -------------------------------------------- resilience registry hooks

class TestResilienceTelemetry:
    def test_checkpoint_save_load_counts_and_goodput(self, tmp_path):
        from paddle_tpu.resilience.checkpoint import (CheckpointManager,
                                                      _SAVE_SECONDS,
                                                      _SAVES)

        saves0 = _SAVES.value()
        hist0 = _SAVE_SECONDS.value()["count"]
        ckpt0 = goodput._SECONDS.value(category="checkpoint")
        mgr = CheckpointManager(str(tmp_path / "ck"), keep=2)
        mgr.save({"w": np.arange(4, dtype=np.float32)}, step=1)
        state, step = mgr.load()
        assert step == 1
        assert _SAVES.value() == saves0 + 1
        assert _SAVE_SECONDS.value()["count"] == hist0 + 1
        assert goodput._SECONDS.value(category="checkpoint") > ckpt0
        assert tracing.finished(name="checkpoint.save")
        # the only record of a resume's seconds (ROADMAP Reach 8b reads it)
        (loaded,) = tracing.finished(name="checkpoint.load")[-1:]
        assert loaded["attrs"] == {"step": 1} and loaded["duration_s"] > 0

    def test_retry_sleeps_counted(self):
        from paddle_tpu.resilience.retry import _RETRIES, call_with_retry

        n0 = _RETRIES.value()
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionError("transient")
            return "ok"

        assert call_with_retry(flaky, base_delay=0.0,
                               sleep=lambda s: None) == "ok"
        assert _RETRIES.value() == n0 + 2

    def test_badstep_rollback_counted(self):
        from paddle_tpu.resilience.badstep import (_ROLLBACKS,
                                                   BadStepMonitor,
                                                   ROLLBACK, SKIP)

        r0 = _ROLLBACKS.value()
        mon = BadStepMonitor(threshold=2)
        assert mon.record(True) == SKIP
        assert mon.record(True) == ROLLBACK
        assert _ROLLBACKS.value() == r0 + 1

    def test_preemption_marker_counted(self, tmp_path):
        from paddle_tpu.resilience.preemption import (_PREEMPTION_SAVES,
                                                      write_resume_marker)

        n0 = _PREEMPTION_SAVES.value()
        write_resume_marker(str(tmp_path), step=7)
        assert _PREEMPTION_SAVES.value() == n0 + 1
