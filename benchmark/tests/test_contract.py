"""The contract line's keys, and BENCHMARK.json against the rules a driver
refuses it by (the ones that can be checked without a chip)."""
import json
import os
import re

import pytest

from benchmark.harness import cells, contract

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


def test_result_line_has_exactly_the_contract_keys():
    dev = contract.device_record([_Dev()], 123, busy_s=1.5, window_s=2.0)
    line = json.loads(contract.result_line(
        True, 10, 0, {"setup_s": 1.25}, {"setup_s": "s"}, dev,
        {"device_ops": [["fusion", 0.1]], "idle_gaps": []}))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert line["metrics"] == {"setup_s": {"value": 1.25, "unit": "s"}}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 123,
                              "busy_s": 1.5, "window_s": 2.0}
    plain = json.loads(contract.result_line(
        True, 1, 0, {}, {}, contract.device_record([_Dev()], 1)))
    assert "breakdown" not in plain and "busy_s" not in plain["device"]


def test_a_value_that_is_not_finite_makes_the_run_incorrect():
    line = json.loads(contract.result_line(
        True, 1, 0, {"x": float("nan")}, {"x": "ms"},
        contract.device_record([_Dev()], 1)))
    assert line["correct"] is False and line["metrics"] == {}


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert bench["command"][1].startswith("benchmark/")
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_names_units_and_bounds(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_cells_configs_and_their_files(bench):
    cfgs = {c["name"]: c for c in bench["configs"]}
    seen = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in seen  # a pair appears once
        seen.add((w["config"], w["traffic"]))
        traffic = cells.load_json("traffic", w["traffic"])
        cells.load_module("drivers", traffic["driver"])
        cells.load_module("references", w["config"])
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/")
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        sizes = cells.config_sizes(bench, c["name"])
        assert sizes["reduced"] == c["reduced"]
        assert sizes["source"] == c["source"]


def _check_cell_reports(bench, w):
    e2e = [m["name"] for m in cells.metrics_of(bench, "end_to_end", w)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cells.metrics_of(bench, "per_layer", w)
    assert layer
    for m in layer:  # reported only where the metric it moves is
        assert m["moves"] in e2e, (w["name"], m["name"])
    known = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert set(cells.reports(w)) <= known, w["name"]
    return e2e, [m["name"] for m in layer]


def test_every_cell_reports_what_the_contract_asks(bench):
    for w in bench["workloads"]:
        _check_cell_reports(bench, w)
    by_name = {w["name"]: w for w in bench["workloads"]}
    _, one = _check_cell_reports(bench, by_name["bert-base.train-mlm-s128"])
    _, dp4 = _check_cell_reports(bench,
                                 by_name["bert-base.train-mlm-s128-dp4"])
    assert set(dp4) - set(one) == {"collective_ms_per_step",
                                   "collective_exposed_ms_per_step"}


def test_the_index_the_driver_reads_is_what_the_cells_say(bench):
    """A cell names its metrics in its traffic file; the ``workloads`` lists
    on BENCHMARK.json's metric entries are derived from that
    (tools/index_metrics.py) and lack none of them. (A list may hold more:
    a later metric reaches an old cell by listing it.)"""
    assert cells.index_gaps(bench) == []
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            for name in m.get("workloads", ()):
                cells.find_cell(bench, name)  # every listed cell exists


def test_the_dp_mix_is_the_one_chip_mix():
    """A pair of configuration and traffic appears once, so the four-chip
    cell has a traffic file of its own: the same rows, plus the collective
    metrics in ``reports``."""
    one = cells.load_json("traffic", "mlm-s128-b256")
    dp = cells.load_json("traffic", "mlm-s128-b256-dp")
    assert set(dp["reports"]) > set(one["reports"])
    for t in (one, dp):
        del t["reports"], t["why"]
    assert one == dp
    plain = cells.load_json("traffic", "imagenet-b256")
    workers = cells.load_json("traffic", "imagenet-b256-workers4")
    assert (plain["loader"]["num_workers"],
            workers["loader"]["num_workers"]) == (0, 4)
    for t in (plain, workers):
        del t["loader"]["num_workers"], t["why"]
    assert plain == workers


def test_every_per_layer_metric_has_a_reader_that_declares_the_same(bench):
    for m in bench["per_layer"]:
        mod = cells.load_module("layer_metrics", m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == \
            (m["layer"], m["unit"], m["source"], m["moves"]), m["name"]
        assert mod.read({}) is None  # nothing to read -> nothing reported


def _run_py(cwd, root):
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "bert-base.train-mlm-s128", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_fallback_without_a_tpu():
    """On a machine whose jax platform is not ``tpu`` the command exits
    non-zero and prints no result (no metric, no contract line)."""
    proc = _run_py(cells.ROOT, cells.ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "metrics" not in proc.stdout and "correct" not in proc.stdout


def test_fails_where_only_the_benchmark_is(tmp_path):
    import shutil

    shutil.copytree(cells.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run_py(str(tmp_path), str(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_a_device_outside_the_peaks_table_is_an_error():
    from benchmark.harness import peaks

    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_the_proposed_serving_cell_keeps_the_same_rules():
    """benchmark/proposed/serve-embed-open.json: the entries the next
    benchmark issue appends, and nothing else (built and measured in PR 22,
    not admitted because an encoder service does not fill a quarter of the
    chip). Appending them edits no entry that is there."""
    current = cells.load_benchmark()
    if any(w["name"] == "bert-base.serve-embed-open"
           for w in current["workloads"]):
        pytest.skip("a later PR has appended these entries")
    grown = cells.load_benchmark(append="benchmark/proposed/"
                                 "serve-embed-open.json")
    for key, value in current.items():
        assert grown[key][:len(value)] == value if isinstance(value, list) \
            else grown[key] == value
    cell = grown["workloads"][-1]
    assert cell["name"] == "bert-base.serve-embed-open"
    assert cells.index_gaps(grown) == []
    e2e, layer = _check_cell_reports(grown, cell)
    assert e2e == ["setup_s", "first_reply_ms_p50", "first_reply_ms_p95",
                   "serve_good_tokens_per_s"]
    assert "train_mfu_pct" not in layer and "gen_late_ms_p95" in layer
    for m in cells.metrics_of(grown, "per_layer", cell):
        mod = cells.load_module("layer_metrics", m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == \
            (m["layer"], m["unit"], m["source"], m["moves"]), m["name"]
    for w in current["workloads"]:  # the old cells report what they did
        assert cells.metrics_of(grown, "end_to_end", w) == \
            cells.metrics_of(current, "end_to_end", w)
