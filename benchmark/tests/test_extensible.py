"""A later PR adds cells, traffic mixes and a per-layer metric by ADDING
files and APPENDING to BENCHMARK.json; it edits no file that is there.
Exercised on a copy of the benchmark (README.md describes the same steps):

  * a train cell from an existing configuration and a new traffic file,
    which takes the old train metrics by naming them in ``reports``;
  * a second cell across chips, which takes ``collective_ms_per_step``;
  * a cell of the OTHER driver (``serve_open_loop`` at toy width), which
    takes the serving metrics and none of the train metrics;
  * a new per-layer metric that reaches an old cell and a new one.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from benchmark.harness import cells
from benchmark.tests import toy

TRAIN = ["train_samples_per_s", "step_ms_p50", "train_mfu_pct",
         "input_wait_pct", "hbm_compiled_gb", "device_idle_pct"]
COLLECTIVE = ["collective_ms_per_step", "collective_exposed_ms_per_step"]

NEW_TRAFFIC = {
    "driver": "train_loop", "reports": TRAIN,
    "why": "a toy mix a later PR might add: 64-token rows",
    "rows_per_chip": 4,
    "loader": {"num_workers": 2, "prefetch_factor": 2},
    "fields": [
        {"name": "input_ids", "shape": [64], "dtype": "int32",
         "draw": "uniform_int", "low": 10, "high": "vocab_size"},
        {"name": "masked_positions", "shape": [8], "dtype": "int32",
         "draw": "distinct_positions", "of": 64},
        {"name": "mlm_labels", "shape": [8], "dtype": "int32",
         "draw": "uniform_int", "low": 10, "high": "vocab_size"}],
    "pack": ["input_ids", "masked_positions"], "label": "mlm_labels",
    "probe_steps": 10, "trace_steps": 2,
}

NEW_METRIC = '''"""Steps the window completed (a new per-layer metric)."""
LAYER = "train step (distributed/spmd.py, amp/, optimizer/)"
UNIT = "count"
SOURCE = "host_clock"
MOVES = "train_samples_per_s"


def read(record):
    return record.get("steps")
'''

SCRIPT = '''
import json, sys
from benchmark.harness import cells, rehearsal, runner
from benchmark import run as bench_run
root = sys.argv[1]
assert cells.ROOT == root, (cells.ROOT, root)
bench = cells.load_benchmark()
out = {"resolved": {
    w["name"]: [m["name"] for g in ("end_to_end", "per_layer")
                for m in cells.metrics_of(bench, g, w)]
    for w in bench["workloads"]}}
for name, seconds in (("bert-base.train-mlm-s64", 0.5),
                      ("bert-base.serve-embed-toy", 2.0)):
    cell = cells.find_cell(bench, name)
    traffic = cells.load_json("traffic", cell["traffic"])
    toy = cells.load_module("configs", cell["config"]).TOY
    result, notes = rehearsal.rehearse(cell["config"], traffic, toy,
                                       root + "/out", seconds=seconds)
    runner.stop_children()
    ctx = rehearsal.RehearsalContext(out_dir=root + "/out")
    e2e, complete = bench_run.end_to_end(ctx, bench, cell, result, 1.0)
    record = dict(result["record"], peaks={"bf16_flops_per_s": 1e12})
    layer, _, _, _ = bench_run.per_layer(ctx, bench, cell, record)
    out[name] = {"correct": result["correct"], "complete": complete,
                 "end_to_end": sorted(e2e), "per_layer": layer,
                 "errors": [n for n in ctx.notes if "error" in n]}
print(json.dumps(out))
'''


def _hashes(root):
    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _write(path, obj):
    with open(path, "w") as f:
        f.write(obj) if isinstance(obj, str) else json.dump(obj, f)


def _load(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _appended_only(original, grown):
    """Every list of ``grown`` starts with ``original``'s entries; an old
    entry differs at most by names appended to its ``workloads``."""
    for key, value in original.items():
        if not isinstance(value, list):
            assert grown[key] == value
            continue
        for old, new in zip(value, grown[key]):
            if isinstance(old, dict) and "workloads" in old:
                assert new["workloads"][:len(old["workloads"])] == \
                    old["workloads"]
                old = dict(old, workloads=new["workloads"])
            assert new == old
        assert len(grown[key]) >= len(value)


def test_new_cells_of_both_drivers_and_a_new_metric_need_only_new_files(
        tmp_path):
    root = str(tmp_path)
    shutil.copytree(cells.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), root)
    before = _hashes(root)
    original = _load(root)

    # --- what the later PRs do: new files ... ---
    bench_dir = os.path.join(root, "benchmark")
    new_files = {
        "traffic/mlm-s64-toy.json": NEW_TRAFFIC,
        "traffic/mlm-s64-toy-dp.json": dict(NEW_TRAFFIC,
                                            reports=TRAIN + COLLECTIVE),
        "traffic/embed-open-toy.json": toy.embed(),  # keeps its ``reports``
        "layer_metrics/steps_in_window.py": NEW_METRIC}
    for rel, content in new_files.items():
        _write(os.path.join(bench_dir, rel), content)
    # --- ... and entries appended to BENCHMARK.json: the serving metrics
    # as proposed/ holds them, three cells, one metric ---
    admitted = any(m["name"] == "first_reply_ms_p95"
                   for m in original["end_to_end"])
    grown = cells.load_benchmark(
        root, append=None if admitted
        else "benchmark/proposed/serve-embed-open.json")
    for name, traffic, chips in (
            ("bert-base.train-mlm-s64", "mlm-s64-toy", 1),
            ("bert-base.train-mlm-s64-dp4", "mlm-s64-toy-dp", 4),
            ("bert-base.serve-embed-toy", "embed-open-toy", 1)):
        grown["workloads"].append({"name": name, "config": "bert-base",
                                   "traffic": traffic, "chips": chips,
                                   "why": "test"})
    grown["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "host_clock",
        "layer": "train step (distributed/spmd.py, amp/, optimizer/)",
        "moves": "train_samples_per_s",
        "workloads": ["bert-base.train-mlm-s128",      # reaches an old cell
                      "bert-base.train-mlm-s64"]})
    _write(os.path.join(root, "BENCHMARK.json"), grown)
    _appended_only(original, grown)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=root + os.pathsep + cells.ROOT)
    proc = subprocess.run([sys.executable, "-c", SCRIPT, root], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])

    serve_names = set(toy.embed()["reports"])
    assert len(serve_names) == 8
    everywhere = {m["name"] for g in ("end_to_end", "per_layer")
                  for m in grown[g] if "workloads" not in m}
    assert {"setup_s", "setup_compile_s", "window_compiles"} <= everywhere
    resolved = {k: set(v) for k, v in got["resolved"].items()}
    assert resolved["bert-base.train-mlm-s64"] == \
        everywhere | set(TRAIN) | {"steps_in_window"}
    assert resolved["bert-base.train-mlm-s64-dp4"] == \
        everywhere | set(TRAIN) | set(COLLECTIVE)
    assert resolved["bert-base.serve-embed-toy"] == everywhere | serve_names
    assert "steps_in_window" in resolved["bert-base.train-mlm-s128"]
    for w in original["workloads"]:  # nothing else changed for an old cell
        assert resolved[w["name"]] - {"steps_in_window"} == {
            m["name"] for g in ("end_to_end", "per_layer")
            for m in cells.metrics_of(original, g, w)}

    train = got["bert-base.train-mlm-s64"]
    assert train["correct"] and train["complete"], train
    assert train["end_to_end"] == ["setup_s", "train_samples_per_s"]
    assert train["per_layer"]["steps_in_window"] >= 2
    assert "step_ms_p50" in train["per_layer"]     # an old metric, new cell
    serve = got["bert-base.serve-embed-toy"]       # the other driver
    assert serve["correct"] and serve["complete"], serve
    assert serve["end_to_end"] == sorted(
        ["setup_s", "first_reply_ms_p50", "first_reply_ms_p95",
         "serve_good_tokens_per_s"])
    assert {"serve_pad_pct", "gen_late_ms_p95"} <= set(serve["per_layer"])
    assert not set(TRAIN) & set(serve["per_layer"])

    # --- the driver's index is derived from the cells, by appending ---
    proc = subprocess.run(
        [sys.executable, os.path.join(bench_dir, "tools",
                                      "index_metrics.py")],
        cwd=root, env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-3000:]
    indexed = _load(root)
    assert cells.index_gaps(indexed, bench_dir) == []
    _appended_only(grown, indexed)
    by_name = {m["name"]: m for m in indexed["end_to_end"]
               + indexed["per_layer"]}
    collective = by_name["collective_ms_per_step"]["workloads"]
    assert collective[0] == "bert-base.train-mlm-s128-dp4"
    assert collective[-1] == "bert-base.train-mlm-s64-dp4"
    assert by_name["first_reply_ms_p95"]["workloads"][-1] == \
        "bert-base.serve-embed-toy"
    assert "bert-base.serve-embed-toy" not in \
        by_name["train_samples_per_s"]["workloads"]

    # --- no file that existed was edited ---
    after = {k: v for k, v in _hashes(root).items()
             if not k.startswith("out/")}
    assert [k for k in before if after.get(k) != before[k]] == \
        ["BENCHMARK.json"]
    assert sorted(set(after) - set(before)) == sorted(
        "benchmark/" + rel for rel in new_files)
