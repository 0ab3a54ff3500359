"""Everything is found by the name ``BENCHMARK.json`` gives it:

  a cell's ``config``   -> the entry of ``configs`` (its ``file`` holds the
                           sizes), ``configs/<config>.py`` (the builder)
                           and ``references/<config>.py`` (plain forward)
  a cell's ``traffic``  -> ``traffic/<traffic>.json``; its ``driver`` key
                           -> ``drivers/<driver>.py``, its ``reports`` key
                           -> the metrics the cell reports
  a per-layer metric    -> ``layer_metrics/<name>.py``

so a new cell, configuration, mix or metric adds files and appends to
``BENCHMARK.json``; no file that exists is edited.
"""
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root=ROOT, append=None):
    """``BENCHMARK.json``; with ``append`` (a file of entries under the same
    list keys, relative to ``root``), with those entries appended as a
    later PR would append them (``proposed/*.json``)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if append:
        with open(os.path.join(root, append)) as f:
            for key, entries in json.load(f).items():
                bench[key] = bench[key] + entries
    return bench


def load_module(kind, name, bench_dir=BENCH_DIR):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold '-')."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    mod_name = f"benchmark_{kind}_" + name.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind, name, bench_dir=BENCH_DIR):
    with open(os.path.join(bench_dir, kind, name + ".json")) as f:
        return json.load(f)


def find_cell(bench, name):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"({[c['name'] for c in bench['workloads']]})")


def config_sizes(bench, config_name, root=ROOT):
    """The configuration as it is run: the JSON object its ``file`` holds."""
    for entry in bench["configs"]:
        if entry["name"] == config_name:
            with open(os.path.join(root, entry["file"])) as f:
                return json.load(f)
    raise KeyError(f"no config {config_name!r} in BENCHMARK.json")


def reports(cell, bench_dir=BENCH_DIR):
    """The metric names the cell's traffic file lists under ``reports``."""
    return load_json("traffic", cell["traffic"], bench_dir).get("reports", [])


def metrics_of(bench, group, cell, bench_dir=BENCH_DIR):
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports.

    The cell says so: its traffic file names them under ``reports``, so a
    new cell (of any driver) takes an old metric with no edit to the
    metric. A new metric reaches an old cell by listing it under its own
    ``workloads``, with no edit to the cell's files. A metric without a
    ``workloads`` key belongs to every cell, as the contract reads it."""
    named = reports(cell, bench_dir)
    return [m for m in bench[group]
            if "workloads" not in m or cell["name"] in m["workloads"]
            or m["name"] in named]


def index_gaps(bench, bench_dir=BENCH_DIR):
    """The driver reads only ``BENCHMARK.json``: there a metric that not
    every cell reports lists its cells under ``workloads``. Those lists are
    an index derived from the cells. Returns the (metric, cell) pairs the
    index lacks; ``tools/index_metrics.py`` appends them."""
    return [(m["name"], cell["name"])
            for cell in bench["workloads"]
            for group in ("end_to_end", "per_layer")
            for m in metrics_of(bench, group, cell, bench_dir)
            if "workloads" in m and cell["name"] not in m["workloads"]]
