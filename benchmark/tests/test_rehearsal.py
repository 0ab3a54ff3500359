"""Rehearsal 1 and 2 of the on-chip-measurement guide: every driver end to
end at toy width on the CPU, the dp path on four virtual devices, and each
plain reference against the framework model. A rehearsal's numbers are
labelled as such and are never a metric."""
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import cells, rehearsal, runner
from benchmark.tests import toy


@pytest.fixture(autouse=True)
def _stop_children():
    yield
    runner.stop_children()


def _toy(config):
    return cells.load_module("configs", config).TOY


def _check_train(result, notes, rows):
    assert result["rehearsal"] is True
    ref = notes[0]["reference_check"]
    # float32 against float32: far inside the tolerance a bf16 run fails
    assert ref["ok"] and ref["f32_rel_err"] < 1e-5 < ref["f32_rtol"]
    assert ref["amp_rel_err"] > ref["f32_rel_err"]  # amp really is bf16
    assert notes[0]["loss_fell"]
    assert result["correct"] and result["failed"] == 0
    rec = result["record"]
    assert rec["window_compiles"] == 0
    assert rec["rows_per_step"] == rows
    assert len(rec["step_done"]) == rec["steps"] == result["attempted"] >= 2
    assert rec["programs"][0]["footprint_bytes"] > 0
    names = {n for n, _, _ in rec["spans"]}
    assert names == {"next_batch", "shard_batch", "dispatch", "readback"}
    return rec


def test_train_loop_bert_toy(tmp_path):
    result, notes = rehearsal.rehearse(
        "bert-base", toy.mlm(), _toy("bert-base"), str(tmp_path),
        seconds=1.0, trace=True)
    rec = _check_train(result, notes, rows=4)
    # the per-layer readers work on the driver's record
    rec["peaks"] = {"bf16_flops_per_s": 1e12}
    for name in ("step_ms_p50", "input_wait_pct", "train_mfu_pct",
                 "hbm_compiled_gb", "setup_compile_s", "window_compiles"):
        value = cells.load_module("layer_metrics", name).read(rec)
        assert value is not None and np.isfinite(value), name
    # the CPU trace has no TPU plane: device readers return nothing
    assert cells.load_module("layer_metrics", "device_idle_pct").read(rec) \
        is None


def test_train_loop_bert_toy_dp4(tmp_path):
    result, notes = rehearsal.rehearse(
        "bert-base", toy.mlm(), _toy("bert-base"), str(tmp_path),
        seconds=1.0, chips=4)
    _check_train(result, notes, rows=16)


def test_train_loop_resnet_toy(tmp_path):
    result, notes = rehearsal.rehearse(
        "resnet50", toy.imagenet(), _toy("resnet50"), str(tmp_path),
        seconds=1.0)
    _check_train(result, notes, rows=4)


def test_same_seed_same_batches():
    from benchmark.harness.datasets import SeededDataset

    sizes = {"vocab_size": 512}
    a = SeededDataset(toy.mlm(), sizes, 3, 100)
    b = SeededDataset(toy.mlm(), sizes, 3, 100)
    c = SeededDataset(toy.mlm(), sizes, 4, 100)
    for i in (0, 7, 99):
        assert all(np.array_equal(x, y) for x, y in zip(a[i], b[i]))
    assert not np.array_equal(a[0][0], c[0][0])
    assert not np.array_equal(a[0][0], a[1][0])
    x, y = a[5]
    assert x.shape == (36,) and y.shape == (4,) and x.dtype == np.int32
    assert len(set(x[32:].tolist())) == 4 and x[32:].max() < 32


def test_serve_open_loop_bert_toy(tmp_path):
    result, notes = rehearsal.rehearse(
        "bert-base", toy.embed(), _toy("bert-base"), str(tmp_path),
        seconds=2.0, trace=True)
    check = notes[0]["serve_check"]
    assert check["ok"] and check["f32_rel_err"] < 1e-5
    assert notes[0]["warmup_compiles"] == len(notes[0]["declared_buckets"])
    assert result["correct"], notes
    assert result["failed"] == 0 and result["attempted"] > 100
    assert set(result["end_to_end"]) == {
        "first_reply_ms_p50", "first_reply_ms_p95", "serve_good_tokens_per_s"}
    rec = result["record"]
    assert rec["window_compiles"] == 0
    for name in ("serve_queue_wait_ms_mean", "serve_batch_rows_mean",
                 "serve_pad_pct", "serve_exec_ms_mean", "gen_late_ms_p95"):
        value = cells.load_module("layer_metrics", name).read(rec)
        assert value is not None and np.isfinite(value), name


def test_run_py_reports_only_what_readers_find(tmp_path):
    """run.py's per-layer path on a record with no trace: metrics whose
    reader finds nothing are left out, and the run is not correct."""
    bench = cells.load_benchmark()
    cell = bench["workloads"][0]
    ctx = rehearsal.RehearsalContext(out_dir=str(tmp_path))
    values, device, breakdown, ok = bench_run.per_layer(
        ctx, bench, cell, {"setup_compile_s": 1.0, "window_compiles": 0})
    assert values == {"setup_compile_s": 1.0, "window_compiles": 0}
    assert not ok and breakdown is None and device["busy_s"] == 0.0
