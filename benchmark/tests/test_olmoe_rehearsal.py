"""Rehearsals of the two cells PR 25 adds, on the CPU: the OLMoE cell's
driver end to end at ``TOY`` width (the dropless expert layer, the chunked
loss, the reference check of logits and loss), the ``mlm-s512-b64`` mix on
the toy BERT, and the five new per-layer readers on a recorded form of the
trace. A rehearsal's numbers are never a metric."""
import copy

import numpy as np
import pytest

from benchmark.harness import cells, rehearsal, runner


@pytest.fixture(autouse=True)
def _stop_children():
    # MoELayer's ``auto`` reads the global mesh where no step builder
    # announced one (the reference check's plain jit): none here
    from paddle_tpu.distributed import topology

    topology.set_global_mesh(None)
    yield
    runner.stop_children()


def _toy_lm(rows_per_chip=2, seq=32):
    t = copy.deepcopy(cells.load_json("traffic", "lm-s4096-b4"))
    t["rows_per_chip"] = rows_per_chip
    t["fields"][0]["shape"] = [seq]
    t["probe_steps"], t["trace_steps"] = 10, 2
    return t


def test_train_loop_olmoe_toy(tmp_path):
    from paddle_tpu.incubate import moe

    config = cells.load_module("configs", "olmoe-1b-7b")
    sorted_before = moe._DISPATCH_TOTAL.value(path="sorted")
    capacity_before = moe._DISPATCH_TOTAL.value(path="capacity")
    result, notes = rehearsal.rehearse(
        "olmoe-1b-7b", _toy_lm(), config.TOY, str(tmp_path), seconds=1.0)
    ref = notes[0]["reference_check"]
    # float32 against float32: far inside the tolerances a bf16 run fails
    assert ref["ok"], ref
    assert ref["f32_rel_err"] < 2e-6 < ref["f32_rtol"]
    assert ref["loss_f32_rel_err"] < 2e-6 < ref["loss_f32_rtol"]
    assert ref["amp_rel_err"] > 10 * ref["f32_rel_err"]   # amp really is bf16
    assert ref["amp_rel_err"] > ref["f32_rtol"]           # and would fail f32
    assert notes[0]["loss_fell"]
    assert result["correct"] and result["failed"] == 0
    rec = result["record"]
    assert rec["window_compiles"] == 0 and rec["rows_per_step"] == 2
    assert rec["programs"][0]["footprint_bytes"] > 0
    # every traced expert layer took the dropless path
    assert moe._DISPATCH_TOTAL.value(path="sorted") > sorted_before
    assert moe._DISPATCH_TOTAL.value(path="capacity") == capacity_before
    # 17.56 TFLOP a step at published widths, one layer, 4 rows of 4,096
    sizes = dict(cells.config_sizes(cells.load_benchmark(), "olmoe-1b-7b"))
    flops = 4 * config.flops_per_sample(sizes, {"input_ids": (4096,)})
    assert 17.5e12 < flops < 17.6e12


def test_mlm_s512_mix_on_the_toy_bert(tmp_path):
    """The phase-2 mix is the s128 file with 512 / 80 / 64 in place of 128 /
    20 / 256, the same tokens and predictions a step, and drives the toy
    BERT end to end at its own shape ratio (seq 64, 10 masked)."""
    small, large = (cells.load_json("traffic", n)
                    for n in ("mlm-s128-b256", "mlm-s512-b64"))
    shape = {f["name"]: f["shape"][0] for f in large["fields"]}
    assert shape == {"input_ids": 512, "masked_positions": 80,
                     "mlm_labels": 80}
    assert large["fields"][1]["of"] == 512
    assert large["rows_per_chip"] * 512 == small["rows_per_chip"] * 128
    assert large["rows_per_chip"] * 80 == small["rows_per_chip"] * 20
    for key in ("driver", "loader", "pack", "label", "probe_steps",
                "trace_steps"):
        assert large[key] == small[key], key
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, "bert-base.train-mlm-s512")
    of_s128 = {m["name"] for g in ("end_to_end", "per_layer")
               for m in cells.metrics_of(bench, g, cells.find_cell(
                   bench, "bert-base.train-mlm-s128"))}
    assert set(large["reports"]) | {"setup_s", "setup_compile_s",
                                    "window_compiles"} == of_s128
    assert {m["name"] for g in ("end_to_end", "per_layer")
            for m in cells.metrics_of(bench, g, cell)} == of_s128

    toy = copy.deepcopy(large)
    toy["rows_per_chip"] = 2
    by_name = {f["name"]: f for f in toy["fields"]}
    by_name["input_ids"].update(shape=[64], low=10)
    by_name["masked_positions"].update(shape=[10], of=64)
    by_name["mlm_labels"].update(shape=[10], low=10)
    toy["trace_steps"] = 2
    result, notes = rehearsal.rehearse(
        "bert-base", toy, cells.load_module("configs", "bert-base").TOY,
        str(tmp_path), seconds=1.0)
    assert notes[0]["reference_check"]["ok"] and notes[0]["loss_fell"]
    assert result["correct"] and result["record"]["rows_per_step"] == 2


# ---------------------------------------------------------- the readers
ROOT_SCOPE = "jit(train_step)/jvp(CausalLM)/lm:OlmoeModel/layers:LayerList/"
MOE = ROOT_SCOPE + "0:OlmoeDecoderLayer/mlp:MoELayer/"
BWD_MOE = MOE.replace("jvp(CausalLM)", "transpose(jvp(CausalLM))")


def _record(events):
    """A traced slice of 2 steps in ``program_trace``'s loaded form;
    ``events``: (event name, op_name, start_us, duration_us)."""
    sizes = cells.config_sizes(cells.load_benchmark(), "olmoe-1b-7b")
    return {
        "program_trace": {"planes": [{"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops",
            "events": [[n, s * 1e3, d * 1e3, op] for n, op, s, d in events],
        }]}]},
        "trace_steps": 2, "rows_per_step": 4, "chips": 1, "sizes": sizes,
        "traffic": cells.load_json("traffic", "lm-s4096-b4"),
        "peaks": {"bf16_flops_per_s": 197e12}}


def test_new_readers_on_a_recorded_trace():
    read = {n: cells.load_module("layer_metrics", n).read for n in (
        "moe_ms_per_step", "moe_dispatch_ms_per_step", "moe_gemm_roofline",
        "flash_roofline", "lm_head_ms_per_step")}
    attn = ROOT_SCOPE + "0:OlmoeDecoderLayer/self_attn:OlmoeAttention/"
    rec = _record([
        ("%fusion.1", MOE + "moe.route/dot_general", 0, 1000),
        ("%sort.1", MOE + "moe.dispatch/sort", 1000, 2000),
        ("%ragged.1", MOE + "moe.experts/ragged_dot_general", 3000, 40000),
        ("%ragged.2", BWD_MOE + "moe.experts/ragged_dot_general", 43000,
         60000),
        ("%gather.9", BWD_MOE + "moe.combine/gather", 103000, 3000),
        ("%flash_stream_fwd.1", attn + "flash_stream_fwd/pallas_call",
         110000, 10000),
        ("%flash_stream_bwd_dq.1",
         attn.replace("jvp(CausalLM)", "transpose(jvp(CausalLM))")
         + "flash_stream_bwd_dq/pallas_call", 120000, 30000),
        ("%fusion.7", "jit(train_step)/jvp(loss)/dot_general", 150000, 70000),
        ("%fusion.8", "jit(train_step)/transpose(jvp(loss))/mul", 220000,
         2000),
        ("%fusion.9", "jit(train_step)/optimizer/add", 222000, 9000),
    ])
    assert read["moe_ms_per_step"](rec) == pytest.approx(106000 / 2e3)
    assert read["moe_dispatch_ms_per_step"](rec) == pytest.approx(6000 / 2e3)
    assert read["lm_head_ms_per_step"](rec) == pytest.approx(72000 / 2e3)
    # 3 matrices x 2 x 2048 x 1024 x 131,072 rows x 3 = 4.95 TFLOP a step
    gemm = cells.load_module("layer_metrics", "moe_gemm_roofline")
    flops = gemm.expert_gemm_flops(rec["sizes"], 4 * 4096)
    assert flops == 3 * 3 * 2 * 2048 * 1024 * 131072
    assert read["moe_gemm_roofline"](rec) == pytest.approx(
        100 * flops / 0.050 / 197e12)
    # 7 matmuls x 2 x 4 x 16 x (4096 x 4097 / 2) x 128: the causal half
    flash = cells.load_module("layer_metrics", "flash_roofline")
    causal = flash.attention_flops(4, 16, 4096, 128)
    assert causal == 7 * 2 * 4 * 16 * (4096 * 4097 // 2) * 128
    assert read["flash_roofline"](rec) == pytest.approx(
        100 * causal / 0.020 / 197e12)
    assert read["flash_roofline"](rec) < 100 > read["moe_gemm_roofline"](rec)


def test_new_readers_find_nothing_on_a_program_without_the_layers():
    """A parent without the expert layer, a BERT cell, a run without a
    trace: every new reader returns None and raises nothing."""
    bert = ("jit(train_step)/jvp(PackedMLM)/inner:BertForPretraining/"
            "bert:BertModel/encoder:TransformerEncoder/"
            "0:TransformerEncoderLayer/linear1:Linear/dot_general")
    rec = _record([("%fusion.1", bert, 0, 1000)])
    empty = {"trace": None, "trace_steps": 2, "sizes": {}, "traffic": {}}
    for name in ("moe_ms_per_step", "moe_dispatch_ms_per_step",
                 "moe_gemm_roofline", "flash_roofline"):
        reader = cells.load_module("layer_metrics", name).read
        assert reader(rec) is None, name
        assert reader(dict(empty)) is None, name
    head = cells.load_module("layer_metrics", "lm_head_ms_per_step").read
    assert head(rec) is None and head(dict(empty)) is None
