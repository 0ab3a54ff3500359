"""Rehearsal of the cell PR 38 adds, on the CPU: the Qwen3-Next cell's driver
end to end at ``TOY`` width (Gated DeltaNet's scan with one decay a head in
three layers, gated grouped-query attention in one, the softmax router with
a held share, the gated shared expert, per-block recomputation, the
reference check in float32 and block by block under amp), the configuration
file against the catalog row, the FLOP and byte functions against hand
counts, and the five new per-layer readers on a recorded form of the trace.
A rehearsal's numbers are never a metric."""
import copy
import json

import pytest

from benchmark.harness import cells, rehearsal, runner

CONFIG, TRAFFIC = "qwen3-next-80b-a3b", "lm-s16384-b1-gdn"
CELL = "qwen3-next-80b-a3b.train-lm-s16384"
NEW = ("gdn_ms_per_step", "gdn_core_ms_per_step", "gdn_core_roofline",
       "gqa_ms_per_step", "gqa_flash_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def _stop_children():
    from paddle_tpu.distributed import topology

    topology.set_global_mesh(None)
    yield
    runner.stop_children()


def _sizes():
    return dict(cells.config_sizes(cells.load_benchmark(), CONFIG))


def test_train_loop_qwen3_next_toy(tmp_path):
    from paddle_tpu.incubate import moe
    from paddle_tpu.ops import linear_attention

    config = cells.load_module("configs", CONFIG)
    toy = copy.deepcopy(cells.load_json("traffic", TRAFFIC))
    toy["rows_per_chip"] = 2
    toy["fields"][0]["shape"] = [40]     # two and a half toy chunks
    toy["trace_steps"] = 2
    paths = ("chunked_scalar", "recurrent_scalar", "chunked", "kernel")
    before = {p: linear_attention._CORE_TOTAL.value(path=p) for p in paths}
    held = moe._DISPATCH_TOTAL.value(path="sorted_held")
    result, notes = rehearsal.rehearse(CONFIG, toy, config.TOY,
                                       str(tmp_path), seconds=1.0)
    ref = notes[0]["reference_check"]
    assert ref["ok"], ref
    # float32 against float32: far inside the tolerance a bf16 run fails
    assert ref["f32_rel_err"] < 1e-5 < ref["f32_rtol"]
    assert ref["loss_f32_rel_err"] < 2e-6 < ref["loss_f32_rtol"]
    # the amp half really is bf16, is compared a block, and not on nothing
    assert ref["amp_rel_err"] > 10 * ref["f32_rel_err"]
    assert len(ref["amp_compared_share"]) == 4
    assert min(ref["amp_compared_share"]) >= ref["amp_compared_min"]
    assert len(ref["held_pairs_landed"]) == 4     # every layer has experts
    assert ref["f32_dropped"] == ref["amp_dropped"] == ref["ref_dropped"] == 0
    assert ref["overflow_train_steps"] == 0
    assert notes[0]["loss_fell"]
    assert result["correct"] and result["failed"] == 0
    rec = result["record"]
    assert rec["window_compiles"] == 0 and rec["rows_per_step"] == 2
    # the step runs the scan with the decay a head, never the per-channel
    # one or the token recurrence, and every layer the held-share path
    now = {p: linear_attention._CORE_TOTAL.value(path=p) for p in paths}
    assert now["chunked_scalar"] > before["chunked_scalar"]
    assert all(now[p] == before[p] for p in paths[1:])
    assert moe._DISPATCH_TOTAL.value(path="sorted_held") > held


def test_cell_reports_the_new_readers_beside_the_shared_ones():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    names = {m["name"] for m in cells.metrics_of(bench, "per_layer", cell)}
    assert set(NEW) | {"moe_held_gemm_roofline", "recompute_ms_per_step",
                       "lm_head_ms_per_step", "moe_ms_per_step",
                       "moe_dispatch_ms_per_step", "train_mfu_pct"} <= names
    # no latent attention, no per-channel scan, no MTP module; and the
    # equal-width rooflines would take hidden / heads = 128 as the head
    # width and every layer as attention
    assert not {"mla_ms_per_step", "mla_flash_roofline", "kda_ms_per_step",
                "kda_core_roofline", "mtp_ms_per_step", "flash_roofline",
                "moe_gemm_roofline"} & names
    for name in NEW:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_samples_per_s"
    assert not cells.index_gaps(bench)
    # the mix is Kimi-Linear's row for row: the two scans are read at one
    # shape
    mine, theirs = (cells.load_json("traffic", t)
                    for t in (TRAFFIC, "lm-s16384-b1"))
    for key in ("rows_per_chip", "loader", "fields", "pack", "label",
                "probe_steps", "trace_steps", "driver"):
        assert mine[key] == theirs[key], key


def test_configuration_keeps_every_published_width():
    """Every number of the catalog row's config is in the file under the
    same key, but the three the cut lists; the cut's arithmetic is the
    model's."""
    sizes = _sizes()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert sizes["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if sizes.get(k) != v)
    assert differ == sorted(sizes["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (sizes["num_hidden_layers"], sizes["num_experts"],
            sizes["vocab_size"]) == (4, 32, 18992)
    assert sizes["published"] == {k: row["config"][k]
                                  for k in sizes["reduced"]}
    assert sizes["vocab_size"] * 8 == row["config"]["vocab_size"]
    # the reader's names say what the source's do
    assert sizes["router_experts"] == row["config"]["num_experts"] == 512
    assert sizes["n_routed_experts"] == sizes["num_experts"]
    assert sizes["held_experts"] == [0, sizes["n_routed_experts"]]
    assert sizes["first_k_dense_replace"] == 0
    # 625.7 M parameters, counted from the shapes the model would build
    h, key, value = 2048, 16 * 128, 32 * 128
    gdn = (h * (2 * key + 2 * value) + h * 64 + 4 * (2 * key + value)
           + 32 + 32 + 128 + value * h)
    gqa = h * 2 * 16 * 256 + 2 * h * 2 * 256 + 16 * 256 * h + 2 * 256
    expert = 3 * h * 512
    moe = h * 512 + 33 * expert + h
    total = (3 * gdn + gqa + 4 * moe + 8 * h + h + 2 * 18992 * h)
    assert gdn == pytest.approx(33.72e6, rel=1e-3)
    assert gqa == pytest.approx(27.26e6, rel=1e-3)
    assert total == pytest.approx(625.7e6, rel=1e-3)
    assert "625.7 M parameters = 10.0 GB" in sizes["cut"]["arithmetic"]


def test_toy_model_has_the_parameters_the_arithmetic_counts():
    """The constructor at published widths would build what the cut's
    arithmetic counts: checked on the shapes, at the toy's depth of four."""
    config = cells.load_module("configs", CONFIG)
    sizes = dict(_sizes(), **config.TOY)
    from paddle_tpu.text.models import Qwen3NextModel

    net = Qwen3NextModel(**config.model_kwargs(sizes))
    counted = sum(int(p.size) for p in net.parameters())
    h, key, value = 64, 2 * 16, 4 * 16
    gdn = (h * (2 * key + 2 * value) + h * 8 + 4 * (2 * key + value)
           + 4 + 4 + 16 + value * h)
    gqa = h * 2 * 4 * 32 + 2 * h * 2 * 32 + 4 * 32 * h + 2 * 32
    moe = h * 32 + 9 * 3 * h * 32 + h
    assert counted == 3 * gdn + gqa + 4 * moe + 9 * h + 2 * 256 * h


def test_flops_per_sample_and_the_roofline_counts():
    config = cells.load_module("configs", CONFIG)
    gdn = cells.load_module("layer_metrics", "gdn_core_roofline")
    gqa = cells.load_module("layer_metrics", "gqa_flash_roofline")
    sizes = _sizes()
    seq, h = 16384, 2048
    assert config.mixer_counts(sizes) == (3, 1)
    # a Gated DeltaNet layer's matrices: 33.69 M multiply-adds a token
    assert config.gdn_projection_flops_per_token(sizes) == 2 * (
        h * 12288 + h * 64 + 4096 * h)
    # the scan: 142.6 kFLOP a token and value head at d 128, chunk 64
    per_head = (2 * 128 * 65 + 2 * 64 * 64 / 3 + 65 * 256 + 6 * 128 * 128
                + 65 * 128)
    assert gdn.gdn_core_flops(1, 1, 128, 128, 64) == pytest.approx(per_head)
    assert per_head == pytest.approx(142.6e3, rel=1e-3)
    assert config.gdn_core_flops_per_token(sizes) == pytest.approx(
        32 * per_head)
    # a backward pass is two forwards' worth
    assert gdn.gdn_core_flops(10, 32, 128, 128, 64, 2, 1) == pytest.approx(
        4 * 10 * 32 * per_head)
    # bytes a token: q, k in bf16 a KEY head, v in bf16, decay and beta one
    # float32 each and o in float32 a value head — 1,032 a value head where
    # the per-channel scan moves 1,796
    assert gdn.gdn_core_bytes(1, 32, 16, 128, 128) == (
        16 * 2 * 256 + 32 * (2 * 128 + 4 * 130))
    assert gdn.gdn_core_bytes(1, 32, 16, 128, 128) / 32 == 1032
    assert gdn.gdn_core_bytes(1, 32, 16, 128, 128, 0, 1) == 2 * 32 * 1032
    # 138 FLOPs a byte: under the chip's 240, so the bytes bound the scan
    assert per_head / 1032 < 197e12 / 819e9
    # gated attention: four matrices, and the causal core at 16k over the
    # 16 QUERY heads of 256
    assert config.gqa_projection_flops_per_token(sizes) == 2 * (
        h * 8192 + 2 * h * 512 + 4096 * h)
    core = config.gqa_core_flops(sizes, seq)
    assert core == 2 * 16 * (seq * (seq + 1) // 2) * 512
    assert core / seq == pytest.approx(134.2e6, rel=1e-3)
    assert gqa.core_flops(1, 16, seq, 256, 1, 1) == pytest.approx(3.5 * core)
    assert gqa.core_bytes(1, 16, 2, seq, 256, 1, 0) == seq * 256 * 2 * 36
    # a token's 10 choices land on the 32 held of 512 experts 0.625 times
    assert config.held_expert_flops_per_token(sizes) == (
        10 * 32 / 512 * 3 * 2 * h * 512)
    # 26.1 TFLOP a step: nothing recomputed, only the held experts' rows,
    # the head over the held vocabulary slice
    flops = config.flops_per_sample(sizes, {"input_ids": (seq,)})
    per_token = (202.11e6 + 13.69e6 + 54.53e6 + 134.23e6 + 8.39e6
                 + 25.18e6 + 15.73e6 + 77.79e6)
    assert flops == pytest.approx(3 * seq * per_token, rel=2e-3)
    assert 26.0e12 < flops < 26.3e12
    # the accepted held-gemm roofline's functions hold here: widths and
    # counts are the file's, the gated shared expert runs under moe.shared
    held = cells.load_module("layer_metrics", "moe_held_gemm_roofline")
    assert held.expert_layers(sizes) == 4 and held.held_rows(
        sizes, seq) == 10240
    assert held.held_gemm_flops(sizes, seq, 1) == pytest.approx(
        4 * 3 * 2 * h * 512 * 10240)


# ---------------------------------------------------------- the readers
ROOT_SCOPE = "jit(train_step)/jvp(CausalLM)/lm:Qwen3NextModel/"
BLOCK = ROOT_SCOPE + "checkpoint/layers:LayerList/1:Qwen3NextDecoderLayer/"
BWD = BLOCK.replace("jvp(CausalLM)", "transpose(jvp(CausalLM))")
REMAT = BWD.replace("checkpoint/", "checkpoint/rematted_computation/")
GDN, GQA = "linear_attn:GatedDeltaNet/", "self_attn:GatedGQAttention/"
ATTN = BLOCK.replace("1:", "3:") + GQA
ATTN_BWD = BWD.replace("1:", "3:") + GQA


def _record(events):
    """A traced slice of 2 steps in ``program_trace``'s loaded form;
    ``events``: (event name, op_name, start_us, duration_us)."""
    return {
        "program_trace": {"planes": [{"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops",
            "events": [[n, s * 1e3, d * 1e3, op] for n, op, s, d in events],
        }]}]},
        "trace_steps": 2, "rows_per_step": 1, "chips": 1, "sizes": _sizes(),
        "traffic": cells.load_json("traffic", TRAFFIC),
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_new_readers_on_a_recorded_trace():
    read = {n: cells.load_module("layer_metrics", n).read for n in NEW}
    rec = _record([
        ("%fusion.1", BLOCK + GDN + "gdn.proj/in_proj_qkvz:Linear/"
         "dot_general", 0, 3000),
        ("%kda_chunk_fwd.1", BLOCK + GDN + "gdn.core/kda_chunk_fwd/"
         "pallas_call", 3000, 8000),
        ("%flash_stream_fwd.1", ATTN + "gqa.core/flash_stream_fwd/"
         "pallas_call", 100000, 30000),
        # XLA's relayout of the kernel's result, named after its operand:
        # time under the kernel's name, but no call
        ("%reduce.1", ATTN + "gqa.core/flash_stream_fwd/pallas_call",
         130000, 500),
        ("%fusion.3", ATTN + "gqa.repeat/repeat", 130500, 500),
        ("%fusion.4", REMAT + GDN + "gdn.conv/mul", 21000, 1000),
        ("%kda_chunk_fwd.2", REMAT + GDN + "gdn.core/kda_chunk_fwd/"
         "pallas_call", 22000, 8000),
        ("%kda_chunk_bwd.1", BWD + GDN + "gdn.core/kda_chunk_bwd/"
         "pallas_call", 30000, 12000),
        ("%flash_stream_bwd_dkv_dq.1", ATTN_BWD + "gqa.core/"
         "flash_stream_bwd_dkv_dq/pallas_call", 140000, 60000),
        ("%fusion.8", BWD + GDN + "gdn.out/out_proj:Linear/dot_general",
         62000, 2000),
        ("%fusion.9", "jit(train_step)/optimizer/add", 70000, 9000),
    ])
    assert read["gdn_ms_per_step"](rec) == pytest.approx(34000 / 2e3)
    assert read["gdn_core_ms_per_step"](rec) == pytest.approx(28000 / 2e3)
    assert read["gqa_ms_per_step"](rec) == pytest.approx(91000 / 2e3)
    # the trace shows a forward, a block's recomputed forward and a
    # backward of the scan
    gdn = cells.load_module("layer_metrics", "gdn_core_roofline")
    assert gdn.passes(rec) == (2, 1)
    tokens = 3 * 16384                 # three DeltaNet layers, one row
    least = gdn.gdn_core_bytes(tokens, 32, 16, 128, 128, 2, 1) / 819e9
    assert least > gdn.gdn_core_flops(tokens, 32, 128, 128, 64, 2,
                                      1) / 197e12
    assert read["gdn_core_roofline"](rec) == pytest.approx(
        100 * least / 0.014)
    assert read["gdn_core_roofline"](rec) < 100
    # ONE forward call a step (the relayout is none: half a call a step it
    # would add) and one backward, over the 90.5 ms under the kernel's name
    gqa = cells.load_module("layer_metrics", "gqa_flash_roofline")
    assert gqa.calls(rec, "flash_stream_fwd") == 1
    flops = gqa.core_flops(1, 16, 16384, 256, 0.5, 0.5)
    assert read["gqa_flash_roofline"](rec) == pytest.approx(
        100 * flops / 0.04525 / 197e12)
    assert read["gqa_flash_roofline"](rec) < 100
    # a trace that names its events otherwise: the op_name decides
    renamed = _record([
        ("%custom-call.1", ATTN + "gqa.core/flash_stream_fwd/pallas_call",
         0, 9000),
        ("%custom-call.2", ATTN_BWD + "gqa.core/flash_stream_bwd_dkv_dq/"
         "pallas_call", 9000, 20000)])
    assert gqa.calls(renamed, "flash_stream_fwd") == 1
    assert gqa.calls(renamed, "flash_stream_bwd_dkv") == 1


def test_new_readers_find_nothing_on_a_program_without_the_layers():
    """A parent without the model, a Kimi-Linear or BERT cell, a run without
    a trace: every new reader returns None and raises nothing."""
    kimi = ("jit(train_step)/jvp(CausalLM)/lm:KimiLinearModel/checkpoint/"
            "layers:LayerList/1:JoyAIDecoderLayer/self_attn:"
            "KimiDeltaAttention/kda.core/kda_chunk_fwd/pallas_call")
    rec = _record([("%kda_chunk_fwd.1", kimi, 0, 1000),
                   ("%flash_stream_fwd.1", kimi.replace(
                       "KimiDeltaAttention/kda.core/kda_chunk_fwd",
                       "MLAttention/mla.core/flash_stream_fwd"), 1000, 1000)])
    rec["sizes"] = cells.config_sizes(cells.load_benchmark(),
                                      "kimi-linear-48b-a3b")
    rec["traffic"] = cells.load_json("traffic", "lm-s16384-b1")
    empty = {"trace": None, "trace_steps": 2, "sizes": {}, "traffic": {}}
    for name in NEW:
        reader = cells.load_module("layer_metrics", name).read
        assert reader(rec) is None, name
        assert reader(dict(empty)) is None, name
        assert reader({}) is None, name
