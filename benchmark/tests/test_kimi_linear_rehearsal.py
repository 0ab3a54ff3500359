"""Rehearsal of the cell PR 32 adds, on the CPU: the Kimi-Linear cell's
driver end to end at ``TOY`` width (Kimi Delta Attention's chunked scan in
four layers, latent attention without positions in one, the sigmoid router
with a held share, the shared expert, per-block recomputation, the
reference check in float32 and block by block under amp), the FLOP and byte
functions against hand counts, and the three new per-layer readers on a
recorded form of the trace. A rehearsal's numbers are never a metric."""
import copy

import numpy as np

import pytest

from benchmark.harness import cells, rehearsal, runner

CONFIG, TRAFFIC = "kimi-linear-48b-a3b", "lm-s16384-b1"
CELL = "kimi-linear-48b-a3b.train-lm-s16384"
NEW = ("kda_ms_per_step", "kda_core_ms_per_step", "kda_core_roofline")


@pytest.fixture(autouse=True)
def _stop_children():
    from paddle_tpu.distributed import topology

    topology.set_global_mesh(None)
    yield
    runner.stop_children()


def _sizes():
    return dict(cells.config_sizes(cells.load_benchmark(), CONFIG))


def test_train_loop_kimi_linear_toy(tmp_path):
    from paddle_tpu.incubate import moe
    from paddle_tpu.ops import linear_attention

    config = cells.load_module("configs", CONFIG)
    toy = copy.deepcopy(cells.load_json("traffic", TRAFFIC))
    toy["rows_per_chip"] = 2
    toy["fields"][0]["shape"] = [40]     # two and a half toy chunks
    toy["trace_steps"] = 2
    chunked = linear_attention._CORE_TOTAL.value(path="chunked")
    recurrent = linear_attention._CORE_TOTAL.value(path="recurrent")
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
    assert len(ref["amp_compared_share"]) == 5
    assert ref["amp_compared_share"][0] == 1.0      # the dense block: all
    assert min(ref["amp_compared_share"]) >= ref["amp_compared_min"]
    assert len(ref["held_pairs_landed"]) == 4
    assert ref["f32_dropped"] == ref["amp_dropped"] == ref["ref_dropped"] == 0
    assert ref["overflow_train_steps"] == 0
    assert notes[0]["loss_fell"]
    assert result["correct"] and result["failed"] == 0
    rec = result["record"]
    assert rec["window_compiles"] == 0 and rec["rows_per_step"] == 2
    # the step runs the chunked scan, never the token recurrence, and
    # every expert layer the held-share path
    assert linear_attention._CORE_TOTAL.value(path="chunked") > chunked
    assert linear_attention._CORE_TOTAL.value(path="recurrent") == recurrent
    assert moe._DISPATCH_TOTAL.value(path="sorted_held") > held


def test_cell_reports_the_new_readers_beside_the_shared_ones():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    names = {m["name"] for m in cells.metrics_of(bench, "per_layer", cell)}
    assert set(NEW) | {"mla_ms_per_step", "mla_flash_roofline",
                       "moe_held_gemm_roofline", "recompute_ms_per_step",
                       "lm_head_ms_per_step", "moe_ms_per_step",
                       "moe_dispatch_ms_per_step"} <= names
    # no MTP module here; and the equal-width rooflines would take hidden /
    # heads = 72 as the head width
    assert not {"mtp_ms_per_step", "flash_roofline",
                "moe_gemm_roofline"} & names
    for name in NEW:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_samples_per_s"
    assert not cells.index_gaps(bench)


def test_configuration_keeps_every_published_width(tmp_path):
    """Every number of the source's config is in the file under the same
    key, but the three the cut lists; the cut's arithmetic is the model's."""
    sizes = _sizes()
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "moe_intermediate_size": 1024, "num_attention_heads": 32,
        "num_experts": 256, "num_experts_per_token": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-5, "routed_scaling_factor": 2.446,
        "v_head_dim": 128, "mla_use_nope": True, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid"}
    assert {k: sizes[k] for k in published} == published
    linear = sizes["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (32, 128, 4)
    assert len(linear["kda_layers"]) == 20 and linear["full_attn_layers"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert sizes["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert (sizes["num_hidden_layers"], sizes["n_routed_experts"],
            sizes["vocab_size"]) == (5, 8, 20480)
    assert sizes["published"] == {"num_hidden_layers": 27,
                                  "n_routed_experts": 256,
                                  "vocab_size": 163840}
    # the reader's names say what the source's do
    assert sizes["router_experts"] == sizes["num_experts"]
    assert sizes["num_experts_per_tok"] == sizes["num_experts_per_token"]
    assert sizes["held_experts"] == [0, sizes["n_routed_experts"]]
    # 602.4 M parameters, counted from the shapes the model would build
    h, inner, rank = 2304, 32 * 128, 128
    kda = (4 * h * inner + 2 * (h * rank + rank * inner) + h * 32
           + 3 * 4 * inner + 32 + inner + 128)
    mla = h * 32 * 192 + h * 576 + 512 + 512 * 32 * 256 + inner * h
    expert = 3 * h * 1024
    moe = h * 256 + 9 * expert
    total = (4 * kda + mla + 3 * h * 9216 + 4 * moe + 10 * h + h
             + 2 * 20480 * h)
    assert total == pytest.approx(602.4e6, rel=1e-3)
    assert "602.4 M parameters = 9.64 GB" in sizes["cut"]["arithmetic"]


def test_flops_per_sample_and_the_roofline_counts():
    config = cells.load_module("configs", CONFIG)
    reader = cells.load_module("layer_metrics", "kda_core_roofline")
    sizes = _sizes()
    seq, h = 16384, 2304
    assert config.mixer_counts(sizes) == (4, 1)
    assert config.block_counts(sizes) == (1, 4)
    # a KDA layer's matrices: 39.46 M multiply-adds a token
    assert config.kda_projection_flops_per_token(sizes) == 2 * (
        4 * h * 4096 + 2 * (h * 128 + 128 * 4096) + h * 32)
    # the scan: 142.6 kFLOP a token and head at d 128, chunk 64
    per_head = (2 * 128 * 65 + 2 * 64 * 64 / 3 + 65 * 256 + 6 * 128 * 128
                + 65 * 128)
    assert reader.kda_core_flops(1, 1, 128, 128, 64) == pytest.approx(
        per_head)
    assert per_head == pytest.approx(142.6e3, rel=1e-3)
    assert config.kda_core_flops_per_token(sizes) == pytest.approx(
        32 * per_head)
    # a backward pass is two forwards' worth
    assert reader.kda_core_flops(10, 32, 128, 128, 64, 2, 1) == pytest.approx(
        4 * 10 * 32 * per_head)
    # bytes a token and head: q, k, v in bf16, decay, beta and o in float32
    assert reader.kda_core_bytes(1, 1, 128, 128) == 2 * 384 + 4 * 257
    assert reader.kda_core_bytes(1, 1, 128, 128, 0, 1) == 2 * 1796 + 512
    # 79 FLOPs a byte: under the chip's 240, so the bytes bound the scan
    assert per_head / 1796 < 197e12 / 819e9
    # latent attention: four matrices, and the causal core at 16k
    assert config.mla_projection_flops_per_token(sizes) == 2 * (
        h * 6144 + h * 576 + 512 * 8192 + 4096 * h)
    core = config.mla_core_flops(sizes, seq)
    assert core == 2 * 32 * (seq * (seq + 1) // 2) * (192 + 128)
    assert core / seq == pytest.approx(167.8e6, rel=1e-3)
    # a token's 8 choices land on the 8 held of 256 experts a quarter time
    assert config.held_expert_flops_per_token(sizes) == (
        8 * 8 / 256 * 3 * 2 * h * 1024)
    # 42.1 TFLOP a step: nothing recomputed, only the held experts' rows,
    # the head over the held vocabulary slice
    flops = config.flops_per_sample(sizes, {"input_ids": (seq,)})
    per_token = (315.69e6 + 18.26e6 + 58.23e6 + 167.78e6 + 127.40e6
                 + 4 * (1.18e6 + 14.16e6 + 3.54e6) + 94.37e6)
    assert flops == pytest.approx(3 * seq * per_token, rel=2e-3)
    assert 42.0e12 < flops < 42.3e12
    held = cells.load_module("layer_metrics", "moe_held_gemm_roofline")
    assert held.expert_layers(sizes) == 4 and held.held_rows(
        sizes, seq) == 4096


# ---------------------------------------------------------- the readers
ROOT_SCOPE = "jit(train_step)/jvp(CausalLM)/lm:KimiLinearModel/"
BLOCK = ROOT_SCOPE + "checkpoint/layers:LayerList/1:JoyAIDecoderLayer/"
BWD = BLOCK.replace("jvp(CausalLM)", "transpose(jvp(CausalLM))")
REMAT = BWD.replace("checkpoint/", "checkpoint/rematted_computation/")
KDA, MLA = "self_attn:KimiDeltaAttention/", "self_attn:MLAttention/"


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
        ("%fusion.1", BLOCK + KDA + "kda.proj/q_proj:Linear/dot_general",
         0, 3000),
        ("%fusion.2", BLOCK + KDA + "kda.core/while/body/dot_general",
         3000, 8000),
        ("%fusion.3", BLOCK.replace("1:", "3:") + MLA
         + "mla.core/flash_stream_fwd", 11000, 9000),
        ("%fusion.4", REMAT + KDA + "kda.conv/q_conv:CausalDepthwiseConv1D/"
         "mul", 20000, 1000),
        ("%fusion.5", REMAT + KDA + "kda.core/while/body/dot_general",
         21000, 8000),
        ("%fusion.6", BWD + KDA + "kda.core/while/body/checkpoint/"
         "rematted_computation/dot_general", 29000, 6000),
        ("%fusion.7", BWD + KDA + "kda.core/while/body/transpose",
         35000, 14000),
        ("%fusion.8", BWD + KDA + "kda.out/o_proj:Linear/dot_general",
         49000, 2000),
        ("%fusion.9", "jit(train_step)/optimizer/add", 60000, 9000),
    ])
    assert read["kda_ms_per_step"](rec) == pytest.approx(42000 / 2e3)
    assert read["kda_core_ms_per_step"](rec) == pytest.approx(36000 / 2e3)
    # the trace shows a forward, a block's recomputed forward and a
    # backward; what the backward rebuilds of a segment is no pass
    reader = cells.load_module("layer_metrics", "kda_core_roofline")
    assert reader.passes(rec) == (2, 1)
    tokens = 4 * 16384                 # four KDA layers, one row
    least = reader.kda_core_bytes(tokens, 32, 128, 128, 2, 1) / 819e9
    assert least > reader.kda_core_flops(tokens, 32, 128, 128, 64, 2,
                                         1) / 197e12
    assert read["kda_core_roofline"](rec) == pytest.approx(
        100 * least / 0.018)
    # without a block's recomputation: one forward, one backward
    plain = _record([
        ("%fusion.2", BLOCK + KDA + "kda.core/while/body/dot_general",
         3000, 8000),
        ("%fusion.7", BWD + KDA + "kda.core/while/body/transpose",
         35000, 14000)])
    assert reader.passes(plain) == (1, 1)


def test_new_readers_find_nothing_on_a_program_without_the_layer():
    """A parent without the model, a JoyAI or BERT cell, a run without a
    trace: every new reader returns None and raises nothing."""
    joyai = ("jit(train_step)/jvp(CausalLM)/lm:JoyAIFlashModel/checkpoint/"
             "layers:LayerList/1:JoyAIDecoderLayer/self_attn:MLAttention/"
             "mla.core/flash_stream_fwd")
    rec = _record([("%flash_stream_fwd.1", joyai, 0, 1000)])
    rec["sizes"] = cells.config_sizes(cells.load_benchmark(),
                                      "joyai-llm-flash")
    empty = {"trace": None, "trace_steps": 2, "sizes": {}, "traffic": {}}
    for name in NEW:
        reader = cells.load_module("layer_metrics", name).read
        assert reader(rec) is None, name
        assert reader(dict(empty)) is None, name
    # the scan's scope in a program whose configuration is another's
    odd = _record([("%fusion.2", BLOCK + KDA + "kda.core/dot_general",
                    0, 1000)])
    odd["sizes"] = rec["sizes"]
    assert cells.load_module("layer_metrics",
                             "kda_core_roofline").read(odd) is None
