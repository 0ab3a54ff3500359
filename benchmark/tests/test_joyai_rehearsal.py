"""Rehearsal of the cell PR 29 adds, on the CPU: the JoyAI-LLM-Flash cell's
driver end to end at ``TOY`` width (latent attention, the sigmoid router
with a held share, the shared expert, the MTP module, per-block
recomputation, the two-term loss, the reference check in float32 and block
by block under amp), the FLOP and byte functions against hand counts, and
the five new per-layer readers on a recorded form of the trace. A
rehearsal's numbers are never a metric."""
import copy

import numpy as np

import pytest

from benchmark.harness import cells, rehearsal, runner

CONFIG, TRAFFIC = "joyai-llm-flash", "lm-s8192-b1"
CELL = "joyai-llm-flash.train-lm-s8192"


@pytest.fixture(autouse=True)
def _stop_children():
    from paddle_tpu.distributed import topology

    topology.set_global_mesh(None)
    yield
    runner.stop_children()


def _sizes():
    return dict(cells.config_sizes(cells.load_benchmark(), CONFIG))


def test_train_loop_joyai_toy(tmp_path):
    from paddle_tpu.incubate import moe

    config = cells.load_module("configs", CONFIG)
    toy = copy.deepcopy(cells.load_json("traffic", TRAFFIC))
    toy["rows_per_chip"] = 2
    toy["fields"][0]["shape"] = [32]
    toy["trace_steps"] = 2
    held_before = moe._DISPATCH_TOTAL.value(path="sorted_held")
    others = {p: moe._DISPATCH_TOTAL.value(path=p)
              for p in ("sorted", "capacity", "dense")}
    result, notes = rehearsal.rehearse(CONFIG, toy, config.TOY,
                                       str(tmp_path), seconds=1.0)
    ref = notes[0]["reference_check"]
    assert ref["ok"], ref
    # float32 against float32: far inside the tolerance a bf16 run fails
    assert ref["f32_rel_err"] < 1e-5 < ref["f32_rtol"]
    assert ref["loss_f32_rel_err"] < 2e-6 < ref["loss_f32_rtol"]
    # the amp half really is bf16, is compared a block, and not on nothing
    assert ref["amp_rel_err"] > 10 * ref["f32_rel_err"]
    assert len(ref["amp_compared_share"]) == 4      # 3 blocks + MTP
    assert ref["amp_compared_share"][0] == 1.0      # the dense block: all
    assert min(ref["amp_compared_share"]) >= ref["amp_compared_min"]
    assert ref["f32_dropped"] == ref["amp_dropped"] == ref["ref_dropped"] == 0
    assert ref["overflow_train_steps"] == 0
    assert notes[0]["loss_fell"]
    assert result["correct"] and result["failed"] == 0
    rec = result["record"]
    assert rec["window_compiles"] == 0 and rec["rows_per_step"] == 2
    # every traced expert layer took the held-share path and no other
    assert moe._DISPATCH_TOTAL.value(path="sorted_held") > held_before
    assert all(moe._DISPATCH_TOTAL.value(path=p) == n
               for p, n in others.items())


def _compare_with_errors(config, errs, margin):
    """``compare`` on float32 logits that are off by ``errs`` a token (a
    share of the largest reference logit, 1.0) and nothing else wrong;
    the row goes on to 40 decided tokens that are off as the last one is."""
    pad = 40 - len(errs)
    errs = np.asarray(errs + [errs[-1]] * pad)[None]
    margin = np.asarray(margin + [1.0] * pad)[None]
    logits = np.zeros(errs.shape + (2,), np.float32)
    logits[..., 0] = 1.0
    got = logits.copy()
    got[..., 1] = errs
    one = np.float32(1.0)
    ref = (logits, [logits], one, one, one, 0, margin, [4])
    got32 = (got, [logits], one, one, one, 0)
    block = (np.zeros(errs.shape), np.ones(errs.shape))
    return config.compare(ref, got32, (logits, [logits], one, one, one, 0),
                          [block])


@pytest.mark.parametrize("errs, margin, ok, share", [
    # no swap: every decided token counts
    ([1e-6, 1e-6, 1e-6, 1e-6], [1.0, 1e-7, 1.0, 1.0], True, 39 / 40),
    # an undecided token swapped: the tokens after it read it, and the
    # decided ones among them may be over the bound with nothing wrong
    ([1e-6, 6e-2, 3e-5, 1e-6], [1.0, 1e-7, 1.0, 1.0], True, 1 / 40),
    # a decided token over the bound before any swap is a fault
    ([3e-5, 6e-2, 1e-6, 1e-6], [1.0, 1e-7, 1.0, 1.0], False, 1 / 40),
    # ... and so is one with no swap anywhere
    ([1e-6, 1e-6, 1e-6, 3e-5], [1.0, 1e-7, 1.0, 1.0], False, 39 / 40),
    # a swap at the row's first token leaves nothing to compare
    ([6e-2, 1e-6, 1e-6, 1e-6], [1e-7, 1.0, 1.0, 1.0], False, 0.0),
    # bf16 arithmetic: over the bound from the first token on, and at the
    # median of all tokens, wherever its first undecided token cuts the row
    ([6e-3, 6e-3, 6e-3, 6e-3], [1.0, 1e-7, 1.0, 1.0], False, 1 / 40),
])
def test_float32_logits_are_compared_on_the_clean_prefix(errs, margin, ok,
                                                         share):
    config = cells.load_module("configs", CONFIG)
    out = _compare_with_errors(config, errs, margin)
    assert out["ok"] is ok, out
    assert out["f32_compared_share"] == pytest.approx(share)
    assert out["f32_rel_err_all_tokens"] == pytest.approx(max(errs))
    assert out["f32_undecided_share"] == pytest.approx(1 / 40)
    # the loss terms are read whatever the logits said
    assert out["loss_f32_rel_err"] == out["loss_amp_rel_err"] == 0.0


def test_cell_reports_the_new_readers_and_not_the_equal_width_rooflines():
    bench = cells.load_benchmark()
    names = {m["name"] for m in cells.metrics_of(
        bench, "per_layer", cells.find_cell(bench, CELL))}
    assert {"mla_ms_per_step", "mla_flash_roofline", "moe_held_gemm_roofline",
            "mtp_ms_per_step", "recompute_ms_per_step", "lm_head_ms_per_step",
            "moe_ms_per_step", "moe_dispatch_ms_per_step"} <= names
    # those take hidden / heads as the head width and intermediate_size as
    # the expert width: 64 and 7168 here, an impossible reading
    assert not {"flash_roofline", "moe_gemm_roofline"} & names


def test_flops_per_sample_and_the_roofline_counts():
    config = cells.load_module("configs", CONFIG)
    sizes = _sizes()
    seq, h = 8192, 2048
    # MLA's seven matrices: 26.35 M multiply-adds a token
    assert config.mla_projection_flops_per_token(sizes) == 2 * (
        2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
        + 4096 * 2048)
    # the causal core: 83.9 MFLOP a token and block at 8,192 positions
    core = config.mla_core_flops(sizes, seq)
    assert core == 2 * 32 * (seq * (seq + 1) // 2) * (192 + 128)
    assert core / seq == pytest.approx(83.9e6, rel=1e-3)
    # a token's 8 choices land on the 16 held of 256 experts half a time
    assert config.held_expert_flops_per_token(sizes) == (
        8 * 16 / 256 * 3 * 2 * h * 768)
    # 27.84 TFLOP a step: nothing recomputed, only the held experts' rows,
    # the head twice over the held vocabulary slice
    flops = config.flops_per_sample(sizes, {"input_ids": (seq,)})
    per_token = (6 * 52.69e6 + 6 * 83.9e6 + 88.08e6
                 + 5 * (1.05e6 + 9.44e6 + 4.72e6) + 16.78e6 + 2 * 66.19e6)
    assert flops == pytest.approx(3 * seq * per_token, rel=2e-3)
    assert 27.7e12 < flops < 27.9e12
    assert config.block_counts(sizes) == (1, 5)

    flash = cells.load_module("layer_metrics", "mla_flash_roofline")
    pairs = seq * (seq + 1) // 2
    assert flash.core_flops(1, 32, seq, 192, 128, 2, 1) == 2 * 32 * pairs * (
        2 * (192 + 128) + 3 * 192 + 2 * 128)
    assert flash.core_bytes(1, 32, seq, 192, 128, 2, 1) == 32 * seq * 2 * (
        2 * 640 + 1280)
    held = cells.load_module("layer_metrics", "moe_held_gemm_roofline")
    assert held.expert_layers(sizes) == 5 and held.held_rows(
        sizes, seq) == 4096
    assert held.held_gemm_flops(sizes, seq, 4) == 4 * 5 * 3 * 2 * h * 768 * (
        4096)
    # weight-bound: 16 experts' 75.5 MB of bf16 against 4,096 rows
    assert held.held_gemm_bytes(sizes, seq, 1) == 5 * 2 * (
        3 * 16 * h * 768 + 4096 * (2 * (h + 768) + 768 + h))
    assert (held.held_gemm_bytes(sizes, seq) / 819e9
            > held.held_gemm_flops(sizes, seq) / 197e12)


# ---------------------------------------------------------- the readers
ROOT_SCOPE = "jit(train_step)/jvp(CausalLM)/lm:JoyAIFlashModel/"
BLOCK = ROOT_SCOPE + "checkpoint/layers:LayerList/1:JoyAIDecoderLayer/"
BWD = BLOCK.replace("jvp(CausalLM)", "transpose(jvp(CausalLM))")
REMAT = BWD.replace("checkpoint/", "checkpoint/rematted_computation/")
MTP = (ROOT_SCOPE + "checkpoint/mtp:LayerList/0:MultiTokenPredictor/"
       "block:JoyAIDecoderLayer/")


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
    names = ("mla_ms_per_step", "mla_flash_roofline",
             "moe_held_gemm_roofline", "mtp_ms_per_step",
             "recompute_ms_per_step")
    read = {n: cells.load_module("layer_metrics", n).read for n in names}
    attn, moe = "self_attn:MLAttention/", "mlp:MoELayer/"
    rec = _record([
        ("%fusion.1", BLOCK + attn + "mla.q/q_b_proj:Linear/dot_general",
         0, 2000),
        ("%flash_stream_fwd.1", BLOCK + attn + "mla.core/flash_stream_fwd",
         2000, 8000),
        ("%gmm.1", BLOCK + moe + "moe.experts/gmm", 10000, 1000),
        ("%fusion.2", MTP + attn + "mla.out/o_proj:Linear/dot_general",
         11000, 3000),
        ("%flash_stream_fwd.2", REMAT + attn + "mla.core/flash_stream_fwd",
         20000, 8000),
        ("%gmm.2", REMAT + moe + "moe.experts/gmm", 28000, 1000),
        ("%flash_stream_bwd_dkv.1", BWD + attn
         + "mla.core/flash_stream_bwd_dkv", 30000, 16000),
        ("%flash_stream_bwd_dq.1", BWD + attn
         + "mla.core/flash_stream_bwd_dq", 46000, 12000),
        ("%gmm.3", BWD + moe + "moe.experts/tgmm", 58000, 2000),
        ("%fusion.9", "jit(train_step)/optimizer/add", 60000, 9000),
    ])
    assert read["mla_ms_per_step"](rec) == pytest.approx(49000 / 2e3)
    assert read["mtp_ms_per_step"](rec) == pytest.approx(3000 / 2e3)
    assert read["recompute_ms_per_step"](rec) == pytest.approx(9000 / 2e3)
    # the trace shows 2 forward calls and 1 backward in 2 steps
    flash = cells.load_module("layer_metrics", "mla_flash_roofline")
    flops = flash.core_flops(1, 32, 8192, 192, 128, 1.0, 0.5)
    assert read["mla_flash_roofline"](rec) == pytest.approx(
        100 * flops / 0.022 / 197e12)
    # a recomputed forward under moe.experts: four passes, the byte bound
    held = cells.load_module("layer_metrics", "moe_held_gemm_roofline")
    least = held.held_gemm_bytes(rec["sizes"], 8192, 4) / 819e9
    assert read["moe_held_gemm_roofline"](rec) == pytest.approx(
        100 * least / 0.002)


def test_new_readers_find_nothing_on_a_program_without_the_layers():
    """A parent without the model, a BERT or OLMoE cell, a run without a
    trace: every new reader returns None and raises nothing."""
    olmoe = ("jit(train_step)/jvp(CausalLM)/lm:OlmoeModel/layers:LayerList/"
             "0:OlmoeDecoderLayer/mlp:MoELayer/moe.experts/gmm")
    rec = _record([("%gmm.1", olmoe, 0, 1000)])
    rec["sizes"] = cells.config_sizes(cells.load_benchmark(), "olmoe-1b-7b")
    empty = {"trace": None, "trace_steps": 2, "sizes": {}, "traffic": {}}
    for name in ("mla_ms_per_step", "mla_flash_roofline",
                 "moe_held_gemm_roofline", "mtp_ms_per_step",
                 "recompute_ms_per_step"):
        reader = cells.load_module("layer_metrics", name).read
        assert reader(rec) is None, name
        assert reader(dict(empty)) is None, name
