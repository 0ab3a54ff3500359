"""Rehearsal of the cell PR 44 adds, on the CPU: the LFM2 cell's driver end
to end at ``TOY`` width (the doubly gated short convolution in four layers
and grouped-query attention in one, the dense first layer, the sigmoid
router with a held share and no shared expert, a tied head, per-block
recomputation, the reference check in float32 and block by block under amp,
on two rows so that a row boundary is compared), the configuration file
against the catalog row, the mix against OLMoE's, the FLOP and byte
functions against hand counts, and the four new per-layer readers on a
recorded form of the trace. A rehearsal's numbers are never a metric."""
import copy
import json

import pytest

from benchmark.harness import cells, rehearsal, runner

CONFIG, TRAFFIC = "lfm2-8b-a1b", "lm-s8192-b4-conv"
CELL = "lfm2-8b-a1b.train-lm-s8192-b4"
NEW = ("shortconv_ms_per_step", "shortconv_stage_roofline",
       "attn64_ms_per_step", "attn64_flash_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def _stop_children():
    from paddle_tpu.distributed import topology

    topology.set_global_mesh(None)
    yield
    runner.stop_children()


def _sizes():
    return dict(cells.config_sizes(cells.load_benchmark(), CONFIG))


def _toy_traffic():
    toy = copy.deepcopy(cells.load_json("traffic", TRAFFIC))
    toy["fields"][0]["shape"] = [40]
    toy["trace_steps"] = 2
    return toy


def test_train_loop_lfm2_toy(tmp_path):
    from paddle_tpu.incubate import moe
    from paddle_tpu.ops import linear_attention

    config = cells.load_module("configs", CONFIG)
    held = moe._DISPATCH_TOTAL.value(path="sorted_held")
    conv = linear_attention._SHORTCONV_TOTAL.value(path="xla")
    result, notes = rehearsal.rehearse(CONFIG, _toy_traffic(), config.TOY,
                                       str(tmp_path), seconds=1.0)
    ref = notes[0]["reference_check"]
    assert ref["ok"], ref
    # two of the step's four rows: a row boundary lies inside the check
    assert ref["tokens"] == config.CHECK_ROWS * 40 == 80
    # float32 against float32: far inside the tolerance a bf16 run fails
    assert ref["f32_rel_err"] < 1e-5 < ref["f32_rtol"]
    assert ref["loss_f32_rel_err"] < 2e-6 < ref["loss_f32_rtol"]
    # the amp half really is bf16, is compared a block, and not on nothing
    assert ref["amp_rel_err"] > 10 * ref["f32_rel_err"]
    assert len(ref["amp_compared_share"]) == 5
    assert ref["amp_compared_share"][0] == 1.0     # the dense block: all
    assert min(ref["amp_compared_share"]) >= ref["amp_compared_min"]
    assert len(ref["held_pairs_landed"]) == 4      # four expert layers
    assert ref["f32_dropped"] == ref["amp_dropped"] == ref["ref_dropped"] == 0
    assert ref["overflow_train_steps"] == 0
    assert ref["ref_loss"] == ref["ref_ce"]        # no balance term
    assert notes[0]["loss_fell"]
    assert result["correct"] and result["failed"] == 0
    rec = result["record"]
    assert rec["window_compiles"] == 0 and rec["rows_per_step"] == 4
    # every expert layer took the held-share path and every conv layer's
    # stage its one path
    assert moe._DISPATCH_TOTAL.value(path="sorted_held") > held
    assert linear_attention._SHORTCONV_TOTAL.value(path="xla") > conv
    # the limits this configuration states are the ones its check ran on
    for name in config.LIMITS:
        assert getattr(config._kimi, name) == getattr(config, name), name
    assert (ref["f32_rtol"], ref["amp_rtol"]) == (config.F32_RTOL,
                                                  config.AMP_RTOL)


@pytest.mark.parametrize("how", ["--taps-shifted", "--gates-swapped",
                                 "--rows-joined"])
def test_a_broken_stage_fails_the_toy_check(tmp_path, how):
    """What ``tools/lfm2_check.py <how>`` shows on the chip, at the toy's
    widths: the program whose short-convolution stage has its taps a token
    late, its gates swapped, or its rows joined into one fails the check
    against the reference — rows joined at the second row's first tokens
    alone, so its blocks' MEDIANS stay bf16's."""
    from paddle_tpu.ops import linear_attention

    config = cells.load_module("configs", CONFIG)
    check = cells.load_module("tools", "lfm2_check")
    toy = _toy_traffic()
    toy["trace_steps"] = 1
    stage = linear_attention.gated_short_conv
    linear_attention.gated_short_conv = check.broken_stage(how)
    try:
        result, notes = rehearsal.rehearse(CONFIG, toy, config.TOY,
                                           str(tmp_path), seconds=0.2)
    finally:
        linear_attention.gated_short_conv = stage
    ref = notes[0]["reference_check"]
    assert not ref["ok"] and not result["correct"]
    assert ref["amp_rel_err"] > ref["amp_rtol"]
    assert ref["f32_rel_err_all_tokens"] > 100 * ref["f32_rtol"]
    conv_blocks = [ref["amp_block_medians"][i] for i in (0, 2, 3, 4)]
    if how == "--rows-joined":
        assert max(ref["amp_block_medians"]) < ref["amp_rtol"]
    else:
        assert min(conv_blocks) > ref["amp_rtol"]
        # the attention block has no stage: its median stays bf16's
        assert ref["amp_block_medians"][1] < ref["amp_rtol"]


def test_cell_reports_the_new_readers_beside_the_shared_ones():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert cell["config"] == CONFIG
    assert len(bench["workloads"]) >= 10 and len(bench["configs"]) >= 8
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    names = {m["name"] for m in cells.metrics_of(bench, "per_layer", cell)}
    assert set(NEW) | {"moe_held_gemm_roofline", "recompute_ms_per_step",
                       "lm_head_ms_per_step", "moe_ms_per_step",
                       "moe_dispatch_ms_per_step", "train_mfu_pct",
                       "hbm_compiled_gb", "step_ms_p50"} <= names
    ends = {m["name"] for m in cells.metrics_of(bench, "end_to_end", cell)}
    assert {"train_samples_per_s", "setup_s"} <= ends
    # no latent attention, no scan, no window, no MTP module; the other
    # attention rooflines are other widths' (``flash_roofline`` would
    # multiply by ``num_hidden_layers``)
    assert not {"mla_ms_per_step", "mla_flash_roofline", "kda_ms_per_step",
                "gdn_ms_per_step", "gqa_ms_per_step", "gqa_flash_roofline",
                "swa_ms_per_step", "swa_flash_roofline",
                "global_flash_roofline", "mtp_ms_per_step", "flash_roofline",
                "moe_gemm_roofline"} & names
    for name in NEW:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
        assert entry["moves"] == "train_samples_per_s"
        reader = cells.load_module("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert not cells.index_gaps(bench)
    # the mix is OLMoE's 4-row mix at twice the row but for what it reports
    # and why
    mine, theirs = (cells.load_json("traffic", t)
                    for t in (TRAFFIC, "lm-s4096-b4"))
    assert set(mine) == set(theirs)
    assert mine["fields"][0] == dict(theirs["fields"][0], shape=[8192])
    for key in mine:
        assert (mine[key] == theirs[key]) == (
            key not in ("reports", "why", "fields")), key
    assert (mine["rows_per_chip"], mine["probe_steps"],
            mine["trace_steps"]) == (4, 10, 6)
    assert mine["loader"] == {"num_workers": 2, "prefetch_factor": 2}
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    assert entry["reduced"] == _sizes()["reduced"]


def test_configuration_keeps_every_published_width():
    """Every number (and list) of the catalog row's config is in the file
    under the same key, but the four the cut lists; the cut's arithmetic is
    the model's."""
    sizes = _sizes()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert sizes["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if sizes.get(k) != v)
    assert differ == sorted(sizes["reduced"]) == [
        "num_dense_layers", "num_experts", "num_hidden_layers", "vocab_size"]
    assert (sizes["num_hidden_layers"], sizes["num_dense_layers"],
            sizes["num_experts"], sizes["vocab_size"]) == (5, 1, 8, 16384)
    assert sizes["published"] == {k: row["config"][k]
                                  for k in sizes["reduced"]}
    assert sizes["vocab_size"] * 4 == row["config"]["vocab_size"]
    for key in ("published", "reduced", "cut", "assumed", "departures"):
        assert sizes[key], key
    for key in ("deployment", "arithmetic", "distorts", "held_rows",
                "num_hidden_layers", "num_dense_layers", "num_experts",
                "vocab_size"):
        assert sizes["cut"][key], key
    for key in ("layer_equations", "head_dim", "tie_word_embeddings",
                "initializer_range", "train_recipe", "bias_update_speed",
                "held_rows_factor", "sequence", "weights"):
        assert sizes["assumed"][key], key
    # the published widths the acceptance names
    assert (sizes["hidden_size"], sizes["num_attention_heads"],
            sizes["head_dim"], sizes["num_key_value_heads"],
            sizes["intermediate_size"], sizes["moe_intermediate_size"],
            sizes["num_experts_per_tok"], sizes["conv_L_cache"],
            sizes["rope_theta"], sizes["routed_scaling_factor"]) == (
                2048, 32, 64, 8, 7168, 1792, 4, 3, 1000000, 1)
    assert sizes["hidden_size"] == (sizes["num_attention_heads"]
                                    * sizes["head_dim"])
    # the layers run: published layer 0, then one whole period, 1 : 3
    config = cells.load_module("configs", CONFIG)
    assert sizes["run_layers"] == [0, 2, 3, 4, 5]
    assert config.layer_types(sizes) == ["conv", "full_attention", "conv",
                                         "conv", "conv"]
    assert sizes["layer_types"][1] == "conv"      # the other dense layer
    assert config.mixer_counts(sizes) == (4, 1)
    assert config.block_counts(sizes) == (1, 4)
    # the readers' names say what the source's do
    assert sizes["router_experts"] == row["config"]["num_experts"] == 32
    assert sizes["n_routed_experts"] == sizes["num_experts"]
    assert sizes["held_experts"] == [0, sizes["n_routed_experts"]]
    assert sizes["first_k_dense_replace"] == sizes["num_dense_layers"]
    # 507.8 M parameters, counted from the shapes the model would build
    h = 2048
    conv = h * 3 * h + h * h + 3 * h
    attn = 2 * h * h + 2 * h * 512 + 2 * 64
    expert = 3 * h * 1792
    moe = h * 32 + 8 * expert
    total = (4 * conv + attn + 5 * 2 * h + 3 * h * 7168 + 4 * moe + h
             + 16384 * h)
    assert conv == pytest.approx(16.78e6, rel=1e-3)
    assert attn == pytest.approx(10.49e6, rel=1e-3)
    assert conv + 3 * h * 7168 + 2 * h == pytest.approx(60.83e6, rel=1e-3)
    assert attn + moe + 2 * h == pytest.approx(98.64e6, rel=1e-3)
    assert conv + moe + 2 * h == pytest.approx(104.93e6, rel=1e-3)
    assert total == pytest.approx(507.8e6, rel=1e-3)
    assert "507.8 M parameters = 8.1 GB" in sizes["cut"]["arithmetic"]
    assert "4 chips share each layer" in sizes["cut"]["deployment"]


def test_toy_model_has_the_parameters_the_arithmetic_counts():
    """The constructor at published widths would build what the cut's
    arithmetic counts: checked on the shapes, at the toy's widths and the
    cell's five layers; the tied head adds nothing."""
    config = cells.load_module("configs", CONFIG)
    sizes = dict(_sizes(), **config.TOY)
    from paddle_tpu.text.models import Lfm2Model

    net = Lfm2Model(**config.model_kwargs(sizes))
    assert net.layer_types == config.layer_types(sizes)
    counted = sum(int(p.size) for p in net.parameters())
    h = 64
    conv = h * 3 * h + h * h + 3 * h
    attn = 2 * h * h + 2 * h * 32 + 2 * 16
    moe = h * 32 + 8 * 3 * h * 32
    assert counted == (4 * conv + attn + 5 * 2 * h + 3 * h * 96 + 4 * moe
                       + h + 256 * h)


def test_flops_per_sample_and_the_roofline_counts():
    config = cells.load_module("configs", CONFIG)
    stage = cells.load_module("layer_metrics", "shortconv_stage_roofline")
    core = cells.load_module("layer_metrics", "attn64_flash_roofline")
    sizes = _sizes()
    seq, h = 8192, 2048
    # two matrices a short convolution: 16.78 M multiply-adds a token
    assert config.shortconv_flops_per_token(sizes) == 2 * (3 * h * h + h * h)
    # four matrices the attention sublayer: 10.49 M multiply-adds a token
    assert config.attention_projection_flops_per_token(sizes) == 2 * (
        2 * h * h + 2 * h * 512)
    # the core over the 32 QUERY heads of 64 on the causal triangle
    assert config.core_flops(sizes, seq) == 2 * 32 * (
        seq * (seq + 1) // 2) * 128
    assert config.core_flops(sizes, seq) / seq == pytest.approx(33.56e6,
                                                                rel=1e-3)
    assert core.core_flops(1, 32, seq, 64, 1, 1) == pytest.approx(
        3.5 * config.core_flops(sizes, seq))
    # K and V move on their 8 heads: the repeat is no work and no byte
    assert core.core_bytes(1, 32, 8, seq, 64, 1, 0) == seq * 64 * 2 * 80
    # a token's 4 choices land on the 8 held of 32 experts once
    assert config.held_expert_flops_per_token(sizes) == 3 * 2 * h * 1792
    # 42.6 TFLOP a step of 4 rows: nothing recomputed, only the held
    # experts' rows, the tied head over the held vocabulary slice
    flops = config.flops_per_sample(sizes, {"input_ids": (seq,)})
    per_token = (134.22e6 + 20.97e6 + 33.56e6 + 88.08e6 + 0.52e6 + 88.08e6
                 + 67.11e6)
    assert flops == pytest.approx(3 * seq * per_token, rel=2e-3)
    assert 42.4e12 < 4 * flops < 42.8e12
    # the stage's least bytes: 4 streams forward, 7 backward, bf16
    assert stage.stage_bytes(1, 2048, 1, 0) == 2048 * 2 * 4
    assert stage.stage_bytes(1, 2048, 0, 1) == 2048 * 2 * 7
    assert stage.conv_layers(sizes) == 4
    assert stage.stage_bytes(4 * 4 * seq, 2048, 2, 1) == pytest.approx(
        8.05e9, rel=1e-3)
    # the accepted held-gemm roofline's functions read THIS file's widths
    # and counts, not another configuration's
    held = cells.load_module("layer_metrics", "moe_held_gemm_roofline")
    assert held.expert_layers(sizes) == 4
    assert held.held_rows(sizes, 4 * seq) == 32768
    assert held.held_gemm_flops(sizes, 4 * seq, 1) == pytest.approx(
        4 * 3 * 2 * h * 1792 * 32768)
    assert held.held_gemm_bytes(sizes, 4 * seq, 1) == pytest.approx(
        4 * 2 * (3 * 8 * h * 1792 + 32768 * (2 * (h + 1792) + 1792 + h)))


# ---------------------------------------------------------- the readers
ROOT_SCOPE = "jit(train_step)/jvp(CausalLM)/lm:Lfm2Model/"
BLOCK = ROOT_SCOPE + "checkpoint/layers:LayerList/2:Lfm2DecoderLayer/"
BWD = BLOCK.replace("jvp(CausalLM)", "transpose(jvp(CausalLM))")
REMAT = BWD.replace("checkpoint/", "checkpoint/rematted_computation/")
CONV = "conv:Lfm2ShortConv/"
ATTN = "self_attn:Lfm2Attention/"
FULL, FULL_BWD = (b.replace("2:", "1:") + ATTN for b in (BLOCK, BWD))


def _record(events, steps=2):
    """A traced slice of ``steps`` steps in ``program_trace``'s loaded form;
    ``events``: (event name, op_name, start_us, duration_us)."""
    return {
        "program_trace": {"planes": [{"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops",
            "events": [[n, s * 1e3, d * 1e3, op] for n, op, s, d in events],
        }]}]},
        "trace_steps": steps, "rows_per_step": 4, "chips": 1,
        "sizes": _sizes(), "traffic": cells.load_json("traffic", TRAFFIC),
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_new_readers_on_a_recorded_trace():
    read = {n: cells.load_module("layer_metrics", n).read for n in NEW}
    stage = cells.load_module("layer_metrics", "shortconv_stage_roofline")
    rec = _record([
        ("%fusion.1", BLOCK + CONV + "shortconv.in_proj/in_proj:Linear/"
         "dot_general", 0, 3000),
        ("%fusion.2", BLOCK + CONV + "shortconv.stage/checkpoint/mul",
         3000, 4000),
        ("%fusion.3", BLOCK + CONV + "shortconv.out_proj/out_proj:Linear/"
         "dot_general", 7000, 1000),
        ("%fusion.4", BLOCK + "ffn_norm:ZeroCenteredRMSNorm/mul", 8000, 700),
        ("%fusion.5", FULL + "lfm2attn.proj/q_proj:Linear/dot_general",
         10000, 1500),
        ("%flash_stream_fwd.1", FULL + "lfm2attn.core/flash_stream_fwd/"
         "pallas_call", 12000, 20000),
        # XLA's relayout of the kernel's result, named after its operand:
        # time under the kernel's name, but no call
        ("%reduce.1", FULL + "lfm2attn.core/flash_stream_fwd/pallas_call",
         32000, 500),
        ("%fusion.6", FULL + "lfm2attn.repeat/repeat", 32500, 400),
        # the block's recomputed forward, then the stage's backward with
        # the float32 it rebuilds under its own checkpoint
        ("%fusion.7", REMAT + CONV + "shortconv.stage/checkpoint/mul",
         50000, 4000),
        ("%fusion.8", BWD + CONV + "shortconv.stage/checkpoint/"
         "rematted_computation/mul", 54000, 3000),
        ("%fusion.9", BWD + CONV + "shortconv.stage/checkpoint/mul",
         57000, 9000),
        ("%flash_stream_bwd_dkv_dq.1", FULL_BWD + "lfm2attn.core/"
         "flash_stream_bwd_dkv_dq/pallas_call", 70000, 44000),
        ("%fusion.10", FULL_BWD + "lfm2attn.out/out_proj:Linear/dot_general",
         114000, 2000),
        ("%fusion.11", "jit(train_step)/optimizer/add", 120000, 9000),
    ])
    assert read["shortconv_ms_per_step"](rec) == pytest.approx(24000 / 2e3)
    assert read["attn64_ms_per_step"](rec) == pytest.approx(68400 / 2e3)
    # a forward, the block's recomputed forward and one backward (the
    # stage's own rebuilt float32 is part of it) over the 20 ms under the
    # stage's scope
    assert stage.passes(rec) == (2, 1)
    least = stage.stage_bytes(4 * 4 * 8192, 2048, 2, 1) / 819e9
    assert read["shortconv_stage_roofline"](rec) == pytest.approx(
        100 * least / 0.010)
    assert read["shortconv_stage_roofline"](rec) < 100
    # ONE forward call in the slice (the relayout is none) and one backward
    gqa = cells.load_module("layer_metrics", "gqa_flash_roofline")
    assert gqa.calls(rec, "flash_stream_fwd") == 1
    assert gqa.calls(rec, "flash_stream_bwd_dkv") == 1
    flops = gqa.core_flops(4, 32, 8192, 64, 0.5, 0.5)
    assert read["attn64_flash_roofline"](rec) == pytest.approx(
        100 * flops / 0.03225 / 197e12)
    assert read["attn64_flash_roofline"](rec) < 100
    # a stage without the block's recomputation counts one forward less
    plain = _record([e for e in (
        ("%fusion.2", BLOCK + CONV + "shortconv.stage/checkpoint/mul",
         0, 4000),
        ("%fusion.9", BWD + CONV + "shortconv.stage/checkpoint/mul",
         5000, 9000))])
    assert stage.passes(plain) == (1, 1)


@pytest.mark.parametrize("name", ["shortconv_stage_roofline",
                                  "attn64_flash_roofline"])
def test_a_share_over_100_would_show(name):
    """The readers cap nothing: a trace whose events under the scope, or
    under the kernel's name, take less time than the chip's peak allows
    reads over 100 — the driver refuses it, and the fault (bytes or FLOPs
    counted too high, or time that leaves out part of the work) is not
    hidden."""
    read = cells.load_module("layer_metrics", name).read
    rec = _record([
        ("%fusion.2", BLOCK + CONV + "shortconv.stage/checkpoint/mul",
         0, 1000),
        ("%flash_stream_fwd.1", FULL + "lfm2attn.core/flash_stream_fwd/"
         "pallas_call", 2000, 1000)], steps=1)
    assert read(rec) > 100


def test_new_readers_find_nothing_on_a_program_without_the_layers():
    """A parent without the model, a Qwen3-Next or BERT cell, a run without
    a trace: every new reader returns None and raises nothing."""
    qwen = ("jit(train_step)/jvp(CausalLM)/lm:Qwen3NextModel/checkpoint/"
            "layers:LayerList/3:Qwen3NextDecoderLayer/self_attn:"
            "GatedGQAttention/gqa.core/flash_stream_fwd/pallas_call")
    rec = _record([("%flash_stream_fwd.1", qwen, 0, 1000)])
    rec["sizes"] = cells.config_sizes(cells.load_benchmark(),
                                      "qwen3-next-80b-a3b")
    rec["traffic"] = cells.load_json("traffic", "lm-s16384-b1-gdn")
    empty = {"trace": None, "trace_steps": 2, "sizes": {}, "traffic": {}}
    for name in NEW:
        reader = cells.load_module("layer_metrics", name).read
        assert reader(rec) is None, name
        assert reader(dict(empty)) is None, name
        assert reader({}) is None, name
    # this configuration's own program with the scopes missing (a trace
    # that names nothing): nothing to read either
    bare = _record([("%fusion.1", "jit(train_step)/jvp(CausalLM)/mul", 0,
                     1000)])
    for name in NEW:
        assert cells.load_module("layer_metrics", name).read(bare) is None
