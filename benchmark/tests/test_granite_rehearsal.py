"""Rehearsal of the cell PR 47 adds, on the CPU: the granite-4.0-h-micro
cell's driver end to end at ``TOY`` width (Mamba-2's state-space mixer in
nine layers and grouped-query attention without positions in one, a dense
SwiGLU in every one, a tied head behind the divisor, per-block
recomputation, the reference check in float32 and block by block under amp,
the model in pieces), the three broken programs ``tools/granite_check.py``
shows to fail, the configuration file against the catalog row, the mix
against JoyAI's, the FLOP and byte functions against hand counts, and the
six new per-layer readers on a recorded form of the trace. A rehearsal's
numbers are never a metric."""
import copy
import json

import pytest

from benchmark.harness import cells, rehearsal, runner

CONFIG, TRAFFIC = "granite-4.0-h-micro", "lm-s8192-b1-ssm"
CELL = "granite-4.0-h-micro.train-lm-s8192-b1"
NEW = ("mamba_ms_per_step", "ssd_core_ms_per_step", "ssd_core_roofline",
       "ssm_conv_stage_roofline", "attn64_nope_ms_per_step",
       "attn64_nope_flash_roofline")
ROOFLINES = tuple(n for n in NEW if n.endswith("_roofline"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KINDS = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


@pytest.fixture(autouse=True)
def _stop_children():
    from paddle_tpu.distributed import topology

    topology.set_global_mesh(None)
    yield
    runner.stop_children()


def _sizes():
    return dict(cells.config_sizes(cells.load_benchmark(), CONFIG))


def _toy_traffic(trace_steps=2):
    toy = copy.deepcopy(cells.load_json("traffic", TRAFFIC))
    toy["fields"][0]["shape"] = [40]
    toy["trace_steps"] = trace_steps
    return toy


def test_train_loop_granite_toy(tmp_path):
    from paddle_tpu.ops import attention, linear_attention

    config = cells.load_module("configs", CONFIG)
    scans = linear_attention._SSD_TOTAL.value(path="chunked")
    convs = linear_attention._CONV_TOTAL.value(path="xla")
    xla = attention._ROUTE_TOTAL.value(route="xla")
    result, notes = rehearsal.rehearse(CONFIG, _toy_traffic(), config.TOY,
                                       str(tmp_path), seconds=1.0)
    ref = notes[0]["reference_check"]
    assert ref["ok"], ref
    assert ref["tokens"] == 40
    # float32 against float32: far inside the tolerance a bf16 run fails
    assert ref["f32_rel_err"] < 1e-5 < ref["f32_rtol"]
    assert ref["loss_f32_rel_err"] < 2e-6 < ref["loss_f32_rtol"]
    # the amp half really is bf16 and is compared a block, every block
    assert ref["amp_rel_err"] > 10 * ref["f32_rel_err"]
    assert len(ref["amp_block_worst"]) == len(ref["amp_block_medians"]) == 10
    assert ref["amp_rel_err"] == max(ref["amp_block_worst"])
    assert ref["loss_amp_rel_err"] < ref["loss_amp_rtol"]
    assert notes[0]["loss_fell"]
    assert result["correct"] and result["failed"] == 0
    rec = result["record"]
    assert rec["window_compiles"] == 0 and rec["rows_per_step"] == 1
    # the step's nine scans and biased stages took their one path each (and
    # the check's pieces theirs); the 40-token core goes XLA's route here
    assert linear_attention._SSD_TOTAL.value(path="chunked") >= scans + 9
    assert linear_attention._CONV_TOTAL.value(path="xla") >= convs + 9
    assert attention._ROUTE_TOTAL.value(route="xla") > xla
    assert (ref["f32_rtol"], ref["amp_rtol"], ref["loss_f32_rtol"],
            ref["loss_amp_rtol"]) == tuple(
                getattr(config, name) for name in config.LIMITS)


@pytest.mark.parametrize("how", ["--gate-after-norm", "--no-conv-bias",
                                 "--scale-eighth"])
def test_a_broken_program_fails_the_toy_check(how):
    """What ``tools/granite_check.py <how>`` shows on the chip, at the toy's
    widths: the program whose mamba blocks gate after the norm or drop the
    convolution's bias, or whose attention block scores at d ** -0.5, fails
    the check against the reference — block by block on the blocks of that
    kind and no other, and the float32 half with it."""
    import jax
    import numpy as np

    config = cells.load_module("configs", CONFIG)
    check = cells.load_module("tools", "granite_check")
    reference = cells.load_module("references", CONFIG)
    sizes = dict(_sizes(), **config.TOY)
    built = config.build_train(3, sizes, {"input_ids": (40,)})
    x = jax.numpy.asarray(np.random.default_rng(3).integers(
        0, sizes["vocab_size"], (1, 40)), "int32")
    with check.broken_program(how, built):
        ref = config.check_train(built, reference, sizes, None, x)
    assert not ref["ok"]
    assert ref["f32_rel_err"] > 100 * ref["f32_rtol"]
    over = [worst > ref["amp_rtol"] for worst in ref["amp_block_worst"]]
    assert over == [kind == check.BROKEN[how] for kind in KINDS]
    # and the program is itself again afterwards
    assert config.check_train(built, reference, sizes, None, x)["ok"]


def test_cell_reports_the_new_readers_beside_the_shared_ones():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert cell["config"] == CONFIG
    assert len(bench["workloads"]) >= 11 and len(bench["configs"]) >= 9
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    names = {m["name"] for m in cells.metrics_of(bench, "per_layer", cell)}
    assert set(NEW) | {"recompute_ms_per_step", "lm_head_ms_per_step",
                       "train_mfu_pct", "hbm_compiled_gb", "step_ms_p50",
                       "optimizer_ms_per_step"} <= names
    ends = {m["name"] for m in cells.metrics_of(bench, "end_to_end", cell)}
    assert {"train_samples_per_s", "setup_s"} <= ends
    # no experts, no delta rule, no LFM2 stage, no window, no MTP; the other
    # attention rooflines are other widths' or other programs'
    assert not {n for n in names if n.startswith(("moe_", "kda_", "gdn_",
                                                  "mla_", "swa_", "gqa_",
                                                  "shortconv_", "mtp_"))}
    assert not {"attn64_ms_per_step", "attn64_flash_roofline",
                "flash_roofline", "global_flash_roofline"} & names
    for name in NEW:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        reader = cells.load_module("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"])
        assert entry["moves"] == "train_samples_per_s"
        assert entry["better"] == ("higher" if name in ROOFLINES
                                   else "lower")
    assert not cells.index_gaps(bench)
    # the mix is JoyAI's one-row 8k mix but for what it reports and why
    mine, theirs = (cells.load_json("traffic", t)
                    for t in (TRAFFIC, "lm-s8192-b1"))
    assert set(mine) == set(theirs)
    for key in mine:
        assert (mine[key] == theirs[key]) == (
            key not in ("reports", "why")), key
    assert (mine["rows_per_chip"], mine["probe_steps"],
            mine["trace_steps"]) == (1, 10, 6)
    assert mine["fields"] == [{"name": "input_ids", "shape": [8192],
                               "dtype": "int32", "draw": "uniform_int",
                               "low": 0, "high": "vocab_size"}]
    assert mine["loader"] == {"num_workers": 2, "prefetch_factor": 2}
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    assert entry["reduced"] == _sizes()["reduced"]


def test_configuration_keeps_every_published_width():
    """Every number (and list) of the catalog row's config is in the file
    under the same key, but the two the cut lists; the cut's arithmetic is
    the model's."""
    sizes = _sizes()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert sizes["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if sizes.get(k) != v)
    assert differ == sorted(sizes["reduced"]) == ["num_hidden_layers",
                                                  "vocab_size"]
    assert (sizes["num_hidden_layers"], sizes["vocab_size"]) == (10, 12544)
    assert sizes["published"] == {k: row["config"][k]
                                  for k in sizes["reduced"]}
    assert sizes["vocab_size"] * 8 == row["config"]["vocab_size"]
    for key in ("published", "reduced", "cut", "assumed", "departures"):
        assert sizes[key], key
    for key in ("deployment", "arithmetic", "distorts", "num_hidden_layers",
                "vocab_size"):
        assert sizes["cut"][key], key
    for key in ("layer_equations", "head_dim", "time_step_limit",
                "time_step", "initializer_range", "train_recipe", "sequence",
                "weights"):
        assert sizes["assumed"][key], key
    # the published widths the acceptance names
    assert (sizes["hidden_size"], sizes["mamba_n_heads"],
            sizes["mamba_d_head"], sizes["mamba_d_state"],
            sizes["mamba_n_groups"], sizes["mamba_d_conv"],
            sizes["mamba_conv_bias"], sizes["num_attention_heads"],
            sizes["head_dim"], sizes["num_key_value_heads"],
            sizes["attention_multiplier"], sizes["intermediate_size"],
            sizes["embedding_multiplier"], sizes["residual_multiplier"],
            sizes["logits_scaling"], sizes["tie_word_embeddings"],
            sizes["position_embedding_type"]) == (
                2048, 64, 64, 128, 1, 4, True, 32, 64, 8, 1 / 64, 8192, 12,
                0.22, 8, True, "nope")
    assert sizes["mamba_expand"] * sizes["hidden_size"] == (
        sizes["mamba_n_heads"] * sizes["mamba_d_head"])
    assert sizes["num_local_experts"] == 0
    assert sizes["shared_intermediate_size"] == sizes["intermediate_size"]
    # the layers run: published layers 0-9, one whole period at 9 : 1
    config = cells.load_module("configs", CONFIG)
    assert len(sizes["layer_types"]) == 40
    assert config.layer_types(sizes) == KINDS
    assert config.mixer_counts(sizes) == (9, 1)
    # 772.2 M parameters, counted from the shapes the model would build
    h = 2048
    mixer = h * 8512 + 4096 * h + 4352 * 5 + 3 * 64 + 4096
    attn = 2 * h * h + 2 * h * 512
    mlp = 3 * h * 8192
    total = 9 * (mixer + mlp + 2 * h) + attn + mlp + 2 * h + 12544 * h + h
    assert mixer == pytest.approx(25.85e6, rel=1e-3)
    assert attn == pytest.approx(10.49e6, rel=1e-3)
    assert mixer + mlp + 2 * h == pytest.approx(76.18e6, rel=1e-3)
    assert attn + mlp + 2 * h == pytest.approx(60.82e6, rel=1e-3)
    assert total == pytest.approx(772.2e6, rel=1e-3)
    assert 16 * total == pytest.approx(12.35e9, rel=1e-3)
    assert "772.2 M parameters = 12.35 GB" in sizes["cut"]["arithmetic"]
    assert "four stages of ten layers" in sizes["cut"]["deployment"]


def test_toy_model_has_the_parameters_the_arithmetic_counts():
    """The constructor at published widths would build what the cut's
    arithmetic counts: checked on the shapes, at the toy's widths and the
    cell's ten layers; the tied head adds nothing."""
    config = cells.load_module("configs", CONFIG)
    sizes = dict(_sizes(), **config.TOY)
    from paddle_tpu.text.models import GraniteHybridModel

    net = GraniteHybridModel(**config.model_kwargs(sizes))
    assert net.layer_types == config.layer_types(sizes) == KINDS
    counted = sum(int(p.size) for p in net.parameters())
    h, inner, state = 64, 128, 32
    conv = inner + 2 * state
    mixer = h * (inner + conv + 8) + inner * h + conv * 5 + 3 * 8 + inner
    attn = 2 * h * h + 2 * h * 32
    mlp = 3 * h * 96
    assert counted == (9 * (mixer + mlp + 2 * h) + attn + mlp + 2 * h
                       + 256 * h + h)


def test_flops_per_sample_and_the_roofline_counts():
    config = cells.load_module("configs", CONFIG)
    scan = cells.load_module("layer_metrics", "ssd_core_roofline")
    stage = cells.load_module("layer_metrics", "ssm_conv_stage_roofline")
    core = cells.load_module("layer_metrics", "attn64_nope_flash_roofline")
    sizes = _sizes()
    seq, h = 8192, 2048
    # two matrices a state-space mixer: 25.82 M multiply-adds a token
    assert config.mamba_projection_flops_per_token(sizes) == 2 * (
        h * 8512 + 4096 * h)
    # the scan at chunks of 256: the ONE group's pair product on the
    # triangle, 64 heads' masked products, the state's update and read
    assert (sizes["mamba_chunk"], sizes["mamba_segment"]) == (256, 2048)
    assert config.ssd_core_flops_per_token(sizes) == (
        128 * 257 + 64 * (64 * 257 + 4 * 128 * 64))
    assert scan.ssd_core_flops(10, 64, 64, 128, 1, 256, 2, 1) == 4 * 10 * (
        config.ssd_core_flops_per_token(sizes))
    # B and C move once a group, never a copy a head
    assert scan.ssd_core_bytes(1, 64, 64, 128, 1, 1, 0) == 64 * (
        2 * 64 * 2 + 4) + 2 * 128 * 2
    assert scan.ssd_core_bytes(1, 64, 64, 128, 1, 0, 1) == 64 * (
        3 * 64 * 2 + 8) + 4 * 128 * 2
    assert scan.ssd_core_bytes(1, 64, 64, 128, 8, 1, 0) - scan.ssd_core_bytes(
        1, 64, 64, 128, 1, 1, 0) == 7 * 2 * 128 * 2
    # four matrices the attention sublayer: 10.49 M multiply-adds a token
    assert config.attention_projection_flops_per_token(sizes) == 2 * (
        2 * h * h + 2 * h * 512)
    assert config.core_flops(sizes, seq) == 2 * 32 * (
        seq * (seq + 1) // 2) * 128
    assert core.core_flops(1, 32, seq, 64, 1, 1) == pytest.approx(
        3.5 * config.core_flops(sizes, seq))
    # 39.47 TFLOP a step of one row: nothing recomputed, ten dense SwiGLUs,
    # the tied head over the held vocabulary slice
    flops = config.flops_per_sample(sizes, {"input_ids": (seq,)})
    per_token = (464.78e6 + 28.64e6 + 20.97e6 + 33.56e6 + 1006.63e6
                 + 51.38e6)
    assert flops == pytest.approx(3 * seq * per_token, rel=1e-3)
    assert 39.3e12 < flops < 39.6e12
    # the stage's least bytes: 2 streams forward, 3 backward, bf16
    assert stage.stage_bytes(1, 4352, 1, 0) == 4352 * 2 * 2
    assert stage.stage_bytes(1, 4352, 0, 1) == 4352 * 2 * 3


# ---------------------------------------------------------- the readers
ROOT_SCOPE = "jit(train_step)/jvp(CausalLM)/lm:GraniteHybridModel/"
BLOCK = (ROOT_SCOPE + "checkpoint/layers:LayerList/"
         "2:GraniteHybridDecoderLayer/")
BWD = BLOCK.replace("jvp(CausalLM)", "transpose(jvp(CausalLM))")
REMAT = BWD.replace("checkpoint/", "checkpoint/rematted_computation/")
MAMBA = "mamba:Mamba2Mixer/"
ATTN = "self_attn:GraniteAttention/"
FULL, FULL_BWD = (b.replace("2:", "5:") + ATTN for b in (BLOCK, BWD))


def _record(events, steps=2):
    """A traced slice of ``steps`` steps in ``program_trace``'s loaded form;
    ``events``: (event name, op_name, start_us, duration_us)."""
    return {
        "program_trace": {"planes": [{"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops",
            "events": [[n, s * 1e3, d * 1e3, op] for n, op, s, d in events],
        }]}]},
        "trace_steps": steps, "rows_per_step": 1, "chips": 1,
        "sizes": _sizes(), "traffic": cells.load_json("traffic", TRAFFIC),
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_new_readers_on_a_recorded_trace():
    read = {n: cells.load_module("layer_metrics", n).read for n in NEW}
    scan = cells.load_module("layer_metrics", "ssd_core_roofline")
    stage = cells.load_module("layer_metrics", "ssm_conv_stage_roofline")
    rec = _record([
        ("%fusion.1", BLOCK + MAMBA + "mamba.in_proj/in_proj:Linear/"
         "dot_general", 0, 3000),
        ("%fusion.2", BLOCK + MAMBA + "mamba.conv/checkpoint/mul", 3000,
         4000),
        ("%fusion.3", BLOCK + MAMBA + "mamba.dt/softplus", 7000, 100),
        ("%fusion.4", BLOCK + MAMBA + "mamba.core/while/body/checkpoint/"
         "dot_general", 7100, 6000),
        ("%fusion.5", BLOCK + MAMBA + "mamba.norm/checkpoint/mul", 13100,
         900),
        ("%fusion.6", BLOCK + "post_attention_layernorm:"
         "ZeroCenteredRMSNorm/mul", 14000, 700),
        ("%fusion.7", FULL + "gattn64.proj/q_proj:Linear/dot_general",
         15000, 1500),
        ("%flash_stream_fwd.1", FULL + "gattn64.core/flash_stream_fwd/"
         "pallas_call", 17000, 5000),
        # XLA's relayout of the kernel's result, named after its operand:
        # time under the kernel's name, but no call
        ("%reduce.1", FULL + "gattn64.core/flash_stream_fwd/pallas_call",
         22000, 200),
        ("%fusion.8", FULL + "gattn64.repeat/repeat", 22200, 300),
        # the block's recomputed forward, then the backward with the
        # segments the scan rebuilds under its own checkpoint
        ("%fusion.9", REMAT + MAMBA + "mamba.conv/checkpoint/mul", 30000,
         4000),
        ("%fusion.10", REMAT + MAMBA + "mamba.core/while/body/checkpoint/"
         "dot_general", 34000, 6000),
        ("%fusion.11", BWD + MAMBA + "mamba.core/while/body/checkpoint/"
         "rematted_computation/dot_general", 40000, 6000),
        ("%fusion.12", BWD + MAMBA + "mamba.core/while/body/checkpoint/"
         "dot_general", 46000, 12000),
        ("%fusion.13", BWD + MAMBA + "mamba.conv/checkpoint/mul", 58000,
         8000),
        ("%flash_stream_bwd_dkv_dq.1", FULL_BWD + "gattn64.core/"
         "flash_stream_bwd_dkv_dq/pallas_call", 70000, 11000),
        ("%fusion.14", FULL_BWD + "gattn64.out/o_proj:Linear/dot_general",
         81000, 2000),
        ("%fusion.15", "jit(train_step)/optimizer/add", 90000, 9000),
    ])
    assert read["mamba_ms_per_step"](rec) == pytest.approx(50000 / 2e3)
    assert read["ssd_core_ms_per_step"](rec) == pytest.approx(30000 / 2e3)
    assert read["attn64_nope_ms_per_step"](rec) == pytest.approx(
        20000 / 2e3)
    # a forward, the block's recomputed forward and one backward (the
    # scan's own rebuilt segments are part of it)
    assert scan.passes(rec) == (2, 1)
    assert scan.passes(rec, stage.SCOPE) == (2, 1)
    tokens = 9 * 8192
    assert scan.mamba_tokens(rec) == tokens
    least = max(scan.ssd_core_flops(tokens, 64, 64, 128, 1, 256, 2, 1)
                / 197e12,
                scan.ssd_core_bytes(tokens, 64, 64, 128, 1, 2, 1) / 819e9)
    assert read["ssd_core_roofline"](rec) == pytest.approx(
        100 * least / 0.015)
    # the bytes bound it: 186 FLOPs a byte under the chip's 240
    assert least == scan.ssd_core_bytes(tokens, 64, 64, 128, 1, 2, 1) / 819e9
    assert 0 < read["ssd_core_roofline"](rec) < 100
    least = stage.stage_bytes(tokens, 4352, 2, 1) / 819e9
    assert read["ssm_conv_stage_roofline"](rec) == pytest.approx(
        100 * least / 0.008)
    assert 0 < read["ssm_conv_stage_roofline"](rec) < 100
    # ONE forward call in the slice (the relayout is none) and one backward
    gqa = cells.load_module("layer_metrics", "gqa_flash_roofline")
    assert gqa.calls(rec, "flash_stream_fwd") == 1
    assert gqa.calls(rec, "flash_stream_bwd_dkv") == 1
    flops = gqa.core_flops(1, 32, 8192, 64, 0.5, 0.5)
    assert read["attn64_nope_flash_roofline"](rec) == pytest.approx(
        100 * flops / 0.0081 / 197e12)
    assert 0 < read["attn64_nope_flash_roofline"](rec) < 100


@pytest.mark.parametrize("name", ROOFLINES)
def test_a_share_over_100_would_show(name):
    """The readers cap nothing: a trace whose events under the scope, or
    under the kernel's name, take less time than the chip's peak allows
    reads over 100 — the driver refuses it, and the fault (bytes or FLOPs
    counted too high, or time that leaves out part of the work) is not
    hidden."""
    read = cells.load_module("layer_metrics", name).read
    rec = _record([
        ("%fusion.2", BLOCK + MAMBA + "mamba.conv/checkpoint/mul", 0, 100),
        ("%fusion.4", BLOCK + MAMBA + "mamba.core/dot_general", 200, 100),
        ("%flash_stream_fwd.1", FULL + "gattn64.core/flash_stream_fwd/"
         "pallas_call", 2000, 100)], steps=1)
    assert read(rec) > 100


def test_new_readers_find_nothing_on_a_program_without_the_layers():
    """A parent without the model, an LFM2 or BERT cell, a run without a
    trace: every new reader returns None and raises nothing."""
    lfm2 = ("jit(train_step)/jvp(CausalLM)/lm:Lfm2Model/checkpoint/"
            "layers:LayerList/1:Lfm2DecoderLayer/self_attn:Lfm2Attention/"
            "lfm2attn.core/flash_stream_fwd/pallas_call")
    rec = _record([("%flash_stream_fwd.1", lfm2, 0, 1000)])
    rec["sizes"] = cells.config_sizes(cells.load_benchmark(), "lfm2-8b-a1b")
    rec["traffic"] = cells.load_json("traffic", "lm-s8192-b4-conv")
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
