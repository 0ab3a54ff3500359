"""Rehearsal of the cell PR 51 adds, on the CPU: the
nemotron-3-super-120b-a12b cell's driver end to end at ``TOY`` width (eleven
one-sublayer layers ``MEMEMEMEM*E`` and the MTP module's two, every layer a
share — 4 of 8 state-space heads, 2 of 8 query heads, 8 of 32 relu² experts
in a latent space —, per-layer recomputation, the reference check in float32
and layer by layer under amp, the model in pieces), the configuration file
against the catalog row, the cut's arithmetic against the shapes the model
builds, the FLOP and byte functions against hand counts, and the three new
per-layer readers on a recorded form of the trace. A rehearsal's numbers are
never a metric."""
import copy
import json

import pytest

from benchmark.harness import cells, rehearsal, runner

CONFIG, TRAFFIC = "nemotron-3-super-120b-a12b", "lm-s4096-b1-latentmoe"
CELL = "nemotron-3-super-120b-a12b.train-lm-s4096-b1"
NEW = ("latent_moe_gemm_roofline", "latent_proj_ms_per_step",
       "shared_expert_ms_per_step")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KINDS = ["mamba", "moe"] * 4 + ["mamba", "attention", "moe"]
REDUCED = ["num_hidden_layers", "n_routed_experts", "mamba_num_heads",
           "n_groups", "num_attention_heads", "num_key_value_heads",
           "vocab_size"]


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


def test_train_loop_nemotron_toy(tmp_path):
    from paddle_tpu.incubate import moe
    from paddle_tpu.ops import linear_attention

    config = cells.load_module("configs", CONFIG)
    layers = moe._LAYER_TOTAL.value(activation="relu2", latent="32")
    held = moe._DISPATCH_TOTAL.value(path="sorted_held")
    scans = linear_attention._SSD_TOTAL.value(path="chunked")
    result, notes = rehearsal.rehearse(CONFIG, _toy_traffic(), config.TOY,
                                       str(tmp_path), seconds=1.0)
    ref = notes[0]["reference_check"]
    assert ref["ok"], ref
    assert ref["tokens"] == 40
    # float32 against float32: far inside the tolerance a bf16 run fails
    assert ref["f32_rel_err"] < 1e-5 < ref["f32_rtol"]
    assert ref["loss_f32_rel_err"] < 2e-6 < ref["loss_f32_rtol"]
    assert ref["mtp_ce_f32_rel_err"] < 2e-6 and ref["ref_mtp_ce"] > 0
    # the amp half really is bf16 and is compared a layer, every layer:
    # eleven and the module's two
    assert ref["amp_rel_err"] > 10 * ref["f32_rel_err"]
    assert len(ref["amp_block_worst"]) == len(ref["amp_block_medians"]) == 13
    assert ref["amp_rel_err"] == max(ref["amp_block_worst"])
    assert min(ref["amp_compared_share"]) >= ref["amp_compared_min"]
    # a layer without a router compares every token
    assert [s == 1.0 for s in ref["amp_compared_share"]] == [
        kind != "moe" for kind in KINDS + ["attention", "moe"]]
    # six expert layers' held pairs, none dropped, now or in the steps
    assert len(ref["held_pairs_landed"]) == 6
    assert (ref["ref_dropped"], ref["f32_dropped"], ref["amp_dropped"],
            ref["overflow_train_steps"]) == (0, 0, 0, 0)
    assert notes[0]["loss_fell"]
    assert result["correct"] and result["failed"] == 0
    rec = result["record"]
    assert rec["window_compiles"] == 0 and rec["rows_per_step"] == 1
    # the step's six LatentMoE layers and five scans took their one path
    # each (and the check's pieces theirs)
    assert moe._LAYER_TOTAL.value(activation="relu2",
                                  latent="32") >= layers + 6
    assert moe._DISPATCH_TOTAL.value(path="sorted_held") >= held + 6
    assert linear_attention._SSD_TOTAL.value(path="chunked") >= scans + 5
    assert (ref["f32_rtol"], ref["amp_rtol"], ref["loss_f32_rtol"],
            ref["loss_amp_rtol"]) == tuple(
                getattr(config, name) for name in config.LIMITS)


def test_a_broken_program_fails_the_toy_check(monkeypatch):
    """The program with relu for relu² in its experts, or with the gated
    norm over all features where the model norms a group, fails the check
    against the reference — layer by layer on the layers of that kind and
    no other, and the float32 half with it."""
    import jax
    import numpy as np
    from paddle_tpu.incubate import moe

    config = cells.load_module("configs", CONFIG)
    reference = cells.load_module("references", CONFIG)
    sizes = dict(_sizes(), **config.TOY)
    built = config.build_train(3, sizes, {"input_ids": (40,)})
    x = jax.numpy.asarray(np.random.default_rng(3).integers(
        0, sizes["vocab_size"], (1, 40)), "int32")
    monkeypatch.setattr(moe, "_relu2", jax.nn.relu)
    ref = config.check_train(built, reference, sizes, None, x)
    assert not ref["ok"]
    assert ref["f32_rel_err"] > 100 * ref["f32_rtol"]
    over = [worst > ref["amp_rtol"] for worst in ref["amp_block_worst"]]
    assert over == [kind == "moe" for kind in KINDS + ["attention", "moe"]]
    monkeypatch.undo()
    assert config.check_train(built, reference, sizes, None, x)["ok"]


def test_cell_reports_the_new_readers_beside_the_shared_ones():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert cell["config"] == CONFIG
    assert len(bench["workloads"]) >= 12 and len(bench["configs"]) >= 10
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    names = {m["name"] for m in cells.metrics_of(bench, "per_layer", cell)}
    assert set(NEW) | {
        "recompute_ms_per_step", "lm_head_ms_per_step", "mtp_ms_per_step",
        "moe_ms_per_step", "moe_dispatch_ms_per_step", "mamba_ms_per_step",
        "ssd_core_ms_per_step", "ssd_core_roofline",
        "ssm_conv_stage_roofline", "train_mfu_pct", "hbm_compiled_gb",
        "step_ms_p50", "optimizer_ms_per_step", "host_gc_ms_per_step",
        "host_stall_ms_max"} <= names
    ends = {m["name"] for m in cells.metrics_of(bench, "end_to_end", cell)}
    assert {"train_samples_per_s", "setup_s"} <= ends
    # its experts are two matrices in a latent space and it has no leading
    # dense layer: the three-matrix reader is not this cell's; nor are the
    # other mixers' or the other attention widths'
    assert "moe_held_gemm_roofline" not in names
    assert "moe_gemm_roofline" not in names
    assert not {n for n in names if n.startswith((
        "kda_", "gdn_", "mla_", "swa_", "gqa_", "shortconv_", "attn64_"))}
    for name in NEW:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        reader = cells.load_module("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"])
        assert entry["layer"] == "expert layer (incubate/moe.py)"
        assert entry["better"] == ("higher" if name.endswith("_roofline")
                                   else "lower")
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW)
    assert bench["workloads"][-1] is cell
    assert bench["configs"][-1]["name"] == CONFIG
    assert not cells.index_gaps(bench)
    # the mix is the other one-row LM mixes' but for its length, what it
    # reports and why
    mine, theirs = (cells.load_json("traffic", t)
                    for t in (TRAFFIC, "lm-s8192-b1"))
    assert set(mine) == set(theirs)
    for key in mine:
        assert (mine[key] == theirs[key]) == (
            key not in ("reports", "why", "fields")), key
    assert mine["fields"] == [{"name": "input_ids", "shape": [4096],
                               "dtype": "int32", "draw": "uniform_int",
                               "low": 0, "high": "vocab_size"}]
    assert (mine["rows_per_chip"], mine["probe_steps"],
            mine["trace_steps"]) == (1, 10, 6)
    assert mine["loader"] == {"num_workers": 2, "prefetch_factor": 2}
    entry = bench["configs"][-1]
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    assert entry["reduced"] == _sizes()["reduced"] == REDUCED


def test_configuration_keeps_every_published_width():
    """Every number (and string) of the catalog row's config is in the file
    under the same key, but the seven counts the cut lists; no width is
    among them; the cut's arithmetic is the model's."""
    sizes = _sizes()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert sizes["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if sizes.get(k) != v)
    assert differ == sorted(sizes["reduced"]) == sorted(REDUCED)
    assert sizes["published"] == {k: row["config"][k] for k in REDUCED}
    assert [sizes[k] for k in REDUCED] == [11, 8, 16, 1, 4, 1, 16384]
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for key in ("published", "reduced", "cut", "assumed", "departures"):
        assert sizes[key], key
    for key in ("deployment", "arithmetic", "distorts", "num_hidden_layers",
                "n_routed_experts", "mamba_num_heads, n_groups",
                "num_attention_heads, num_key_value_heads", "vocab_size"):
        assert sizes["cut"][key], key
    # the published widths the acceptance names
    assert (sizes["hidden_size"], sizes["mamba_head_dim"],
            sizes["ssm_state_size"], sizes["conv_kernel"], sizes["head_dim"],
            sizes["moe_latent_size"], sizes["moe_intermediate_size"],
            sizes["moe_shared_expert_intermediate_size"],
            sizes["router_experts"], sizes["num_experts_per_tok"],
            sizes["routed_scaling_factor"], sizes["mlp_hidden_act"],
            sizes["use_conv_bias"], sizes["tie_word_embeddings"]) == (
                4096, 64, 128, 4, 128, 1024, 2688, 5376, 512, 22, 5, "relu2",
                True, False)
    # what the reused readers and the reference read: the HELD values
    assert (sizes["mamba_n_heads"], sizes["mamba_d_head"],
            sizes["mamba_d_state"], sizes["mamba_n_groups"],
            sizes["mamba_chunk"]) == (16, 64, 128, 1, 256)
    assert (sizes["held_experts"], sizes["held_mamba_heads"],
            sizes["held_attention_heads"]) == ([0, 8], [0, 16], [0, 4])
    # the layers run: published layers 27-37, one whole period at 5 : 5 : 1
    config = cells.load_module("configs", CONFIG)
    pattern = sizes["hybrid_override_pattern"]
    assert pattern == row["config"]["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (88, 40, 40, 8)
    assert sizes["run_layers"] == [27, 38]
    assert pattern[27:38] == "MEMEMEMEM*E"
    assert config.layer_types(sizes) == KINDS == sizes["layer_types"]
    assert config.kind_counts(sizes) == {"mamba": 5, "attention": 2,
                                         "moe": 6}
    kw = config.model_kwargs(sizes)
    assert (kw["mamba_num_heads"], kw["n_groups"], kw["held_mamba_heads"],
            kw["num_attention_heads"], kw["num_key_value_heads"],
            kw["held_attention_heads"], kw["n_routed_experts"],
            kw["held_experts"], kw["hybrid_override_pattern"]) == (
                128, 8, (0, 16), 32, 2, (0, 4), 512, (0, 8), "MEMEMEMEM*E")
    # 838.2 M parameters, counted from the shapes the model would build
    h = 4096
    mamba = h * (1024 + 1280 + 16) + 1024 * h + 1280 * 5 + 3 * 16 + 1024 + h
    attn = 2 * h * 512 + 2 * h * 128 + h
    expert = (2 * 8 * 1024 * 2688 + h * 512 + 2 * h * 1024 + 2 * h * 5376
              + h)
    mtp = 2 * h * h + 3 * h + attn + expert
    total = 5 * mamba + attn + 5 * expert + 2 * 16384 * h + h + mtp
    assert mamba == pytest.approx(13.71e6, rel=1e-3)
    assert attn == pytest.approx(5.25e6, rel=1e-3)
    assert expert == pytest.approx(98.57e6, rel=1e-3)
    assert mtp == pytest.approx(137.4e6, rel=1e-3)
    assert total == pytest.approx(838.2e6, rel=1e-4)
    assert 16 * total == pytest.approx(13.41e9, rel=1e-3)
    assert "838.2 M parameters = 13.41 GB" in sizes["cut"]["arithmetic"]
    # the published model by the same formulae: 120.7 B, 12.8 B active
    whole_mamba = h * 18560 + 8192 * h
    whole_attn = 2 * h * h + 2 * h * 256
    one = 2 * 1024 * 2688
    around = h * 512 + 2 * h * 1024 + 2 * h * 5376
    assert (40 * whole_mamba + 40 * (512 * one + around) + 8 * whole_attn
            + 2 * 131072 * h) == pytest.approx(120.7e9, rel=2e-3)
    assert (40 * whole_mamba + 40 * (22 * one + around) + 8 * whole_attn
            + 2 * 131072 * h) == pytest.approx(12.8e9, rel=3e-3)


def test_toy_model_has_the_parameters_the_arithmetic_counts():
    """The constructor builds what the cut's arithmetic counts: checked on
    the shapes, at the toy's widths and the cell's eleven layers and
    module."""
    config = cells.load_module("configs", CONFIG)
    sizes = dict(_sizes(), **config.TOY)
    from paddle_tpu.text.models import NemotronHModel

    net = NemotronHModel(**config.model_kwargs(sizes))
    assert net.layer_types == config.layer_types(sizes) == KINDS
    counted = sum(int(p.size) for p in net.parameters())
    h, inner, state = 64, 64, 32
    conv = inner + 2 * state
    mamba = h * (inner + conv + 4) + inner * h + conv * 5 + 3 * 4 + inner + h
    attn = 2 * h * 32 + 2 * h * 16 + h
    expert = 2 * 8 * 32 * 48 + h * 32 + 2 * h * 32 + 2 * h * 96 + h
    mtp = 2 * h * h + 3 * h + attn + expert
    assert counted == (5 * mamba + attn + 5 * expert + 2 * 256 * h + h
                       + mtp)
    with pytest.raises(ValueError, match="not the counts the file states"):
        config.model_kwargs(dict(sizes, held_experts=[0, 4]))


def test_flops_per_sample_and_the_roofline_counts():
    config = cells.load_module("configs", CONFIG)
    gemm = cells.load_module("layer_metrics", "latent_moe_gemm_roofline")
    sizes = _sizes()
    seq, h = 4096, 4096
    assert config.mamba_projection_flops_per_token(sizes) == 2 * (
        h * 2320 + 1024 * h)
    assert config.ssd_core_flops_per_token(sizes) == (
        128 * 257 + 16 * (64 * 257 + 4 * 128 * 64))
    assert config.attention_flops_per_token(sizes, seq) == 2 * (
        2 * h * 512 + 2 * h * 128) + 4 * 2 * 2 * 128 * (seq + 1) / 2
    around, shared, held = config.latent_moe_flops_per_token(sizes)
    assert around == 2 * h * 512 + 2 * 2 * h * 1024
    assert shared == 2 * 2 * h * 5376
    assert held == 22 * 8 / 512 * 2 * 2 * 1024 * 2688
    # 1,183 MFLOP a token forward, 14.5 TFLOP a step: nothing recomputed
    flops = config.flops_per_sample(sizes, {"input_ids": (seq,)})
    per_token = (5 * 28.2e6 + 6 * 88.08e6 + 6 * 24.75e6 + 2 * 134.22e6
                 + 67.11e6 + 2 * 14.68e6)
    assert flops == pytest.approx(3 * seq * per_token, rel=2e-3)
    assert 14.4e12 < flops < 14.7e12
    # six expert layers; 1,408 pairs land here a layer, 176 an expert
    assert gemm.expert_layers(sizes) == 6
    assert gemm.held_rows(sizes, seq) == 1408
    # TWO matrices of 1,024 x 2,688, not three of hidden x width
    assert gemm.latent_gemm_flops(sizes, seq, 1, 0) == (
        6 * 2 * 2 * 1024 * 2688 * 1408)
    assert gemm.latent_gemm_flops(sizes, seq, 2, 1) == 4 * (
        gemm.latent_gemm_flops(sizes, seq, 1, 0))
    assert gemm.latent_gemm_bytes(sizes, seq, 1, 0) == 6 * 2 * (
        2 * 8 * 1024 * 2688 + 1408 * 2 * (1024 + 2688))
    # weight-bound: 142 FLOPs a byte under the chip's 240
    assert gemm.latent_gemm_flops(sizes, seq) / gemm.latent_gemm_bytes(
        sizes, seq) == pytest.approx(142, rel=0.02)


# ---------------------------------------------------------- the readers
ROOT_SCOPE = "jit(train_step)/jvp(CausalLM)/lm:NemotronHModel/"
LAYER = ROOT_SCOPE + "checkpoint/layers:LayerList/1:NemotronHLayer/"
BWD = LAYER.replace("jvp(CausalLM)", "transpose(jvp(CausalLM))")
REMAT = BWD.replace("checkpoint/", "checkpoint/rematted_computation/")
MOE = "mixer:MoELayer/"
MTP = (ROOT_SCOPE + "checkpoint/mtp:LayerList/0:MultiTokenPredictor/"
       "block:Sequential/1:NemotronHLayer/" + MOE)


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
    gemm = cells.load_module("layer_metrics", "latent_moe_gemm_roofline")
    scan = cells.load_module("layer_metrics", "ssd_core_roofline")
    rec = _record([
        ("%fusion.1", LAYER + MOE + "latentmoe.down/latent_down:Linear/"
         "dot_general", 0, 300),
        ("%fusion.2", LAYER + MOE + "moe.route/top_k", 300, 900),
        ("%fusion.3", LAYER + MOE + "moe.dispatch/gather", 1200, 200),
        ("%gmm.1", LAYER + MOE + "moe.experts/gmm/pallas_call", 1400, 400),
        ("%fusion.4", LAYER + MOE + "moe.experts/integer_pow", 1800, 50),
        ("%gmm.2", LAYER + MOE + "moe.experts/gmm/pallas_call", 1850, 400),
        ("%fusion.5", LAYER + MOE + "moe.combine/mul", 2250, 150),
        ("%fusion.6", LAYER + MOE + "latentmoe.up/latent_up:Linear/"
         "dot_general", 2400, 300),
        ("%fusion.7", LAYER + MOE + "moe.shared/shared:Relu2MLP/"
         "up_proj:Linear/dot_general", 2700, 1000),
        ("%fusion.8", LAYER + MOE + "moe.shared/shared:Relu2MLP/"
         "down_proj:Linear/dot_general", 3700, 1000),
        ("%gmm.3", MTP + "moe.experts/gmm/pallas_call", 5000, 850),
        ("%fusion.9", MTP + "moe.shared/add", 5850, 100),
        # the layer's recomputed forward, then its backward
        ("%gmm.4", REMAT + MOE + "moe.experts/gmm/pallas_call", 10000, 850),
        ("%fusion.10", REMAT + MOE + "latentmoe.down/latent_down:Linear/"
         "dot_general", 10850, 300),
        ("%tgmm.1", BWD + MOE + "moe.experts/tgmm/pallas_call", 12000, 1700),
        ("%fusion.11", BWD + MOE + "latentmoe.up/latent_up:Linear/"
         "dot_general", 13700, 600),
        ("%fusion.12", BWD + MOE + "moe.shared/shared:Relu2MLP/"
         "down_proj:Linear/dot_general", 14300, 4000),
        ("%fusion.13", "jit(train_step)/optimizer/add", 20000, 9000),
    ], steps=1)
    assert read["latent_proj_ms_per_step"](rec) == pytest.approx(1500 / 1e3)
    assert read["shared_expert_ms_per_step"](rec) == pytest.approx(
        6100 / 1e3)
    # a forward, the layer's recomputed forward and one backward
    assert scan.passes(rec, gemm.SCOPE) == (2, 1)
    sizes = _sizes()
    least = max(gemm.latent_gemm_flops(sizes, 4096, 2, 1) / 197e12,
                gemm.latent_gemm_bytes(sizes, 4096, 2, 1) / 819e9)
    assert least == gemm.latent_gemm_bytes(sizes, 4096, 2, 1) / 819e9
    # 850 + 850 + 850 + 1,700 us under the scope in the one step
    assert read["latent_moe_gemm_roofline"](rec) == pytest.approx(
        100 * least / 4250e-6)
    assert 0 < read["latent_moe_gemm_roofline"](rec) < 100
    # the three-matrix reader is not asked of this cell, and would count
    # another layer: the reason the cell does not report it
    old = cells.load_module("layer_metrics", "moe_held_gemm_roofline")
    with pytest.raises(KeyError, match="first_k_dense_replace"):
        old.read(rec)


def test_a_share_over_100_would_show():
    """The reader caps nothing: a trace whose gemms take less time than the
    chip's peak allows reads over 100 — the driver refuses it, and the fault
    (bytes counted too high, or time that leaves out part of the work) is
    not hidden."""
    read = cells.load_module("layer_metrics", "latent_moe_gemm_roofline").read
    rec = _record([("%gmm.1", LAYER + MOE + "moe.experts/gmm/pallas_call", 0,
                    100)], steps=1)
    assert read(rec) > 100


def test_new_readers_find_nothing_on_a_program_without_the_layers():
    """A parent without the model, a JoyAI or BERT cell, a run without a
    trace: every new reader returns None and raises nothing."""
    joyai = ("jit(train_step)/jvp(CausalLM)/lm:JoyAIFlashModel/checkpoint/"
             "layers:LayerList/1:JoyAIDecoderLayer/mlp:MoELayer/moe.experts/"
             "gmm/pallas_call")
    rec = _record([("%gmm.1", joyai, 0, 1000)])
    rec["sizes"] = cells.config_sizes(cells.load_benchmark(),
                                      "joyai-llm-flash")
    rec["traffic"] = cells.load_json("traffic", "lm-s8192-b1")
    empty = {"trace": None, "trace_steps": 2, "sizes": {}, "traffic": {}}
    for name in ("latent_moe_gemm_roofline", "latent_proj_ms_per_step"):
        reader = cells.load_module("layer_metrics", name).read
        assert reader(rec) is None, name
    for name in NEW:
        reader = cells.load_module("layer_metrics", name).read
        assert reader(dict(empty)) is None, name
        assert reader({}) is None, name
    # this configuration's own program with the scopes missing (a trace
    # that names nothing): nothing to read either
    bare = _record([("%fusion.1", "jit(train_step)/jvp(CausalLM)/mul", 0,
                     1000)])
    for name in NEW:
        assert cells.load_module("layer_metrics", name).read(bare) is None
