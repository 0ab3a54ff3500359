"""Rehearsal of the cell PR 53 adds, on the CPU: the xing4.0-29b-a4b cell's
driver end to end at ``TOY`` width (a dense block, two expert blocks and the
MTP module on four residual streams under manifold-constrained
hyper-connections, 2 of 4 latent-attention heads and 8 of 32 experts held,
YaRN, per-block recomputation, the reference check in float32 and block by
block under amp, the model in pieces), the configuration file against the
catalog row, the cut's arithmetic against the shapes the model builds, the
FLOP and byte functions against hand counts, and the three new per-layer
readers on a built trace. Every ``BENCHMARK.json`` entry is found by its
name. A rehearsal's numbers are never a metric."""
import copy
import json

import pytest

from benchmark.harness import cells, rehearsal, runner

CONFIG, TRAFFIC = "xing4.0-29b-a4b", "lm-s4096-b1-mhc"
CELL = "xing4.0-29b-a4b.train-lm-s4096-b1"
NEW = ("mhc_ms_per_step", "mhc_maps_ms_per_step", "mhc_stage_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "num_attention_heads", "num_key_value_heads", "vocab_size"]
LAYER = "residual path (ops/hyper_connections.py, text/models.py)"


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
    toy["fields"][0]["shape"] = [32]
    toy["trace_steps"] = trace_steps
    return toy


def test_train_loop_xing4_toy(tmp_path):
    from paddle_tpu.incubate import moe
    from paddle_tpu.ops import hyper_connections

    config = cells.load_module("configs", CONFIG)
    held = moe._DISPATCH_TOTAL.value(path="sorted_held")
    sublayers = hyper_connections._MHC_TOTAL.value(path="xla")
    result, notes = rehearsal.rehearse(CONFIG, _toy_traffic(), config.TOY,
                                       str(tmp_path), seconds=1.0)
    ref = notes[0]["reference_check"]
    assert ref["ok"], ref
    assert ref["tokens"] == 32
    # float32 against float32: far inside the tolerance a bf16 run fails
    assert ref["f32_rel_err"] < 1e-5 < ref["f32_rtol"]
    assert ref["loss_f32_rel_err"] < 2e-6 < ref["loss_f32_rtol"]
    assert ref["mtp_ce_f32_rel_err"] < 2e-6 and ref["ref_mtp_ce"] > 0
    # the amp half really is bf16 and is compared a block, every block:
    # three and the module's
    assert ref["amp_rel_err"] > 10 * ref["f32_rel_err"]
    assert len(ref["amp_block_worst"]) == len(ref["amp_block_medians"]) == 4
    assert ref["amp_rel_err"] == max(ref["amp_block_worst"])
    assert min(ref["amp_compared_share"]) >= ref["amp_compared_min"]
    # the dense block has no router: every token compared
    assert ref["amp_compared_share"][0] == 1.0
    # three expert blocks' held pairs, none dropped, now or in the steps
    assert len(ref["held_pairs_landed"]) == 3
    assert (ref["ref_dropped"], ref["f32_dropped"], ref["amp_dropped"],
            ref["overflow_train_steps"]) == (0, 0, 0, 0)
    assert notes[0]["loss_fell"]
    assert result["correct"] and result["failed"] == 0
    rec = result["record"]
    assert rec["window_compiles"] == 0 and rec["rows_per_step"] == 1
    # the step's three held expert layers and eight hyper-connected
    # sublayers took their one path each; all twenty rounds ran
    assert moe._DISPATCH_TOTAL.value(path="sorted_held") >= held + 3
    assert hyper_connections._MHC_TOTAL.value(path="xla") >= sublayers + 8
    assert hyper_connections._SINKHORN_ROUNDS.value() == 20
    assert (ref["f32_rtol"], ref["amp_rtol"], ref["loss_f32_rtol"],
            ref["loss_amp_rtol"]) == tuple(
                getattr(config, name) for name in config.LIMITS)


@pytest.mark.parametrize("broken", ["unprojected", "no-yarn", "rows-only"])
def test_a_reference_built_otherwise_fails_the_toy_check(broken, monkeypatch):
    """The check tells the program from a reference whose H_res is left
    unprojected, whose attention rotates by the plain table at the plain
    scale, or whose H_res is row-stochastic alone (a softmax a row: no
    column ever normalised) — in float32 at logit level."""
    import jax
    import numpy as np

    config = cells.load_module("configs", CONFIG)
    reference = cells.load_module("references", CONFIG)
    sizes = dict(_sizes(), **config.TOY)
    built = config.build_train(3, sizes, {"input_ids": (32,)})
    x = jax.numpy.asarray(np.random.default_rng(3).integers(
        0, sizes["vocab_size"], (1, 32)), "int32")
    assert config.check_train(built, reference, sizes, None, x)["ok"]
    other = dict(sizes, **{"unprojected": {"hc_sinkhorn_iters": 0},
                           "no-yarn": {"rope_scaling": None},
                           "rows-only": {}}[broken])
    if broken == "rows-only":
        monkeypatch.setattr(
            reference, "sinkhorn_knopp",
            lambda logits, sizes: jax.nn.softmax(logits, axis=-1))
    ref = config.check_train(built, reference, sizes, None, x,
                             reference_sizes=other)
    assert not ref["ok"]
    assert ref["f32_rel_err"] > 10 * ref["f32_rtol"]


def test_cell_reports_the_new_readers_beside_the_shared_ones():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert cell["config"] == CONFIG
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert "driver-drawn" in entry["why"]
    assert len(entry["why"]) <= 200 and len(cell["why"]) <= 200
    assert entry["reduced"] == _sizes()["reduced"] == REDUCED
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    names = {m["name"] for m in cells.metrics_of(bench, "per_layer", cell)}
    assert set(NEW) | {
        "recompute_ms_per_step", "lm_head_ms_per_step", "mtp_ms_per_step",
        "moe_ms_per_step", "moe_dispatch_ms_per_step",
        "moe_held_gemm_roofline", "mla_ms_per_step", "mla_flash_roofline",
        "train_mfu_pct", "hbm_compiled_gb", "step_ms_p50",
        "optimizer_ms_per_step", "host_gc_ms_per_step",
        "host_stall_ms_max"} <= names
    ends = {m["name"] for m in cells.metrics_of(bench, "end_to_end", cell)}
    assert {"train_samples_per_s", "setup_s"} <= ends
    # no other mixer's, no other attention width's, no whole-layer gemm's
    assert not {n for n in names if n.startswith((
        "kda_", "gdn_", "swa_", "gqa_", "shortconv_", "attn64_", "ssd_",
        "mamba_", "latent_"))}
    assert "moe_gemm_roofline" not in names
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        metric = by_name[name]
        assert metric["workloads"] == [CELL]
        reader = cells.load_module("layer_metrics", name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            metric["layer"], metric["unit"], metric["source"],
            metric["moves"])
        assert metric["layer"] == LAYER
        assert metric["source"] == "device_trace"
        assert metric["better"] == ("higher" if name.endswith("_roofline")
                                    else "lower")
    # every name the traffic file reports is a metric, and lists the cell
    every = {m["name"]: m for g in ("end_to_end", "per_layer")
             for m in bench[g]}
    mine = cells.load_json("traffic", TRAFFIC)
    for name in mine["reports"]:
        assert CELL in every[name]["workloads"], name
    assert set(NEW) <= set(mine["reports"])
    assert not cells.index_gaps(bench)
    # the mix is the other 4,096-token one-row LM mix row for row, but for
    # what it reports and why
    theirs = cells.load_json("traffic", "lm-s4096-b1-latentmoe")
    assert set(mine) == set(theirs)
    for key in mine:
        assert (mine[key] == theirs[key]) == (
            key not in ("reports", "why")), key
    assert mine["fields"] == [{"name": "input_ids", "shape": [4096],
                               "dtype": "int32", "draw": "uniform_int",
                               "low": 0, "high": "vocab_size"}]
    assert (mine["rows_per_chip"], mine["probe_steps"],
            mine["trace_steps"]) == (1, 10, 6)
    assert mine["loader"] == {"num_workers": 2, "prefetch_factor": 2}


def test_configuration_keeps_every_published_width():
    """Every number (and string, and the rope_scaling group) of the catalog
    row's config is in the file under the same key, but the six counts the
    cut lists; no width is among them; the cut's arithmetic is the
    model's."""
    sizes = _sizes()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert sizes["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if sizes.get(k) != v)
    assert differ == sorted(sizes["reduced"]) == sorted(REDUCED)
    assert sizes["published"] == {k: row["config"][k] for k in REDUCED}
    assert [sizes[k] for k in REDUCED] == [5, 1, 8, 16, 16, 16384]
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for key in ("published", "reduced", "cut", "assumed", "departures"):
        assert sizes[key], key
    for key in ("deployment", "arithmetic", "distorts", "num_hidden_layers",
                "first_k_dense_replace", "n_routed_experts",
                "num_attention_heads, num_key_value_heads", "vocab_size",
                "held_rows"):
        assert sizes["cut"][key], key
    for key in ("layer_equations", "hc_norm_eps", "hc_eps", "clamp",
                "reduction", "mtp", "hc_init", "rope_pairing",
                "bias_update_speed", "mtp_loss_weight",
                "balance_loss_weight", "initializer_range", "train_recipe",
                "maps_precision", "streams_dtype", "held_rows_factor"):
        assert sizes["assumed"][key], key
    # the published widths the acceptance names
    assert (sizes["hidden_size"], sizes["q_lora_rank"],
            sizes["kv_lora_rank"], sizes["qk_nope_head_dim"],
            sizes["qk_rope_head_dim"], sizes["v_head_dim"],
            sizes["intermediate_size"], sizes["moe_intermediate_size"],
            sizes["num_experts_per_tok"], sizes["router_experts"],
            sizes["hc_mult"], sizes["hc_sinkhorn_iters"],
            sizes["mhc_h_res_clamp_min"], sizes["mhc_h_res_clamp_max"],
            sizes["routed_scaling_factor"]) == (
                3584, 768, 512, 128, 64, 128, 9216, 1024, 4, 64, 4, 20, -30,
                30, 2)
    assert sizes["rope_scaling"] == row["config"]["rope_scaling"]
    assert (sizes["held_experts"], sizes["held_attention_heads"],
            sizes["attention_heads"]) == ([0, 8], [0, 16], 32)
    config = cells.load_module("configs", CONFIG)
    kw = config.model_kwargs(sizes)
    assert (kw["num_attention_heads"], kw["held_attention_heads"],
            kw["n_routed_experts"], kw["held_experts"],
            kw["num_hidden_layers"], kw["first_k_dense_replace"]) == (
                32, (0, 16), 64, (0, 8), 5, 1)
    assert config.block_kinds(sizes) == (
        [("layers.0.", "dense")] + [(f"layers.{i}.", "expert")
                                    for i in range(1, 5)],
        [("mtp.0.block.", "mtp")])
    # 842.7 M parameters, counted from the shapes the model would build
    h = 3584
    attn = (h * 768 + 768 + 768 * 16 * 192 + h * 576 + 512
            + 512 * 16 * 256 + 16 * 128 * h)
    mhc = 2 * (4 * h * 24 + 24 + 3)
    dense = attn + 3 * h * 9216 + 2 * h + mhc
    expert = attn + 8 * 3 * h * 1024 + 3 * h * 1024 + h * 64 + 2 * h + mhc
    mtp = expert + 2 * h * h + 3 * h
    total = dense + 4 * expert + 2 * 16384 * h + h + mtp
    assert attn == pytest.approx(16.61e6, rel=1e-3)
    assert mhc == pytest.approx(0.69e6, rel=5e-3)
    assert dense == pytest.approx(116.4e6, rel=1e-3)
    assert expert == pytest.approx(116.6e6, rel=1e-3)
    assert mtp == pytest.approx(142.3e6, rel=1e-3)
    assert total == pytest.approx(842.7e6, rel=2e-4)
    assert 16 * total == pytest.approx(13.48e9, rel=1e-3)
    assert "842.7 M parameters = 13.48 GB" in sizes["cut"]["arithmetic"]
    # the published model by the same formulae: 30.3 B, 4.4 B active
    whole_attn = attn + 768 * 16 * 192 + 512 * 16 * 256 + 16 * 128 * h
    around = whole_attn + mhc + 3 * h * 1024 + h * 64 + 2 * h
    one = 3 * h * 1024
    tables = 2 * 131072 * h
    published = (2 * (whole_attn + 3 * h * 9216 + mhc) + 38 * (
        around + 64 * one) + tables + (around + 64 * one + 2 * h * h))
    active = (2 * (whole_attn + 3 * h * 9216 + mhc) + 38 * (
        around + 4 * one) + tables)
    assert published == pytest.approx(30.3e9, rel=5e-3)
    assert active == pytest.approx(4.4e9, rel=2e-2)


def test_toy_model_has_the_parameters_the_arithmetic_counts():
    config = cells.load_module("configs", CONFIG)
    sizes = dict(_sizes(), **config.TOY)
    from paddle_tpu.text.models import Xing4Model

    net = Xing4Model(**config.model_kwargs(sizes))
    counted = sum(int(p.size) for p in net.parameters())
    h = 64
    attn = h * 48 + 48 + 48 * 2 * 24 + h * 40 + 32 + 32 * 2 * 32 + 2 * 16 * h
    mhc = 2 * (4 * h * 24 + 24 + 3)
    dense = attn + 3 * h * 96 + 2 * h + mhc
    expert = attn + 8 * 3 * h * 32 + 3 * h * 32 + h * 32 + 2 * h + mhc
    mtp = expert + 2 * h * h + 3 * h
    assert counted == dense + 2 * expert + 2 * 256 * h + h + mtp
    with pytest.raises(ValueError, match="not the counts the file states"):
        config.model_kwargs(dict(sizes, held_attention_heads=[0, 4]))
    # AdamW decays neither a norm's weight nor a hyper-connection's gates
    # and biases; phi and every matrix it does
    by_decay = {True: set(), False: set()}
    for name, p in net.named_parameters():
        by_decay[config.decays(p.name)].add(name.rsplit(".", 1)[-1])
    assert by_decay[False] == {"alpha", "b", "weight"}
    assert {"phi", "w_gate", "weight"} <= by_decay[True]
    assert not [n for n, p in net.named_parameters()
                if "norm" in n and config.decays(p.name)]
    assert not [n for n, p in net.named_parameters()
                if "norm" not in n and n.endswith("weight")
                and not config.decays(p.name)]


def test_flops_per_sample_and_the_stage_bytes():
    config = cells.load_module("configs", CONFIG)
    stage = cells.load_module("layer_metrics", "mhc_stage_roofline")
    sizes = _sizes()
    seq, h = 4096, 3584
    assert config.mla_projection_flops_per_token(sizes) == 2 * (
        h * 768 + 768 * 16 * 192 + h * 576 + 512 * 16 * 256 + 16 * 128 * h)
    assert config.mla_core_flops(sizes, seq) == (
        2 * 16 * (seq * (seq + 1) // 2) * 320)
    assert config.held_expert_flops_per_token(sizes) == (
        4 * 8 / 64 * 3 * 2 * h * 1024)
    # a sublayer's maps, read and write-back: 4 C x 24, 4 C, 16 C + 4 C
    assert config.mhc_flops_per_token(sizes) == 2 * (
        4 * h * 24 + 4 * h + 16 * h + 4 * h)
    assert config.block_counts(sizes) == (1, 5)
    flops = config.flops_per_sample(sizes, {"input_ids": (seq,)})
    per_token = (6 * (config.mla_projection_flops_per_token(sizes)
                      + 2 * config.mhc_flops_per_token(sizes))
                 + 3 * 2 * h * 9216
                 + 5 * (2 * h * 64 + 3 * 2 * h * 1024
                        + config.held_expert_flops_per_token(sizes))
                 + 2 * 2 * h * h + 2 * 2 * h * 16384)
    assert flops == 3.0 * (seq * per_token
                           + 6 * config.mla_core_flops(sizes, seq))
    # the hyper-connections are 1% of the step's FLOPs
    mhc = 3.0 * seq * 12 * config.mhc_flops_per_token(sizes)
    assert 0.005 < mhc / flops < 0.02
    assert 12.0e12 < flops < 12.3e12
    # twelve sublayers; a forward moves X read, X' written, u written in
    # float32 and y in bf16: (4 + 4 + 1) x 4 + 2 = 38 bytes a feature-token
    assert stage.sublayers(sizes) == 12
    assert stage.stage_bytes(1, 1, 4, 1, 0) == 38
    # a backward X, dX' read, dX written, du read in float32, y and dy in
    # bf16: 13 x 4 + 2 x 2 = 56
    assert stage.stage_bytes(1, 1, 4, 0, 1) == 56
    assert stage.stage_bytes(4096, h, 4, 2, 1) == 4096 * h * (2 * 38 + 56)
    assert stage.stage_bytes(4096, h, 4, 1, 0, itemsize=2) == (
        4096 * h * 20)                                   # ISSUE 53's 10 x 2
    assert stage.stage_bytes(10, 8, 2, 1, 1, itemsize=4, y_itemsize=4) == (
        10 * 8 * 4 * (6 + 9))


# ---------------------------------------------------------- the readers
ROOT_SCOPE = "jit(train_step)/jvp(CausalLM)/lm:Xing4Model/"
BLOCK = ROOT_SCOPE + "checkpoint/layers:LayerList/1:Xing4DecoderLayer/"
BWD = BLOCK.replace("jvp(CausalLM)", "transpose(jvp(CausalLM))")
REMAT = BWD.replace("checkpoint/", "checkpoint/rematted_computation/")


def _record(events, steps=1):
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


def test_new_readers_on_a_built_trace():
    read = {n: cells.load_module("layer_metrics", n).read for n in NEW}
    stage = cells.load_module("layer_metrics", "mhc_stage_roofline")
    rec = _record([
        ("%fusion.0", ROOT_SCOPE + "mhc.expand/tile", 0, 100),
        ("%fusion.1", BLOCK + "mhc.maps/checkpoint/dot_general", 100, 500),
        ("%fusion.2", BLOCK + "mhc.sinkhorn/checkpoint/div", 600, 300),
        ("%fusion.3", BLOCK + "mhc.pre/checkpoint/mul", 900, 1000),
        ("%fusion.4", BLOCK + "self_attn:MLAttention/mla.q/dot_general",
         1900, 4000),
        ("%fusion.5", BLOCK + "mhc.post/checkpoint/add", 5900, 2000),
        ("%fusion.6", ROOT_SCOPE + "mhc.reduce/add", 7900, 200),
        # the block's recomputed forward, then its backward: the stage's own
        # rebuilt float32 lies under the scope and is part of that backward
        ("%fusion.7", REMAT + "mhc.pre/checkpoint/mul", 10000, 1000),
        ("%fusion.8", REMAT + "mhc.post/checkpoint/add", 11000, 2000),
        ("%fusion.9", REMAT + "mhc.sinkhorn/checkpoint/div", 13000, 300),
        ("%fusion.10", BWD + "mhc.post/checkpoint/rematted_computation/add",
         14000, 1500),
        ("%fusion.11", BWD + "mhc.post/checkpoint/mul", 15500, 2500),
        ("%fusion.12", BWD + "mhc.pre/checkpoint/mul", 18000, 2000),
        ("%fusion.13", BWD + "mhc.maps/checkpoint/dot_general", 20000, 900),
        ("%fusion.14", "jit(train_step)/optimizer/add", 30000, 9000),
    ])
    assert read["mhc_ms_per_step"](rec) == pytest.approx(14.3)
    assert read["mhc_maps_ms_per_step"](rec) == pytest.approx(2.0)
    # a forward, the block's recomputed forward and one backward
    assert stage.passes(rec) == (2, 1)
    least = stage.stage_bytes(4096 * 12, 3584, 4, 2, 1) / 819e9
    # 3 + 3 + 6 ms under mhc.pre and mhc.post in the one step
    assert read["mhc_stage_roofline"](rec) == pytest.approx(
        100 * least / 12e-3)
    # the reader caps nothing: a trace whose stage takes less time than the
    # chip's peak allows would read over 100 and be refused
    fast = _record([("%fusion.3", BLOCK + "mhc.pre/checkpoint/mul", 0, 100)])
    assert read["mhc_stage_roofline"](fast) > 100
    # without a backward pass in the slice only the forward's bytes count
    forward = _record([("%fusion.3", BLOCK + "mhc.pre/checkpoint/mul", 0,
                        40000)])
    assert stage.passes(forward) == (1, 0)
    assert read["mhc_stage_roofline"](forward) == pytest.approx(
        100 * stage.stage_bytes(4096 * 12, 3584, 4, 1, 0) / 819e9 / 40e-3)


def test_new_readers_find_nothing_on_a_program_without_the_streams():
    """A parent without the model, a JoyAI cell, a run without a trace:
    every new reader returns None and raises nothing."""
    joyai = ("jit(train_step)/jvp(CausalLM)/lm:JoyAIFlashModel/checkpoint/"
             "layers:LayerList/1:JoyAIDecoderLayer/self_attn:MLAttention/"
             "mla.q/dot_general")
    rec = _record([("%fusion.1", joyai, 0, 1000)])
    rec["sizes"] = cells.config_sizes(cells.load_benchmark(),
                                      "joyai-llm-flash")
    rec["traffic"] = cells.load_json("traffic", "lm-s8192-b1")
    empty = {"trace": None, "trace_steps": 2, "sizes": {}, "traffic": {}}
    for name in NEW:
        reader = cells.load_module("layer_metrics", name).read
        assert reader(rec) is None, name
        assert reader(dict(empty)) is None, name
        assert reader({}) is None, name
    # this configuration's own sizes on a trace that names no such scope
    bare = _record([("%fusion.1", ROOT_SCOPE + "mul", 0, 1000)])
    for name in NEW:
        assert cells.load_module("layer_metrics", name).read(bare) is None
