"""Rehearsal of the cell PR 40 adds, on the CPU: the Trinity-Mini cell's
driver end to end at ``TOY`` width (a sliding window in four layers and full
attention without positions in one, the dense first layer, the sigmoid
router with a held share and a shared expert, four norms a block, per-block
recomputation, the reference check in float32 and block by block under amp),
the configuration file against the catalog row, the mix against
Kimi-Linear's, the FLOP and byte functions against hand counts (the band's
pairs against a brute-force count), and the four new per-layer readers on a
recorded form of the trace. A rehearsal's numbers are never a metric."""
import copy
import json

import pytest

from benchmark.harness import cells, rehearsal, runner

CONFIG, TRAFFIC = "trinity-mini", "lm-s16384-b1-swa"
CELL = "trinity-mini.train-lm-s16384"
NEW = ("swa_ms_per_step", "swa_flash_roofline", "global_attn_ms_per_step",
       "global_flash_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def _stop_children():
    from paddle_tpu.distributed import topology

    topology.set_global_mesh(None)
    yield
    runner.stop_children()


def _sizes():
    return dict(cells.config_sizes(cells.load_benchmark(), CONFIG))


def test_train_loop_trinity_mini_toy(tmp_path):
    from paddle_tpu.incubate import moe
    from paddle_tpu.ops import attention

    config = cells.load_module("configs", CONFIG)
    toy = copy.deepcopy(cells.load_json("traffic", TRAFFIC))
    toy["rows_per_chip"] = 2
    toy["fields"][0]["shape"] = [40]     # five toy windows
    toy["trace_steps"] = 2
    held = moe._DISPATCH_TOTAL.value(path="sorted_held")
    windowed = attention._WINDOW_ROUTE_TOTAL.value(route="xla")
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
    assert ref["amp_compared_share"][0] == 1.0     # the dense block: all
    assert min(ref["amp_compared_share"]) >= ref["amp_compared_min"]
    assert len(ref["held_pairs_landed"]) == 4      # four expert layers
    assert ref["f32_dropped"] == ref["amp_dropped"] == ref["ref_dropped"] == 0
    assert ref["overflow_train_steps"] == 0
    assert ref["ref_loss"] == ref["ref_ce"]        # no balance term
    assert notes[0]["loss_fell"]
    assert result["correct"] and result["failed"] == 0
    rec = result["record"]
    assert rec["window_compiles"] == 0 and rec["rows_per_step"] == 2
    # every expert layer took the held-share path and every sliding layer
    # a windowed call (on the CPU the gate gives them XLA's route)
    assert moe._DISPATCH_TOTAL.value(path="sorted_held") > held
    assert attention._WINDOW_ROUTE_TOTAL.value(route="xla") > windowed
    # the limits this configuration states are the ones its check ran on
    for name in config.LIMITS:
        assert getattr(config._kimi, name) == getattr(config, name), name
    assert (ref["f32_rtol"], ref["amp_rtol"]) == (config.F32_RTOL,
                                                  config.AMP_RTOL)
    # and the verdict is this configuration's: the median token has a limit
    # of its own (a swap's echo reaches it through a window: 1.18e-5 on the
    # chip), between the program's readings and a bf16 reference's 5.9e-3
    assert ref["f32_median_rtol"] == config.F32_MEDIAN_RTOL == 2e-4
    assert config._kimi.compare is config.compare
    echo = dict(ref, f32_rel_err_median=1.18e-5)
    bf16 = dict(ref, f32_rel_err_median=5.9e-3)
    readings = config._readings
    try:
        for case, verdict in ((echo, True), (bf16, False)):
            config._readings = lambda *a, case=case: dict(case)
            assert config.compare(None, None, None, None)["ok"] is verdict
    finally:
        config._readings = readings


def test_a_window_off_by_one_toy_block_fails_the_block_check(tmp_path):
    """What ``tools/trinity_check.py --window-off`` shows on the chip, at the
    toy's widths: the program built with half the window fails the check
    against the reference at the configuration's window."""
    config = cells.load_module("configs", CONFIG)
    toy = copy.deepcopy(cells.load_json("traffic", TRAFFIC))
    toy["fields"][0]["shape"] = [40]
    toy["trace_steps"] = 1
    real_build = config.build_train
    config_short = cells.load_module("configs", CONFIG)

    def build_short(seed, sizes, shapes):
        return real_build(seed, dict(sizes, sliding_window=4), shapes)

    # the rehearsal loads the configuration's module itself: give it a
    # module whose builder shortens the window
    config_short.build_train = build_short
    load = cells.load_module
    try:
        cells.load_module = lambda kind, name, *a: (
            config_short if (kind, name) == ("configs", CONFIG)
            else load(kind, name, *a))
        result, notes = rehearsal.rehearse(CONFIG, toy, config.TOY,
                                           str(tmp_path), seconds=0.2)
    finally:
        cells.load_module = load
    ref = notes[0]["reference_check"]
    assert not ref["ok"] and not result["correct"]
    assert ref["amp_rel_err"] > ref["amp_rtol"]
    assert ref["f32_rel_err_median"] > ref["f32_median_rtol"]
    # the full block's mask is untouched: its median stays bf16's
    assert ref["amp_block_medians"][4] < ref["amp_rtol"] < max(
        ref["amp_block_medians"][:4])


def test_cell_reports_the_new_readers_beside_the_shared_ones():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert len(bench["workloads"]) == 9 and len(bench["configs"]) == 7
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1
    names = {m["name"] for m in cells.metrics_of(bench, "per_layer", cell)}
    assert set(NEW) | {"moe_held_gemm_roofline", "recompute_ms_per_step",
                       "lm_head_ms_per_step", "moe_ms_per_step",
                       "moe_dispatch_ms_per_step", "train_mfu_pct",
                       "hbm_compiled_gb"} <= names
    # no latent attention, no scan, no MTP module; the other attention
    # rooflines are other widths' (and would count a banded call as full)
    assert not {"mla_ms_per_step", "mla_flash_roofline", "kda_ms_per_step",
                "gdn_ms_per_step", "gqa_ms_per_step", "gqa_flash_roofline",
                "mtp_ms_per_step", "flash_roofline",
                "moe_gemm_roofline"} & names
    for name in NEW:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_samples_per_s"
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
    assert bench["workloads"][-1]["name"] == CELL
    assert not cells.index_gaps(bench)
    # the mix is Kimi-Linear's but for what it reports and why: three
    # configurations read the streaming kernel and the held path at one shape
    mine, theirs = (cells.load_json("traffic", t)
                    for t in (TRAFFIC, "lm-s16384-b1"))
    assert set(mine) == set(theirs)
    for key in mine:
        assert (mine[key] == theirs[key]) == (key not in ("reports", "why"))
    assert len(bench["configs"][-1]["why"]) <= 200 and len(cell["why"]) <= 200


def test_configuration_keeps_every_published_width():
    """Every number (and list) of the catalog row's config is in the file
    under the same key, but the four the cut lists; the cut's arithmetic is
    the model's."""
    sizes = _sizes()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    assert sizes["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if sizes.get(k) != v)
    assert differ == sorted(sizes["reduced"]) == [
        "num_dense_layers", "num_experts", "num_hidden_layers", "vocab_size"]
    assert (sizes["num_hidden_layers"], sizes["num_dense_layers"],
            sizes["num_experts"], sizes["vocab_size"]) == (5, 1, 16, 25024)
    assert sizes["published"] == {k: row["config"][k]
                                  for k in sizes["reduced"]}
    assert sizes["vocab_size"] * 8 == row["config"]["vocab_size"]
    # the published widths the acceptance names
    assert (sizes["hidden_size"], sizes["num_attention_heads"],
            sizes["head_dim"], sizes["num_key_value_heads"],
            sizes["sliding_window"], sizes["intermediate_size"],
            sizes["moe_intermediate_size"], sizes["num_experts_per_tok"],
            sizes["route_scale"], sizes["rope_theta"]) == (
                2048, 32, 128, 4, 2048, 6144, 1024, 8, 2.826, 10000)
    # the layers run: published layer 0, then one whole period, 3 : 1
    config = cells.load_module("configs", CONFIG)
    assert sizes["run_layers"] == [0, 4, 5, 6, 7]
    assert config.layer_types(sizes) == ["sliding_attention"] * 4 + [
        "full_attention"]
    assert sizes["layer_types"][1] == "sliding_attention"  # the other dense
    assert config.mixer_counts(sizes) == (4, 1)
    assert config.block_counts(sizes) == (1, 4)
    # the reader's names say what the source's do
    assert sizes["router_experts"] == row["config"]["num_experts"] == 128
    assert sizes["n_routed_experts"] == sizes["num_experts"]
    assert sizes["held_experts"] == [0, sizes["n_routed_experts"]]
    assert sizes["first_k_dense_replace"] == sizes["num_dense_layers"]
    # 705.5 M parameters, counted from the shapes the model would build
    h = 2048
    attn = 3 * h * 4096 + 2 * h * 512 + 2 * 128
    expert = 3 * h * 1024
    moe = h * 128 + 17 * expert
    total = (5 * (attn + 4 * h) + 3 * h * 6144 + 4 * moe + h
             + 2 * 25024 * h)
    assert attn == pytest.approx(27.26e6, rel=1e-3)
    assert attn + 3 * h * 6144 == pytest.approx(65.0e6, rel=1e-3)
    assert attn + moe == pytest.approx(134.5e6, rel=1e-3)
    assert total == pytest.approx(705.5e6, rel=1e-3)
    assert "705.5 M parameters = 11.3 GB" in sizes["cut"]["arithmetic"]
    assert "8 chips share each layer" in sizes["cut"]["deployment"]


def test_toy_model_has_the_parameters_the_arithmetic_counts():
    """The constructor at published widths would build what the cut's
    arithmetic counts: checked on the shapes, at the toy's widths and the
    cell's five layers."""
    config = cells.load_module("configs", CONFIG)
    sizes = dict(_sizes(), **config.TOY)
    from paddle_tpu.text.models import AfmoeModel

    net = AfmoeModel(**config.model_kwargs(sizes))
    assert net.layer_types == config.layer_types(sizes)
    counted = sum(int(p.size) for p in net.parameters())
    h = 64
    attn = 3 * h * 4 * 16 + 2 * h * 2 * 16 + 2 * 16
    moe = h * 32 + 9 * 3 * h * 32
    assert counted == (5 * (attn + 4 * h) + 3 * h * 96 + 4 * moe + h
                       + 2 * 256 * h)


def test_flops_per_sample_and_the_roofline_counts():
    config = cells.load_module("configs", CONFIG)
    swa = cells.load_module("layer_metrics", "swa_flash_roofline")
    full = cells.load_module("layer_metrics", "global_flash_roofline")
    sizes = _sizes()
    seq, h = 16384, 2048
    # the band's pairs against a brute-force count
    for s, w in ((16384, 2048), (300, 64), (64, 64), (64, 100), (7, 1)):
        assert swa.band_pairs(s, w) == sum(min(i + 1, w) for i in range(s))
    pairs = swa.band_pairs(seq, 2048)
    assert pairs == 2048 * 2049 // 2 + (seq - 2048) * 2048
    # a quarter of a full layer's pairs, near enough
    assert pairs / (seq * (seq + 1) // 2) == pytest.approx(0.234, rel=1e-2)
    # five matrices an attention sublayer: 27.26 M multiply-adds a token
    assert config.attention_projection_flops_per_token(sizes) == 2 * (
        3 * h * 4096 + 2 * h * 512)
    # the cores over the 32 QUERY heads of 128: the band and the triangle
    assert config.core_flops(sizes, seq, True) == 2 * 32 * pairs * 256
    assert config.core_flops(sizes, seq, False) == (
        2 * 32 * (seq * (seq + 1) // 2) * 256)
    assert config.core_flops(sizes, seq, True) == pytest.approx(0.515e12,
                                                                rel=1e-3)
    assert config.core_flops(sizes, seq, False) == pytest.approx(2.199e12,
                                                                 rel=1e-3)
    assert swa.band_flops(1, 32, seq, 2048, 128, 1, 1) == pytest.approx(
        3.5 * config.core_flops(sizes, seq, True))
    assert full.core_flops(1, 32, seq, 128, 1, 1) == pytest.approx(
        3.5 * config.core_flops(sizes, seq, False))
    # a window saves pairs, not rows: the least bytes are the full core's
    assert swa.band_bytes(1, 32, 4, seq, 128, 1, 0) == seq * 128 * 2 * 72
    # a token's 8 choices land on the 16 held of 128 experts once
    assert config.held_expert_flops_per_token(sizes) == 3 * 2 * h * 1024
    # 40.0 TFLOP a step: nothing recomputed, the band's pairs only, only
    # the held experts' rows, the head over the held vocabulary slice
    flops = config.flops_per_sample(sizes, {"input_ids": (seq,)})
    per_token = (272.63e6 + 125.83e6 + 134.23e6 + 75.50e6 + 2.10e6
                 + 50.33e6 + 50.33e6 + 102.50e6)
    assert flops == pytest.approx(3 * seq * per_token, rel=2e-3)
    assert 39.8e12 < flops < 40.2e12
    # the accepted held-gemm roofline's functions hold here: widths and
    # counts are the file's
    held = cells.load_module("layer_metrics", "moe_held_gemm_roofline")
    assert held.expert_layers(sizes) == 4 and held.held_rows(
        sizes, seq) == 16384
    assert held.held_gemm_flops(sizes, seq, 1) == pytest.approx(
        4 * 3 * 2 * h * 1024 * 16384)


# ---------------------------------------------------------- the readers
ROOT_SCOPE = "jit(train_step)/jvp(CausalLM)/lm:AfmoeModel/"
BLOCK = ROOT_SCOPE + "checkpoint/layers:LayerList/1:AfmoeDecoderLayer/"
BWD = BLOCK.replace("jvp(CausalLM)", "transpose(jvp(CausalLM))")
REMAT = BWD.replace("checkpoint/", "checkpoint/rematted_computation/")
ATTN = "self_attn:AfmoeAttention/"
FULL, FULL_BWD = (b.replace("1:", "4:") + ATTN for b in (BLOCK, BWD))


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
        ("%fusion.1", BLOCK + ATTN + "swa.proj/q_proj:Linear/dot_general",
         0, 3000),
        ("%fusion.2", BLOCK + ATTN + "swa.qk/mul", 3000, 1000),
        ("%flash_band_fwd.1", BLOCK + ATTN + "swa.core/flash_band_fwd/"
         "pallas_call", 4000, 6000),
        # XLA's relayout of the kernel's result, named after its operand:
        # time under the kernel's name, but no call
        ("%reduce.1", BLOCK + ATTN + "swa.core/flash_band_fwd/pallas_call",
         10000, 500),
        ("%fusion.3", BLOCK + "pre_mlp_layernorm:ZeroCenteredRMSNorm/mul",
         10500, 700),
        ("%flash_stream_fwd.1", FULL + "gattn.core/flash_stream_fwd/"
         "pallas_call", 20000, 20000),
        ("%fusion.4", FULL + "gattn.repeat/repeat", 40000, 400),
        ("%fusion.5", REMAT + ATTN + "swa.proj/q_proj:Linear/dot_general",
         50000, 3000),
        ("%flash_band_bwd_dkv_dq.1", BWD + ATTN + "swa.core/"
         "flash_band_bwd_dkv_dq/pallas_call", 53000, 14000),
        ("%flash_stream_bwd_dkv_dq.1", FULL_BWD + "gattn.core/"
         "flash_stream_bwd_dkv_dq/pallas_call", 70000, 44000),
        ("%fusion.8", FULL_BWD + "gattn.out/o_proj:Linear/dot_general",
         114000, 2000),
        ("%fusion.9", "jit(train_step)/optimizer/add", 120000, 9000),
    ])
    assert read["swa_ms_per_step"](rec) == pytest.approx(27500 / 2e3)
    assert read["global_attn_ms_per_step"](rec) == pytest.approx(66400 / 2e3)
    # ONE banded forward call in the slice (the relayout is none) and one
    # backward, over the 20.5 ms under the banded kernel's name; the
    # full-causal calls are the other reader's
    gqa = cells.load_module("layer_metrics", "gqa_flash_roofline")
    swa = cells.load_module("layer_metrics", "swa_flash_roofline")
    assert gqa.calls(rec, "flash_band_fwd") == 1
    assert gqa.calls(rec, "flash_band_bwd_dkv") == 1
    flops = swa.band_flops(1, 32, 16384, 2048, 128, 0.5, 0.5)
    assert read["swa_flash_roofline"](rec) == pytest.approx(
        100 * flops / 0.01025 / 197e12)
    assert read["swa_flash_roofline"](rec) < 100
    assert gqa.calls(rec, "flash_stream_fwd") == 1
    flops = gqa.core_flops(1, 32, 16384, 128, 0.5, 0.5)
    assert read["global_flash_roofline"](rec) == pytest.approx(
        100 * flops / 0.032 / 197e12)
    assert read["global_flash_roofline"](rec) < 100
    # a kernel that ran the whole causal grid under the window's mask would
    # take the full call's time: the share falls, it cannot pass 100
    slow = _record([("%flash_band_fwd.1", BLOCK + ATTN + "swa.core/"
                     "flash_band_fwd/pallas_call", 0, 20000)])
    assert read["swa_flash_roofline"](slow) == pytest.approx(
        100 * swa.band_flops(1, 32, 16384, 2048, 128, 0.5, 0) / 0.01
        / 197e12)
    assert read["swa_flash_roofline"](slow) < 30


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
