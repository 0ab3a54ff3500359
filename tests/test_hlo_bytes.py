"""tools/hlo_bytes.py on a recorded snippet: lines of the ResNet-50 cell's
step compiled for a described v5e (parent of PR 28), backend_config and
most operands cut away. The whole-step table is in PERF.md section 5."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import hlo_bytes  # noqa: E402

_BN = "jit(train_step)/jvp(FromUint8)/net:ResNet/layer1:Sequential/0:BottleneckBlock/bn1:BatchNorm2D"
_CONV = "jit(train_step)/jvp(FromUint8)/net:ResNet/layer1:Sequential/0:BottleneckBlock/conv1:Conv2D"
_BWD = "jit(train_step)/transpose(jvp(FromUint8))/net:ResNet/layer1:Sequential/0:BottleneckBlock/bn1:BatchNorm2D"

SNIPPET = f"""HloModule jit_train_step, is_scheduled=true

%region_6.10 (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[]{{:T(128)}} parameter(0)
  %b = f32[]{{:T(128)}} parameter(1)
  ROOT %add.1 = f32[]{{:T(128)}} add(%a, %b)
}}

%fused_computation.479 (param_0.7013: bf16[256,64,56,56], param_1.9372: f32[64]) -> f32[64] {{
  %param_0.7013 = bf16[256,64,56,56]{{0,1,3,2:T(8,128)(2,1)S(1)}} parameter(0)
  %convert_element_type.1284 = f32[256,64,56,56]{{0,1,3,2:T(8,128)}} convert(%param_0.7013)
  %param_1.9372 = f32[64]{{0:T(128)S(1)}} parameter(1)
  %sub.2418 = f32[256,64,56,56]{{0,1,3,2:T(8,128)}} broadcast(%param_1.9372), dimensions={{1}}
  %sub.1704 = f32[256,64,56,56]{{0,1,3,2:T(8,128)}} subtract(%convert_element_type.1284, %sub.2418)
  %square.154 = f32[256,64,56,56]{{0,1,3,2:T(8,128)}} multiply(%sub.1704, %sub.1704)
  %constant.4260 = f32[]{{:T(128)}} constant(0)
  ROOT %reduce_sum.1382 = f32[64]{{0:T(128)S(1)}} reduce(%square.154, %constant.4260), dimensions={{0,2,3}}, to_apply=%region_6.10
}}

%fused_computation.440 (param_0.1: f32[256,64,56,56], param_1.1: bf16[64,64,1,1]) -> (f32[64], bf16[256,64,56,56]) {{
  %param_0.1 = f32[256,64,56,56]{{0,1,3,2:T(8,128)}} parameter(0)
  %param_1.1 = bf16[64,64,1,1]{{1,0,3,2:T(8,128)(2,1)}} parameter(1)
  %convert.9 = bf16[256,64,56,56]{{0,1,3,2:T(8,128)(2,1)}} convert(%param_0.1)
  %conv.1 = bf16[256,64,56,56]{{0,1,3,2:T(8,128)(2,1)}} convolution(%convert.9, %param_1.1), window={{size=1x1}}, dim_labels=bf01_oi01->bf01
  %convert.10 = f32[256,64,56,56]{{0,1,3,2:T(8,128)}} convert(%conv.1)
  %constant.1 = f32[]{{:T(128)}} constant(0)
  %reduce.1 = f32[64]{{0:T(128)S(1)}} reduce(%convert.10, %constant.1), dimensions={{0,2,3}}, to_apply=%region_6.10
  ROOT %tuple.1 = (f32[64]{{0:T(128)S(1)}}, bf16[256,64,56,56]{{0,1,3,2:T(8,128)(2,1)S(1)}}) tuple(%reduce.1, %conv.1)
}}

%fused_computation.7 (param_0.2: f32[256,64,56,56], param_1.2: f32[64]) -> f32[256,64,56,56] {{
  %param_0.2 = f32[256,64,56,56]{{0,1,3,2:T(8,128)}} parameter(0)
  %param_1.2 = f32[64]{{0:T(128)}} parameter(1)
  %bcast.2 = f32[256,64,56,56]{{0,1,3,2:T(8,128)}} broadcast(%param_1.2), dimensions={{1}}
  ROOT %mul.2 = f32[256,64,56,56]{{0,1,3,2:T(8,128)}} multiply(%param_0.2, %bcast.2)
}}

ENTRY %main.380 (x.1: f32[256,64,56,56], w.1: f32[64,64,1,1], m.1: f32[64]) -> (f32[64], f32[256,64,56,56]) {{
  %x.1 = f32[256,64,56,56]{{0,1,3,2:T(8,128)}} parameter(0)
  %w.1 = f32[64,64,1,1]{{1,0,3,2:T(8,128)}} parameter(1)
  %m.1 = f32[64]{{0:T(128)}} parameter(2)
  %convert.38 = bf16[64,64,1,1]{{1,0,3,2:T(8,128)(2,1)}} convert(%w.1), metadata={{op_name="{_CONV}/convert_element_type"}}
  %copy-start.454 = (f32[64]{{0:T(256)}}, f32[64]{{0:T(256)S(1)}}, u32[]{{:S(2)}}) copy-start(%m.1)
  %copy-done.454 = f32[64]{{0:T(256)}} copy-done(%copy-start.454)
  %convert_reduce_fusion.24 = (f32[64]{{0:T(128)S(1)}}, bf16[256,64,56,56]{{0,1,3,2:T(8,128)(2,1)S(1)}}) fusion(%x.1, %convert.38), kind=kOutput, calls=%fused_computation.440, metadata={{op_name="{_CONV}/conv_general_dilated" stack_frame_id=107}}, backend_config={{"flag_configs":[],"window_config":{{"estimated_cycles":"717185"}}}}
  %get-tuple-element.1947 = f32[64]{{0:T(128)S(1)}} get-tuple-element(%convert_reduce_fusion.24), index=0, metadata={{op_name="{_BN}/reduce_sum"}}
  %get-tuple-element.1955 = bf16[256,64,56,56]{{0,1,3,2:T(8,128)(2,1)S(1)}} get-tuple-element(%convert_reduce_fusion.24), index=1
  %fusion.401 = f32[64]{{0:T(128)S(1)}} fusion(%get-tuple-element.1955, %get-tuple-element.1947), kind=kLoop, calls=%fused_computation.479, metadata={{op_name="{_BN}/jit(_var)/reduce_sum" stack_frame_id=108}}
  %fusion.9 = f32[256,64,56,56]{{0,1,3,2:T(8,128)}} fusion(%x.1, %copy-done.454), kind=kLoop, calls=%fused_computation.7, metadata={{op_name="{_BWD}/mul"}}
  ROOT %tuple.9 = (f32[64]{{0:T(128)S(1)}}, f32[256,64,56,56]{{0,1,3,2:T(8,128)}}) tuple(%fusion.401, %fusion.9)
}}
"""

ACT_F32 = 256 * 64 * 56 * 56 * 4
ACT_BF16 = ACT_F32 // 2
W = 64 * 64


def test_arrays_drop_layouts_and_read_tuples():
    got = hlo_bytes.arrays(
        "(f32[64]{0:T(128)S(1)}, bf16[256,64,56,56]{0,1,3,2:T(8,128)(2,1)S(1)}, u32[]{:S(2)})")
    assert got == [("f32", (64,)), ("bf16", (256, 64, 56, 56)), ("u32", ())]
    assert [hlo_bytes.nbytes(a) for a in got] == [256, ACT_BF16, 4]
    with pytest.raises(ValueError):
        hlo_bytes.arrays("q7[3]")


def test_account_classifies_and_sums_the_snippet():
    acc = hlo_bytes.account(SNIPPET)
    rows = acc["rows"]
    # the convolution fusion with the mean's sum in its epilogue
    assert rows[("fwd", "Conv2D", "convolution")] == {
        "ops": 1, "activation_reads": 1,
        "bytes": ACT_F32 + W * 2 + 256 + ACT_BF16}
    # the standalone variance pass: reads the activation, returns [C]
    assert rows[("fwd", "BatchNorm2D", "[C]-only reduction")] == {
        "ops": 1, "activation_reads": 1, "bytes": ACT_BF16 + 256 + 256}
    assert rows[("bwd", "BatchNorm2D", "elementwise")] == {
        "ops": 1, "activation_reads": 1, "bytes": 2 * ACT_F32 + 256}
    # the weight's cast: a plain top-level convert under the conv's scope
    assert rows[("fwd", "Conv2D", "elementwise")]["bytes"] == W * 4 + W * 2
    assert len(rows) == 4
    assert acc["total_bytes"] == sum(r["bytes"] for r in rows.values())
    # the async copy is beside the total, not in it; parameters, tuples and
    # get-tuple-elements move nothing
    assert acc["async_bytes"] == 2 * 256
    assert acc["batch"] == 256
    assert acc["f32_batch_bytes"] == 3 * ACT_F32


def test_render_and_json(capsys, tmp_path):
    path = tmp_path / "step.hlo"
    path.write_text(SNIPPET)
    hlo_bytes.main([str(path)])
    out = capsys.readouterr().out
    assert "| fwd | BatchNorm2D | [C]-only reduction | 1 | 1 |" in out
    assert "**total**" in out and "f32[256,...]" in out
    hlo_bytes.main([str(path), "--json", "--batch", "7"])
    got = json.loads(capsys.readouterr().out)
    assert got["f32_batch_bytes"] == 0 and len(got["rows"]) == 4
