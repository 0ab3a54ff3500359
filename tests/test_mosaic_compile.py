"""The whole-sequence attention kernel compiled by Mosaic for a DESCRIBED
v5e (no chip attached: the on-chip-measurement guide's third rehearsal), at
the BERT cells' shape and at the largest shapes the gate admits. It proves
compilation and which route a program gets — never a time, never numerics
(tests/test_pallas_kernels.py holds the numerics, chip_smoke.py phase 3 runs
the kernel on the chip). Two XLA-only programs ride along, each a tripwire
for what a jax upgrade may undo: the BERT cells' FFN (what ``F.gelu`` lowers
to) and one ResNet-50 bottleneck (where batch norm's statistics end up).

The compiles run in ONE child process (this file as a script) whose
environment describes the topology before libtpu loads: libtpu reads it
once, and the test workers' environment stays as it was. Only a missing
libtpu skips; a child that fails, fails the tests.
"""
import collections
import functools
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

BATCH, SEQ, HEADS, HEAD_DIM = 256, 128, 12, 64   # a chip's rows of the cell
MOSAIC = 'custom_call_target="tpu_custom_call"'

#: what lets the sandbox's libtpu describe a v5e it is not attached to
DESCRIBED_V5E = {"TPU_LOG_DIR": "disabled", "TPU_SKIP_MDS_QUERY": "1",
                 "TPU_ACCELERATOR_TYPE": "v5litepod-4",
                 "TPU_WORKER_HOSTNAMES": "localhost", "TPU_WORKER_ID": "0",
                 "JAX_PLATFORMS": "cpu", "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}

#: kernel alone on one described chip: (batch, seq, heads, head_dim, dtype,
#: dropout). The last two sit at the VMEM clause of ``short_supported``:
#: one more 128-lane group and the gate says xla
KERNEL_CASES = {
    "cell-shape-p0": (BATCH, SEQ, HEADS, HEAD_DIM, "bfloat16", 0.0),
    "cell-shape-p0.1": (BATCH, SEQ, HEADS, HEAD_DIM, "bfloat16", 0.1),
    "largest-f32-s512": (2, 512, 11, 128, "float32", 0.1),
    "largest-bf16-s512": (2, 512, 22, 128, "bfloat16", 0.1),
}
GATE_CASES = ("announced-dp4", "plain-jit-over-mesh-arrays")
#: the streaming kernel, causal (batch, heads, seq, head_dim, dtype): at the
#: OLMoE cell's attention, at the same in float32 (the cell's reference
#: check runs it) and at 512 bytes a row in bf16 — the block sizes
#: ``_stream_block`` picks have to fit VMEM at each, the backward with a
#: whole (batch . head) row of dq beside them. STREAM_CALLS: the names its
#: two Mosaic calls carry (the benchmark's flash_roofline and
#: mla_flash_roofline find them in a trace by ``flash_stream_`` and
#: ``flash_stream_bwd_dkv``)
STREAM_CASES = {
    "olmoe-cell-bf16": (4, 16, 4096, 128, "bfloat16"),
    "olmoe-check-f32": (1, 16, 4096, 128, "float32"),
    "d256-bf16": (2, 8, 8192, 256, "bfloat16"),
    "d64-bf16": (2, 12, 2048, 64, "bfloat16"),
    # gated grouped-query attention at the Qwen3-Next cell: 16 query heads
    # (K and V repeated to them) of 256 at 16,384 keys — 512-token blocks,
    # and the longest row of dq the one-pass backward admits (32 of 40 MiB)
    "qwen3-next-cell-bf16": (1, 16, 16384, 256, "bfloat16"),
    # latent attention's core at the JoyAI cell: keys (192: not a multiple
    # of the 128 lanes) wider than values (128); in float32 for its check
    "joyai-cell-bf16": (1, 32, 8192, (192, 128), "bfloat16"),
    "joyai-check-f32": (1, 32, 8192, (192, 128), "float32"),
    # with a dropout mask (a sixth element): the hash's tiles need VMEM too
    "joyai-cell-bf16-p0.1": (1, 32, 8192, (192, 128), "bfloat16", 0.1),
    "olmoe-cell-bf16-p0.1": (4, 16, 4096, 128, "bfloat16", 0.1),
    # LFM2's attention layer at its cell: 32 query heads (K and V repeated
    # from 8) of 64 — half a lane group a row, 1,024-token blocks, a dq slab
    # of 8 MiB — 4 rows of 8,192 keys, and the check's 2 rows in float32
    "lfm2-cell-bf16": (4, 32, 8192, 64, "bfloat16"),
    "lfm2-check-f32": (2, 32, 8192, 64, "float32"),
    # Granite-4.0-H's attention layer at its cell: LFM2's geometry at one
    # row, the scores at a scale of its own (a seventh element: 1/64, not
    # 64 ** -0.5), and its check's float32
    "granite-cell-bf16": (1, 32, 8192, 64, "bfloat16", 0.0, 1 / 64),
    "granite-check-f32": (1, 32, 8192, 64, "float32", 0.0, 1 / 64),
}
STREAM_CALLS = ("flash_stream_fwd", "flash_stream_bwd_dkv_dq")
#: a row of dq past the one-pass backward's VMEM budget (65,536 x 128 bf16:
#: 64 MiB of slab and output block): the backward's two calls, each with
#: its own streamed accumulator
STREAM_TWO_CALL_CASES = {
    "long-s65536-bf16": (1, 2, 65536, 128, "bfloat16"),
}
STREAM_TWO_CALLS = ("flash_stream_fwd", "flash_stream_bwd_dq",
                    "flash_stream_bwd_dkv")
ALL_STREAM_CASES = {**STREAM_CASES, **STREAM_TWO_CALL_CASES}
#: the same kernel under a sliding window (batch, heads, seq, head_dim,
#: dtype, window): its BANDED calls at the Trinity-Mini cell's sliding
#: layers (32 query heads of 128, 2,048 of 16,384 keys) in bf16 and in its
#: check's float32 — the one-pass backward's slab beside 1,024-wide blocks
#: — and a row past the slab's budget (the two-call backward). The calls
#: carry names of their own (the benchmark's swa_flash_roofline finds them
#: by ``flash_band_``), and their grids' inner dimension is the band's
BAND_CASES = {
    "trinity-cell-bf16": (1, 32, 16384, 128, "bfloat16", 2048),
    "trinity-check-f32": (1, 32, 16384, 128, "float32", 2048),
    "long-s65536-bf16": (1, 2, 65536, 128, "bfloat16", 2048),
}
BAND_CALLS = ("flash_band_fwd", "flash_band_bwd_dkv_dq")
#: the held experts' grouped matmul where the table's 1,024 tile does not
#: divide an operand (rows, groups, k, n, dtype): LFM2's width 1,792 = 7 x
#: 256 on ``n`` (up, gate) and on ``k`` (down) at the cell's 65,536-row
#: buffer, and in its check's float32 (tile 512: 256 divides)
GMM_CASES = {
    "lfm2-up-bf16": (65536, 8, 2048, 1792, "bfloat16"),
    "lfm2-down-bf16": (65536, 8, 1792, 2048, "bfloat16"),
    "lfm2-up-f32": (32768, 8, 2048, 1792, "float32"),
}
BAND_TWO_CALLS = ("flash_band_fwd", "flash_band_bwd_dq",
                  "flash_band_bwd_dkv")
#: a latent-attention block under ``fleet.utils.recompute``, forward +
#: backward (hidden, heads, q rank, kv rank, nope, rope, value width, seq,
#: dtype, rotated): the JoyAI cell's in its check's float32 and the
#: Kimi-Linear cell's (no q rank, nothing rotated) under amp O1 — the
#: kernel's output and log-sum-exp kept, so two Mosaic calls where a plain
#: ``jax.checkpoint`` compiles three
RECOMPUTED_MLA_CASES = {
    "joyai-check-f32": (2048, 32, 1536, 512, 128, 64, 128, 8192,
                        "float32", True),
    "kimi-cell-bf16": (2304, 32, None, 512, 128, 64, 128, 16384,
                       "bfloat16", False),
}
#: the gated delta rule's scan (batch, seq, heads, head_dim, dtype): at the
#: Kimi-Linear cell's four layers, in float32 (the cell's check runs it),
#: and at a head of two lane groups. KDA_CALLS: the names its two Mosaic
#: calls carry in a trace, under the ``kda.core`` scope
KDA_CASES = {
    "kimi-cell-bf16": (1, 16384, 32, 128, "bfloat16"),
    "kimi-check-f32": (1, 16384, 32, 128, "float32"),
    "d256-bf16": (1, 2048, 4, 256, "bfloat16"),
}
KDA_CALLS = ("kda_chunk_fwd", "kda_chunk_bwd")
#: the same scan with ONE decay a head, [batch, seq, heads] (Gated DeltaNet):
#: at the Qwen3-Next cell's three layers (32 value heads, q and k already
#: repeated to them) and in its check's float32
GDN_CASES = {
    "qwen3-next-cell-bf16": (1, 16384, 32, 128, "bfloat16"),
    "qwen3-next-check-f32": (1, 16384, 32, 128, "float32"),
}
#: one linear-attention LAYER between its projections — the stages of
#: ``text/models.py`` and the scan — forward + backward in bf16 (batch, seq,
#: key heads, value heads, head_dim): ``KimiDeltaAttention`` at the
#: Kimi-Linear cell's shape, ``GatedDeltaNet`` at the Qwen3-Next cell's
LAYER_CASES = {
    "kda-kimi-cell": (1, 16384, 32, 32, 128),
    "gdn-qwen3-next-cell": (1, 16384, 16, 32, 128),
}
#: Mamba-2's biased stage alone (batch, seq, heads, head_dim, d_state, one
#: group): ``text.models._mamba_streams`` forward + VJP in bf16 at the
#: granite-4.0-h-micro cell's shape — x | B | C = 4,096 | 128 | 128 channels
#: of one stream, heads of 64, a bias a channel; the same two calls, under
#: ``mamba.conv`` in a trace
MAMBA_CASES = {
    "mamba-granite-cell": (1, 8192, 64, 64, 128),
}
#: the selective state-space scan at the same shape, forward + VJP on the
#: streams, through its own gate: the names its two Mosaic calls carry in a
#: trace, under ``mamba.core``
SSD_CALLS = ("ssd_chunk_fwd", "ssd_chunk_bwd")
#: the layer's stage between the projections and the scan alone, the
#: names its two Mosaic calls carry (the instructions': the calls sit in
#: inner jits, under the ``kda.conv`` / ``gdn.conv`` scopes in a trace)
CONV_CALLS = ("conv_streams_fwd", "conv_streams_bwd")
#: softmax attention's stage before the core alone, the stems'
#: ``text/models.py`` functions forward + VJP in bf16 (batch, seq, heads,
#: kv_heads, head_dim, rotary_dim): Trinity-Mini's sliding layers (all 128
#: features rotated) and its full layer (no positions) through
#: ``_afmoe_heads``, Qwen3-Next's gated attention (64 of 256 rotated, the
#: query every second column block of its projection) through
#: ``_gqa_heads``. QK_CALLS: the names its two Mosaic calls carry in a
#: trace, under the ``swa.qk`` / ``gattn.qk`` / ``gqa.proj`` scopes
QK_CASES = {
    "trinity-swa": (1, 16384, 32, 4, 128, 128),
    "trinity-gattn": (1, 16384, 32, 4, 128, None),
    "qwen3-next-gqa": (1, 16384, 16, 2, 256, 64),
}
QK_CALLS = ("qk_heads_fwd", "qk_heads_bwd")
#: LFM2's gated short convolution (rows, seq, channels a third, taps,
#: dtype): the stage alone, forward + VJP, at the cell's four rows in bf16
#: and at its check's two rows in float32 (half the tokens a block), and —
#: the bf16 case — ``Lfm2ShortConv`` whole under amp O1. GATED_CALLS: the
#: names its two Mosaic calls carry in a trace, under ``shortconv.stage``
SHORTCONV_CASES = {
    "lfm2-cell-bf16": (4, 8192, 2048, 3, "bfloat16"),
    "lfm2-check-f32": (2, 8192, 2048, 3, "float32"),
}
GATED_CALLS = ("gated_conv_fwd", "gated_conv_bwd")
#: the expert layer's router (tokens, hidden, experts, k, scoring):
#: ``incubate.moe._route`` whole, forward + VJP, through its own gate, at the
#: Qwen3-Next cell's (softmax, renormalised) and the Nemotron cell's (sigmoid
#: + selection bias) and the OLMoE cell's (softmax on 64 experts: the tile
#: padded to a lane group of 128). ROUTE_CALLS: the names the choice's two
#: Mosaic calls carry in a trace, under ``moe.route``
ROUTE_CASES = {
    "qwen3-next-cell": (16384, 2048, 512, 10, "softmax"),
    "nemotron-cell": (4096, 4096, 512, 22, "sigmoid"),
    "olmoe-cell": (16384, 2048, 64, 8, "softmax"),
}
ROUTE_CALLS = ("moe_route_fwd", "moe_route_bwd")
FFN_WIDTH = 3072                  # bert-base's intermediate_size
#: what XLA's expansion of erfc brings into a fusion and erf does not
ERFC_OPCODES = ("exponential", "divide", "select", "compare")
#: a ResNet-50 bottleneck of layer 1 at the cell's rows: [256,256,56,56] in,
#: widths 64 / 64 / 256
BOTTLENECK = {"rows": 256, "inplanes": 256, "planes": 64, "hw": 56}


def _hlo_bytes():
    """tools/hlo_bytes.py (``tools`` is no package)."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import hlo_bytes
    return hlo_bytes


def _fusions(text):
    """The fusions of a compiled module's entry computation: for each, its
    result type, the ``op_name`` of its root and the opcodes of the
    computation it calls, those of nested fusions included."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            bodies[name] = []
        elif name and line.startswith("}"):
            name = None
        elif name:
            bodies[name].append(line)

    def opcodes(computation):
        counts = collections.Counter()
        for line in bodies[computation]:
            op = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = .*? ([\w\-]+)\(", line)
            if not op:
                continue
            counts[op.group(1)] += 1
            called = re.search(r"calls=%([\w.\-]+)", line)
            if op.group(1) == "fusion" and called:
                counts.update(opcodes(called.group(1)))
        return counts

    found = []
    for line in bodies["ENTRY"]:
        fusion = re.match(
            r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) fusion\(.*calls=%([\w.\-]+)",
            line)
        if fusion:
            scope = re.search(r'op_name="([^"]*)"', line)
            found.append({
                "result": re.sub(r"\{[^}]*\}", "", fusion.group(1)),
                "op_name": scope.group(1) if scope else "",
                "opcodes": dict(opcodes(fusion.group(2)))})
    return found


def _child():
    """Every compile, in the described environment; one JSON line out."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.core import random as random_core
    from paddle_tpu.distributed import topology
    from paddle_tpu.ops import attention, placement
    from paddle_tpu.ops.pallas import flash_attention as fa

    # an executable for a described device cannot be read back
    jax.config.update("jax_enable_compilation_cache", False)
    v5e = list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    out = {}

    def packed(sharding, batch, seq, embed, dtype):
        return jax.ShapeDtypeStruct((batch, seq, 3 * embed), dtype,
                                    sharding=sharding)

    for name, (batch, seq, heads, head_dim, dtype, p) in KERNEL_CASES.items():
        def loss(qkv, seed):
            o = fa.mha_packed(qkv, heads, dropout_p=p, seed=seed)
            return jnp.sum(o.astype(jnp.float32))

        admitted = fa.short_supported(seq, heads, head_dim, dtype)
        wider = fa.short_supported(
            seq, heads + 128 // head_dim, head_dim, dtype)
        text = jax.jit(jax.grad(loss)).lower(
            packed(one, batch, seq, heads * head_dim, dtype),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
        ).compile().as_text()
        out[name] = {"mosaic": text.count(MOSAIC), "admitted": admitted,
                     "wider_admitted": wider,
                     "scores_in_hbm": f"{batch},{heads},{seq},{seq}" in text}

    for name, (batch, heads, seq, head_dim, dtype, *p) in (
            ALL_STREAM_CASES.items()):
        d_qk, d_v = (head_dim if isinstance(head_dim, tuple)
                     else (head_dim, head_dim))
        qk = jax.ShapeDtypeStruct((batch, heads, seq, d_qk), dtype,
                                  sharding=one)
        v = jax.ShapeDtypeStruct((batch, heads, seq, d_v), dtype,
                                 sharding=one)
        text = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fa.mha(
                q, k, v, causal=True, dropout_p=p[0] if p else 0.0,
                scale=p[1] if len(p) > 1 else None,
                seed=jnp.zeros((), jnp.int32)).astype(jnp.float32)),
            argnums=(0, 1, 2))).lower(qk, qk, v).compile().as_text()
        out["stream-" + name] = {
            "mosaic": text.count(MOSAIC),
            # a call's name, whole, as its op_name carries it
            "calls": [c for c in dict.fromkeys(STREAM_CALLS + STREAM_TWO_CALLS)
                      if f"({c})" in text],
            "scores_in_hbm": f"{seq},{seq}]" in text,
            "value_wide_results": text.count(
                f"[{batch * heads},{seq},{d_v}]"),
            "key_wide_results": text.count(
                f"[{batch * heads},{seq},{d_qk}]")}

    for name, (batch, heads, seq, head_dim, dtype, window) in (
            BAND_CASES.items()):
        qkv = jax.ShapeDtypeStruct((batch, heads, seq, head_dim), dtype,
                                   sharding=one)
        text = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fa.mha(
                q, k, v, causal=True, window=window,
                seed=jnp.zeros((), jnp.int32)).astype(jnp.float32)),
            argnums=(0, 1, 2))).lower(qkv, qkv, qkv).compile().as_text()
        out["band-" + name] = {
            "mosaic": text.count(MOSAIC),
            "calls": [c for c in dict.fromkeys(BAND_CALLS + BAND_TWO_CALLS)
                      if f"({c})" in text],
            "full_calls": "flash_stream_" in text,
            "scores_in_hbm": f"{seq},{seq}]" in text}

    from paddle_tpu.incubate import moe

    for name, (rows, groups, k, n, dtype) in GMM_CASES.items():
        tiling = moe._gmm_tiling(rows, jnp.dtype(dtype).itemsize, k, n)
        text = jax.jit(jax.grad(
            lambda x, w, sizes: jnp.sum(moe._grouped_matmul(
                x, w, sizes, "mosaic").astype(jnp.float32) ** 2),
            argnums=(0, 1))).lower(
                jax.ShapeDtypeStruct((rows, k), dtype, sharding=one),
                jax.ShapeDtypeStruct((groups, k, n), dtype, sharding=one),
                jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one),
            ).compile().as_text()
        out["gmm-" + name] = {
            "mosaic": text.count(MOSAIC),
            "tiles": [list(tiling(rows, *kn)) for kn in ((k, n), (n, k))]}

    # what the kernel's forward rule does for a recomputed block
    # (ops/residuals.py: the tags, the log-sum-exp's reshape between two
    # negations) folds away where nothing keeps it: the OLMoE cell's
    # attention compiles to the program of the forward rule as it was
    # before there were residuals to offer
    from paddle_tpu.ops import residuals

    def unnamed_fwd(q, k, v, seed, *statics):
        o, lse = fa._fwd(q, k, v, seed, *statics)
        return o, (q, k, v, o, lse, seed)

    def stream_program():
        batch, heads, seq, head_dim, dtype = STREAM_CASES["olmoe-cell-bf16"]
        qkv = jax.ShapeDtypeStruct((batch, heads, seq, head_dim), dtype,
                                   sharding=one)
        offered = residuals._RESIDUAL_TOTAL.value(
            name=residuals.NAMES[1], event="offered")
        text = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fa.mha(q, k, v, causal=True).astype(
                jnp.float32)), argnums=(0, 1, 2))).lower(
                    qkv, qkv, qkv).compile().as_text()
        # the computations, less what differs by where a rule's lines
        # stand: the source tables above them, metadata, the Mosaic bodies
        # (they carry their callers' source lines), and the numbers XLA's
        # names end in (a folded negation has used one up)
        lines = [line.split(", backend_config=")[0] if MOSAIC in line
                 else line for line in text.splitlines()
                 if re.match(r"\s*(%|ROOT |ENTRY |\})", line)]
        text = re.sub(r"(%[A-Za-z_\-]+)[.\d]*", r"\1", re.sub(
            r", metadata=\{[^}]*\}", "", "\n".join(lines)))
        return text, residuals._RESIDUAL_TOTAL.value(
            name=residuals.NAMES[1], event="offered") - offered

    programs = []
    for forward_rule in (fa._flash_fwd, unnamed_fwd):
        fa._flash.defvjp(forward_rule, fa._flash_bwd)
        programs.append(stream_program())
    fa._flash.defvjp(fa._flash_fwd, fa._flash_bwd)
    out["stream-offers"] = {
        "same_program": programs[0][0] == programs[1][0],
        "offered": [programs[0][1], programs[1][1]],
        "mosaic": programs[0][0].count(MOSAIC),
        "negations": [text.count(" negate(") for text, _ in programs]}

    # a recomputed latent-attention block through the gate, as a step on
    # one chip traces it
    from paddle_tpu.core import dispatch
    from paddle_tpu.distributed.fleet.utils import recompute
    from paddle_tpu.text.models import MLAttention

    placement.is_tpu_available = lambda: True
    one_mesh = topology.build_mesh(dp=1, devices=v5e[:1])

    def mosaic_calls(text):
        """(name, result shapes) of a compiled program's Mosaic calls."""
        found = []
        for line in text.splitlines():
            if MOSAIC in line:
                name = re.search(r"/(\w+)/pallas_call", line)
                result = line.split(" custom-call(")[0].split(" = ", 1)[1]
                found.append([name.group(1) if name else "",
                              re.sub(r"\{[^}]*\}", "", result)])
        return sorted(found)

    def named_after_a_kernel(text):
        """Operations that run, other than the Mosaic calls, whose op_name
        holds the forward kernel's name: a trace's readers find a kernel's
        calls by that name and would count each as one."""
        return sum(STREAM_CALLS[0] in line and MOSAIC not in line
                   and not re.search(
                       r" (get-tuple-element|constant|bitcast)\(", line)
                   for line in text.splitlines())

    for name, (hidden, heads, q_rank, kv_rank, nope, rope, d_v, seq, dtype,
               rotated) in RECOMPUTED_MLA_CASES.items():
        block = MLAttention(hidden, heads, q_rank, kv_rank, nope, rope, d_v,
                            rope=rotated)
        block.train()
        params0, buffers0 = block.functional_state()

        def loss(params, x, kept):
            saved = block.functional_state()
            try:
                with dispatch.trace_mode(), topology.tracing_for(one_mesh), \
                        paddle.amp.auto_cast(enable=dtype == "bfloat16",
                                             level="O1"):
                    block.load_functional_state(params, buffers0)
                    if kept:
                        o = recompute(block, paddle.Tensor(x))._value
                    else:
                        o = jax.checkpoint(
                            lambda a: block(paddle.Tensor(a))._value)(x)
            finally:
                block.load_functional_state(*saved)
            return jnp.sum(o.astype(jnp.float32))

        args = ({n: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
                 for n, a in params0.items()},
                jax.ShapeDtypeStruct((1, seq, hidden), jnp.float32,
                                     sharding=one))
        calls, strays = {}, {}
        for kept in (False, True):
            # the float32 check asks every product for float32 in earnest
            with jax.default_matmul_precision(
                    "highest" if dtype == "float32" else "default"):
                # with its value: a gradient alone needs no first forward
                text = jax.jit(jax.value_and_grad(
                    functools.partial(loss, kept=kept),
                    argnums=(0, 1))).lower(*args).compile().as_text()
            calls[kept] = mosaic_calls(text)
            strays[kept] = named_after_a_kernel(text)
        out["recomputed-mla-" + name] = {
            "plain": calls[False], "kept": calls[True],
            "named_after_a_kernel": [strays[False], strays[True]]}

    from paddle_tpu.ops.pallas import linear_attention as kda

    for name, (batch, seq, heads, head_dim, dtype) in (
            list(KDA_CASES.items())
            + [("scalar-" + n, c) for n, c in GDN_CASES.items()]):
        def like(*shape, dtype=dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        x = like(batch, seq, heads, head_dim)
        # a decay per key channel, or one a head
        decay = ((batch, seq, heads) if name.startswith("scalar-")
                 else (batch, seq, heads, head_dim))
        # the cell's float32 check asks every product for float32 in
        # earnest: the bf16 ones inside the kernels must not take that
        with jax.default_matmul_precision("highest"):
            compiled = jax.jit(jax.grad(
                lambda *a: jnp.sum(kda.kda(*a).astype(jnp.float32)),
                argnums=(0, 1, 2, 3, 4))).lower(
                    x, x, x, like(*decay, dtype=jnp.float32),
                    like(batch, seq, heads, dtype=jnp.float32)).compile()
        text = compiled.as_text()
        out["kda-" + name] = {
            # float32 arrays as large as a per-channel decay, in any view
            "decay_sized_f32": len(re.findall(
                rf"f32\[{batch},{seq},({heads},{head_dim}|{heads * head_dim})"
                r"\]", text)),
            "mosaic": text.count(MOSAIC),
            "calls": [c for c in KDA_CALLS if f"({c})" in text],
            "loops": text.count(" while("),
            # the head split's [tokens, heads] <-> [heads, chunks] copies
            "transposes": len(re.findall(r" (transpose|copy)\(", text)),
            "temp_gb": compiled.memory_analysis().temp_size_in_bytes / 1e9}

    from paddle_tpu.ops import linear_attention
    from paddle_tpu.text import models

    for name, (batch, seq, key_heads, heads, d) in LAYER_CASES.items():
        bf16, f32 = jnp.bfloat16, jnp.float32

        def like(*shape, dtype=bf16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        stream = like(batch, seq, heads * d)
        per_head = like(batch, seq, heads, dtype=f32)

        def kda_layer(q, k, v, w_q, w_k, w_v, low, w_up, a_log, dt_bias,
                      beta, gate, w):
            q, k, v = models._kda_streams(q, k, v, w_q, w_k, w_v,
                                          heads=heads, eps=1e-6)
            g = models._kda_decay(low, w_up, a_log, dt_bias, heads=heads)
            return models._kda_gated_norm(kda.kda(q, k, v, g, beta), gate, w,
                                          heads=heads, eps=1e-5)

        def gdn_layer(mixed, taps, a, a_log, dt_bias, b, z, w):
            q, k, v = models._gdn_streams(mixed, taps, key_heads=key_heads,
                                          d_k=d, eps=1e-6)
            q, k = (models._repeat_head_lanes(
                x, heads=key_heads, repeats=heads // key_heads)
                for x in (q, k))
            o = kda.kda(q, k, v, models._gdn_decay(a, a_log, dt_bias),
                        models._gdn_beta(b))
            return models._gdn_gated_norm(o, z, w, heads=heads, eps=1e-6)

        def stage_alone(*args):
            """(q, k, v) and the stage's gradients, the stage as the layer
            calls it."""
            def stage(*a):
                if name[:3] == "kda":
                    return models._kda_streams(*a, heads=heads, eps=1e-6)
                return models._gdn_streams(*a, key_heads=key_heads, d_k=d,
                                           eps=1e-6)

            out, vjp = jax.vjp(stage, *args)
            return out, vjp(out)

        taps = like(4, heads * d)
        mixed = (2 * key_heads + heads) * d
        layer, args = {
            "kda": (kda_layer, (stream, stream, stream, taps, taps, taps,
                                like(batch, seq, d), like(d, heads * d),
                                like(heads, dtype=f32),
                                like(heads * d, dtype=f32), per_head, stream,
                                like(d, dtype=f32))),
            "gdn": (gdn_layer, (like(batch, seq, mixed), like(4, mixed),
                                like(batch, seq, heads),
                                like(heads, dtype=f32),
                                like(heads, dtype=f32),
                                like(batch, seq, heads), stream,
                                like(d, dtype=f32)))}[name[:3]]
        # what the cell's process observes: a platform that compiles
        # Mosaic and one device (here only the compiler is a TPU's and the
        # CPU devices are four, so the gate is told)
        here = placement.kernel
        placement.kernel = lambda **site: "mosaic"
        try:
            text = jax.jit(jax.grad(
                lambda *a: jnp.sum(layer(*a).astype(f32)),
                argnums=tuple(range(len(args))))).lower(
                    *args).compile().as_text()
            conv_args = (args[:6] if name[:3] == "kda" else args[:2])
            conv = jax.jit(stage_alone).lower(*conv_args).compile()
        finally:
            placement.kernel = here
        conv_text = conv.as_text()
        out["conv-" + name] = {
            "mosaic": conv_text.count(MOSAIC),
            "calls": [c for c in CONV_CALLS if f"%{c}" in conv_text],
            # a float32 array as large as a stream in HBM, in any tiling:
            # one the entry computation holds (a fusion's body computes in
            # registers what it is printed to hold)
            "stream_sized_f32": len(re.findall(
                rf"f32\[{batch},{seq},\d\d\d+",
                conv_text[conv_text.index("\nENTRY "):])),
            "temp_gb": conv.memory_analysis().temp_size_in_bytes / 1e9}
        # a float32 array in the [tokens, heads, d] tiling, as XLA writes a
        # relayout to or from it: [.., 16384, H, 128] or [2048, 8, H, 128]
        head_view = rf"f32\[(\d+,)?({seq},\d+,{d}|{seq // 8},8,\d+,{d})\]"
        out["layer-" + name] = {
            "mosaic": text.count(MOSAIC),
            "calls": [c for c in KDA_CALLS if f"({c})" in text]
            + [c for c in CONV_CALLS if f"%{c}" in text],
            "f32_relayouts": sorted(set(re.findall(
                rf"= ({head_view})\S* (?:copy|reshape|transpose)\(", text))),
            "f32_head_views": len(re.findall(head_view, text))}

    for name, (batch, seq, heads, d, state) in MAMBA_CASES.items():
        def like(*shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        def stage_alone(*args):
            out, vjp = jax.vjp(lambda *a: models._mamba_streams(
                *a, inner=heads * d, state=state, head=d), *args)
            return out, vjp(out)

        channels = heads * d + 2 * state
        here = placement.kernel
        placement.kernel = lambda **site: "mosaic"
        try:
            conv = jax.jit(stage_alone).lower(
                like(batch, seq, channels),
                like(4, channels, dtype=jnp.float32),
                like(channels, dtype=jnp.float32)).compile()
        finally:
            placement.kernel = here
        conv_text = conv.as_text()
        out["conv-" + name] = {
            "mosaic": conv_text.count(MOSAIC),
            "calls": [c for c in CONV_CALLS if f"%{c}" in conv_text],
            "stream_sized_f32": len(re.findall(
                rf"f32\[{batch},{seq},\d\d\d+",
                conv_text[conv_text.index("\nENTRY "):])),
            "temp_gb": conv.memory_analysis().temp_size_in_bytes / 1e9}

        def scan_alone(*args):
            out, vjp = jax.vjp(functools.partial(
                linear_attention._ssd_kernel_output, groups=1,
                interpret=False), *args[:-1])
            return out, vjp(args[-1])

        # the gate itself: a TPU's platform (told above) and a mesh of one
        with topology.tracing_for(one_mesh):
            path = linear_attention.ssd_path(seq, heads, 1, d, state,
                                             jnp.bfloat16)
            scan = jax.jit(scan_alone).lower(
                like(batch, seq, heads * d),
                like(batch, seq, heads, dtype=jnp.float32),
                like(heads, dtype=jnp.float32), like(batch, seq, state),
                like(batch, seq, state), like(heads, dtype=jnp.float32),
                like(batch, seq, heads * d)).compile()
        scan_text = scan.as_text()
        out["ssd-" + name] = {
            "path": path, "mosaic": scan_text.count(MOSAIC),
            "calls": [c for c in SSD_CALLS if f"%{c}" in scan_text],
            "loops": scan_text.count(" while("),
            # the head view in HBM, in either order, in any dtype
            "head_views": len(re.findall(
                rf"\[{batch},{seq},{heads},{d}\]|\[{batch},{heads},{seq},{d}\]",
                scan_text)),
            "temp_gb": scan.memory_analysis().temp_size_in_bytes / 1e9}

    for name, (batch, seq, heads, kv_heads, d, rotary_dim) in (
            QK_CASES.items()):
        def like(*shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        gated = name.startswith("qwen3")

        def stage_alone(*args):
            """(q, k, v[, gate]) and the stage's gradients, the stage as
            the layer calls it."""
            def stage(*a):
                kernel = attention.qk_kernel(a[0], heads, kv_heads, d,
                                             rotary_dim)
                if gated:
                    return models._gqa_heads(
                        *a, heads=heads, kv_heads=kv_heads, d=d, eps=1e-6,
                        base=1e7, rotary_dim=rotary_dim, kernel=kernel)
                return models._afmoe_heads(
                    *a, heads=heads, kv_heads=kv_heads, d=d, eps=1e-5,
                    base=1e4, rope=rotary_dim is not None, kernel=kernel)

            out, vjp = jax.vjp(stage, *args)
            return out, vjp(out)

        args = (like(batch, seq, heads * d * (2 if gated else 1)),
                like(batch, seq, kv_heads * d), like(batch, seq, kv_heads * d),
                like(d, dtype=jnp.float32), like(d, dtype=jnp.float32))
        here = placement.kernel
        placement.kernel = lambda **site: "mosaic"
        paths = {p: attention._QK_TOTAL.value(path=p)
                 for p in ("kernel", "xla")}
        try:
            text = jax.jit(stage_alone).lower(*args).compile().as_text()
        finally:
            placement.kernel = here
        out["qk-" + name] = {
            "mosaic": text.count(MOSAIC),
            "calls": [c for c in QK_CALLS if f"%{c}" in text],
            "paths": {p: attention._QK_TOTAL.value(path=p) - n
                      for p, n in paths.items()},
            # a float32 array as large as q in HBM, in the streams' order or
            # the core's, in any tiling: one the entry computation holds
            "q_sized_f32": len(re.findall(
                rf"f32\[{batch},({seq},{heads}|{heads},{seq}),{d}\]"
                rf"|f32\[{batch},{seq},{heads * d}\]",
                text[text.index("\nENTRY "):]))}

    from paddle_tpu.incubate import moe

    for name, (tokens, hidden, experts, k, scoring) in ROUTE_CASES.items():
        def like(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)

        def router_alone(x, w, bias, c):
            """The router's weights and their gradients, the router as the
            layer calls it: the decision outside, riding ``choice=``."""
            choice = moe.route_kernel(tokens, experts, k)

            def weights(x, w):
                return moe._route(
                    x, w, None, bias if scoring == "sigmoid" else None,
                    top_k=k, renorm=True, scoring=scoring, choice=choice,
                    counts=True)

            out, vjp = jax.vjp(lambda x, w: weights(x, w)[0], x, w)
            return weights(x, w), vjp(c)

        here = placement.kernel
        placement.kernel = lambda **site: "mosaic"
        paths = {p: moe._ROUTE_TOTAL.value(path=p) for p in ("kernel", "xla")}
        try:
            text = jax.jit(router_alone).lower(
                like(1, tokens, hidden), like(hidden, experts), like(experts),
                like(tokens, k)).compile().as_text()
        finally:
            placement.kernel = here
        out["route-" + name] = {
            "mosaic": text.count(MOSAIC),
            "calls": [c for c in ROUTE_CALLS if f"%{c}" in text],
            "paths": {p: moe._ROUTE_TOTAL.value(path=p) - n
                      for p, n in paths.items()},
            "sorts": text.count(" sort("),
            # the XLA stage's one-hots, in any dtype and order
            "pair_by_expert_arrays": len(re.findall(
                rf"\[{tokens},({k},{experts}|{experts},{k})\]", text))}

    from paddle_tpu.text.models import Lfm2ShortConv

    for name, (batch, seq, channels, taps, dtype) in SHORTCONV_CASES.items():
        def like(*shape, dtype=dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        def entry_f32(text, scope=""):
            """float32 arrays as large as a third or as the stream that the
            entry computation holds (under ``scope``)."""
            return sum(
                scope in line for line in
                text[text.index("\nENTRY "):].splitlines() if re.search(
                    rf"= f32\[{batch},{seq},({channels}|{3 * channels})\]",
                    line))

        def stage_alone(bcu, w, dy):
            out, vjp = jax.vjp(linear_attention.gated_short_conv, bcu, w)
            return out, vjp(dy)

        paths = {p: linear_attention._SHORTCONV_TOTAL.value(path=p)
                 for p in ("kernel", "xla")}
        # the gate itself: a TPU's platform (told above) and a mesh of one
        with topology.tracing_for(one_mesh), jax.default_matmul_precision(
                "highest" if dtype == "float32" else "default"):
            stage = jax.jit(stage_alone).lower(
                like(batch, seq, 3 * channels),
                like(taps, channels, dtype=jnp.float32),
                like(batch, seq, channels)).compile()
        text = stage.as_text()
        out["shortconv-" + name] = {
            "mosaic": text.count(MOSAIC),
            "calls": [c for c in GATED_CALLS if f"%{c}" in text],
            "paths": {p: linear_attention._SHORTCONV_TOTAL.value(path=p) - n
                      for p, n in paths.items()},
            "stream_sized_f32": entry_f32(text),
            "temp_gb": stage.memory_analysis().temp_size_in_bytes / 1e9}
        if dtype != "bfloat16":
            continue
        layer = Lfm2ShortConv(channels, taps)
        layer.train()
        params0, buffers0 = layer.functional_state()

        def loss(params, x):
            saved = layer.functional_state()
            try:
                with dispatch.trace_mode(), topology.tracing_for(one_mesh), \
                        paddle.amp.auto_cast(level="O1"):
                    layer.load_functional_state(params, buffers0)
                    o = layer(paddle.Tensor(x))._value
            finally:
                layer.load_functional_state(*saved)
            return jnp.sum(o.astype(jnp.float32))

        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            {n: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
             for n, a in params0.items()},
            like(batch, seq, channels)).compile().as_text()
        out["shortconv-" + name].update({
            "layer_mosaic": text.count(MOSAIC),
            "layer_calls": [c for c in GATED_CALLS if f"%{c}" in text],
            "layer_f32": entry_f32(text),
            "layer_f32_under_stage": entry_f32(text, "shortconv.stage")})

    # the s128 cell's FFN under amp O1, forward + backward: what F.gelu's
    # erf lowers to behind linear1's gemm, and what leaves that fusion
    def ffn_loss(p, x):
        with paddle.amp.auto_cast(level="O1"):
            with jax.named_scope("linear1"):
                h = F.linear(paddle.Tensor(x), paddle.Tensor(p["w1"]),
                             paddle.Tensor(p["b1"]))
            g = F.gelu(h)
            o = F.linear(g, paddle.Tensor(p["w2"]), paddle.Tensor(p["b2"]))
        return jnp.sum(jnp.square(o._value.astype(jnp.float32)))

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)

    embed = HEADS * HEAD_DIM
    text = jax.jit(jax.grad(ffn_loss, argnums=(0, 1))).lower(
        {"w1": f32(embed, FFN_WIDTH), "b1": f32(FFN_WIDTH),
         "w2": f32(FFN_WIDTH, embed), "b2": f32(embed)},
        f32(BATCH, SEQ, embed)).compile().as_text()
    wide = f"[{BATCH},{SEQ},{FFN_WIDTH}]"
    fusions = _fusions(text)
    out["ffn-gelu"] = {
        "linear1_forward": [
            f for f in fusions if "convolution" in f["opcodes"]
            and "linear1" in f["op_name"]
            and "transpose(" not in f["op_name"]],
        "f32_wide_results": [f["result"] for f in fusions
                             if "f32" + wide in f["result"]],
        "erfc_expansions": [f["op_name"] for f in fusions
                            if "divide" in f["opcodes"]
                            or "select" in f["opcodes"]]}

    # one ResNet-50 bottleneck, forward + backward + Momentum under amp O1
    # through build_train_step: which fusions carry batch norm's statistics
    from paddle_tpu import optimizer
    from paddle_tpu.distributed import spmd
    from paddle_tpu.vision.models.resnet import BottleneckBlock

    hlo = _hlo_bytes()
    rows, hw = BOTTLENECK["rows"], BOTTLENECK["hw"]
    block = BottleneckBlock(BOTTLENECK["inplanes"], BOTTLENECK["planes"])
    block.train()
    opt = optimizer.Momentum(0.1, parameters=block.parameters())
    one_mesh = topology.build_mesh(dp=1, devices=v5e[:1])
    step, _ = spmd.build_train_step(
        block, lambda o, y: jnp.mean(jnp.square(o)), opt, mesh=one_mesh,
        amp_level="O1", donate=True)
    repl = NamedSharding(one_mesh, P())

    def like(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=repl)

    params0, buffers0 = block.functional_state()
    text = step.jitted.lower(
        {n: like(a) for n, a in params0.items()},
        {n: tuple(like(s) for s in opt._init_state(a))
         for n, a in params0.items()},
        {n: like(jnp.asarray(a)) for n, a in buffers0.items()},
        jax.ShapeDtypeStruct((rows, BOTTLENECK["inplanes"], hw, hw),
                             jnp.float32, sharding=repl),
        jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=repl),
        like(jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=repl),
    ).compile().as_text()
    out["bottleneck-bn"] = {"forward_convolutions": [], "statistics_passes": []}
    for inst, operands, (direction, scope, kind) in hlo.operations(text)[0]:
        if direction != "fwd":
            continue
        if kind == "convolution":
            out["bottleneck-bn"]["forward_convolutions"].append(
                [[dtype, list(dims)] for dtype, dims in inst["result"]])
        elif (scope.startswith("BatchNorm") and kind == "[C]-only reduction"
              and any(len(dims) == 4 and dims[0] == rows
                      for _, dims in operands)):
            out["bottleneck-bn"]["statistics_passes"].append(inst["op_name"])

    # through the gate, on a process with several devices: the kernel where
    # the step announced its mesh, XLA's route where a plain jit is handed
    # arrays on a mesh (the dp4 cell's reference check does that)
    assert jax.device_count() > 1
    mesh = topology.build_mesh(dp=4, devices=v5e)
    sharded = NamedSharding(mesh, P("dp"))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    for name in GATE_CASES:
        def step(qkv, key):
            def loss(qkv):
                o = attention.packed_self_attention(
                    paddle.Tensor(qkv), HEADS, dropout_p=0.1)._value
                return jnp.sum(o.astype(jnp.float32))

            with random_core.rng_guard(key):
                if name == "announced-dp4":
                    with topology.tracing_for(mesh):
                        return jax.grad(loss)(qkv)
                return jax.grad(loss)(qkv)

        before = {r: attention._ROUTE_TOTAL.value(route=r)
                  for r in ("short", "xla")}
        text = jax.jit(step, in_shardings=(sharded, None)).lower(
            packed(sharded, 4 * BATCH, SEQ, HEADS * HEAD_DIM, jnp.bfloat16),
            key).compile().as_text()
        out[name] = {
            "mosaic": text.count(MOSAIC),
            "routes": {r: attention._ROUTE_TOTAL.value(route=r) - n
                       for r, n in before.items()},
            "a_shards_rows":
                f"bf16[{BATCH},{SEQ},{3 * HEADS * HEAD_DIM}]" in text}
    print(json.dumps(out))


@pytest.fixture(scope="module")
def compiled():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no libtpu installed: no TPU compiler to describe a v5e")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, **DESCRIBED_V5E, "PYTHONPATH": repo}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_short_kernel_compiles(compiled, case):
    """Forward + ONE backward Mosaic call within the VMEM the kernel asks
    for, nothing of the scores' shape in HBM; the largest cases are the
    last the gate admits at their sequence length."""
    got = compiled[case]
    assert got["mosaic"] == 2 and not got["scores_in_hbm"]
    assert got["admitted"]
    assert got["wider_admitted"] == case.startswith("cell-shape")


@pytest.mark.parametrize("case", list(ALL_STREAM_CASES))
def test_stream_kernel_compiles(compiled, case):
    """The streaming kernel, forward and the one-pass backward (dq, dk and
    dv from one set of score tiles), causal, at the OLMoE cell's b4 h16
    s4096 d128 bf16, in float32, at d256 and d64, at the JoyAI cell's
    b1 h32 s8192 with 192-wide keys and 128-wide values (bf16 and the
    check's float32) and at the LFM2 cell's b4 h32 s8192 d64 (and its
    check's float32; Granite-4.0-H's one row of it at ``scale=1/64``): two
    Mosaic calls under their names within the VMEM
    the backward asks for at the block sizes the kernel picks, no
    [seq, seq] scores in HBM. A row of dq past the slab's budget compiles
    the two-call backward: three calls."""
    got = compiled["stream-" + case]
    calls = STREAM_CALLS if case in STREAM_CASES else STREAM_TWO_CALLS
    assert got["mosaic"] == len(calls) and got["calls"] == list(calls)
    assert not got["scores_in_hbm"]
    head_dim = ALL_STREAM_CASES[case][3]
    if isinstance(head_dim, tuple):
        # keys and values keep their own widths through every call:
        # nothing is padded to the other's
        assert got["value_wide_results"] and got["key_wide_results"]


@pytest.mark.parametrize("case", list(GMM_CASES))
def test_grouped_matmul_compiles_at_a_divisor_tile(compiled, case):
    """The megablox kernel at LFM2's expert gemms, forward and both
    backward calls (gmm, gmm on the transposed weights, tgmm): three Mosaic
    calls within VMEM at the tiles ``_gmm_tiling`` answers, each of which
    divides its operand (896 of 1,792 in bf16, 256 in float32)."""
    got = compiled["gmm-" + case]
    rows, _, k, n, dtype = GMM_CASES[case]
    assert got["mosaic"] == 3
    narrow = 896 if dtype == "bfloat16" else 256
    for (tm, tk, tn), (kk, nn) in zip(got["tiles"], ((k, n), (n, k))):
        assert rows % tm == 0 and kk % tk == 0 and nn % tn == 0
        assert narrow in (tk, tn)


@pytest.mark.parametrize("case", list(BAND_CASES))
def test_banded_stream_kernel_compiles(compiled, case):
    """The streaming kernel's banded calls (``mha(window=2048)``) at the
    Trinity-Mini cell's b1 h32 s16384 d128, in bf16 and the check's
    float32: the forward and the one-pass backward under ``flash_band_*``
    within the VMEM the backward asks for at 1,024-wide blocks, no
    full-causal call beside them and no [seq, seq] scores in HBM; a row of
    dq past the slab's budget compiles the banded two-call backward."""
    got = compiled["band-" + case]
    calls = BAND_TWO_CALLS if case.startswith("long") else BAND_CALLS
    assert got["mosaic"] == len(calls) and got["calls"] == list(calls)
    assert not got["full_calls"] and not got["scores_in_hbm"]


def test_an_offer_nothing_keeps_compiles_to_nothing(compiled):
    """The streaming kernel's forward rule names its output and
    log-sum-exp for the blocks that keep them (ops/residuals.py), the
    log-sum-exp as [bh, seq] between two negations; at the OLMoE cell's
    attention, which no ``recompute`` wraps, the compiled gradient is the
    program of a forward rule that does none of it (the rule as it was
    before): the tags lower to nothing, XLA folds the reshape there and
    back and the two signs, and no negation is left. The two traces did
    differ: one counted an offer, the other none."""
    got = compiled["stream-offers"]
    assert got["offered"] == [1, 0]
    assert got["mosaic"] == 2 and got["negations"] == [0, 0]
    assert got["same_program"]


@pytest.mark.parametrize("case", list(RECOMPUTED_MLA_CASES))
def test_recomputed_latent_attention_keeps_the_kernels_residuals(
        compiled, case):
    """A latent-attention block's gradient under ``fleet.utils.recompute``
    at the JoyAI cell's widths (float32, inside the check's
    ``default_matmul_precision("highest")``) and at the Kimi-Linear cell's
    (bf16 under amp O1, 16,384 keys): ONE forward and one backward Mosaic
    call, where the same block under a plain ``jax.checkpoint`` compiles a
    second forward call — and each is the call Mosaic was asked for
    before: the same names and result shapes."""
    got = compiled["recomputed-mla-" + case]
    assert [c[0] for c in got["kept"]] == sorted(STREAM_CALLS)
    assert [c[0] for c in got["plain"]] == sorted(
        STREAM_CALLS + STREAM_CALLS[:1])
    assert all(call in got["plain"] for call in got["kept"])
    # XLA names a relayout after its operand: the kept log-sum-exp's
    # un-padding must not run under the kernel's name (the benchmark's
    # readers count events by it)
    assert got["named_after_a_kernel"] == [0, 0]


@pytest.mark.parametrize("case", list(GDN_CASES))
def test_scalar_decay_delta_rule_kernels_compile(compiled, case):
    """The scan with one decay a head at the Qwen3-Next cell's b1 s16384
    h32 d128, in bf16 and in the check's float32: the same two Mosaic
    calls, no loop left to XLA — and in bf16 NO float32 array of the
    per-channel decay's size anywhere in the program, forward or backward:
    the decay comes and its gradient leaves as [batch, seq, heads] (in the
    float32 case q, k, v and their gradients are such arrays themselves)."""
    got = compiled["kda-scalar-" + case]
    assert got["mosaic"] == 2 and got["calls"] == list(KDA_CALLS)
    assert got["loops"] == 0
    if "bf16" in case:
        assert got["decay_sized_f32"] == 0
    # the per-channel program at the same shape does hold them
    assert compiled["kda-kimi-cell-bf16"]["decay_sized_f32"] > 0
    batch, seq, heads, d, _ = GDN_CASES[case]
    kept = 4 * batch * heads * seq * (d * d // 64 + 128 + 64) / 1e9
    streams = batch * seq * heads * d * (3 * 2 + (4 if "f32" in case
                                                  else 0)) * 2 / 1e9
    assert got["temp_gb"] <= 1.25 * (kept + streams) + 0.05, (got, kept)


@pytest.mark.parametrize("case", list(LAYER_CASES))
def test_a_linear_attention_layer_keeps_one_tiling(compiled, case):
    """A whole layer between its projections — convolution + SiLU, the L2
    norms, the decay, (the key heads' repeat,) the scan, the gated output
    norm — forward + backward at the cell's shape under amp O1's dtypes:
    the scan's two Mosaic calls and the convolution stage's two (q, k and
    v share a call a pass), and NO float32 array anywhere in the
    program in the [tokens, heads, d] tiling, let alone a copy, reshape or
    transpose to or from it: every stage stays on the kernels' [tokens,
    heads x d] (until PR 39 the Kimi-Linear layer held 13 such relayouts of
    268 MB, 95 ms of an 891 ms step)."""
    got = compiled["layer-" + case]
    assert got["mosaic"] == 4
    assert got["calls"] == list(KDA_CALLS + CONV_CALLS)
    assert got["f32_relayouts"] == []
    assert got["f32_head_views"] == 0


@pytest.mark.parametrize("case", list(LAYER_CASES) + list(MAMBA_CASES))
def test_the_convolution_stage_keeps_its_float32_in_vmem(compiled, case):
    """The stage alone, forward + VJP at the cell's shape in bf16: one
    Mosaic call a pass for q, k and v together — three streams of 4,096
    channels (Kimi-Linear), the segments 2,048 | 2,048 | 4,096 of one
    (Qwen3-Next) or, with a bias a channel and heads of 64, Mamba-2's
    x | B | C = 4,096 | 128 | 128 of one (granite-4.0-h-micro) — and no
    float32 array as large as a stream in HBM (the XLA stage held five of
    268 MB a stream); what the program holds beside its arguments and
    results is not more than the three bf16 streams the backward writes
    before they are one again."""
    got = compiled["conv-" + case]
    assert got["mosaic"] == 2 and got["calls"] == list(CONV_CALLS)
    assert got["stream_sized_f32"] == 0
    if case in MAMBA_CASES:
        batch, seq, heads, d, state = MAMBA_CASES[case]
        channels = heads * d + 2 * state
    else:
        batch, seq, key_heads, heads, d = LAYER_CASES[case]
        channels = (2 * key_heads + heads) * d
    assert got["temp_gb"] <= 1.1 * batch * seq * channels * 2 / 1e9, got


@pytest.mark.parametrize("case", list(QK_CASES))
def test_the_stage_before_the_core_keeps_its_float32_in_vmem(compiled, case):
    """A stem's stage alone, forward + VJP at its cell's shape in bf16: the
    layer's own function takes the kernels (counted ``kernel``), one Mosaic
    call a pass for q and k together, and no float32 array as large as q in
    HBM in either order (the XLA stage holds several of 268 MB a pass, and
    the relayouts between them)."""
    got = compiled["qk-" + case]
    assert got["mosaic"] == 2 and got["calls"] == list(QK_CALLS)
    assert got["paths"] == {"kernel": 1, "xla": 0}
    assert got["q_sized_f32"] == 0


@pytest.mark.parametrize("case", list(SHORTCONV_CASES))
def test_the_gated_short_convolution_keeps_its_float32_in_vmem(compiled,
                                                               case):
    """LFM2's stage alone through its own gate, forward + VJP at the cell's
    shape in bf16 and at its check's in float32: both kernels compile for
    the described v5e within the VMEM they ask for, one Mosaic call a pass
    (counted ``kernel``), what the program holds beside its arguments and
    results is nothing (``bcu`` and the taps are the residuals, ``d bcu``
    is written as one array), and in bf16 no float32 array as large as a
    third or as the stream is in HBM — nor in ``Lfm2ShortConv`` whole under
    amp O1, where the XLA stage's backward holds them by the GB."""
    got = compiled["shortconv-" + case]
    assert got["mosaic"] == 2 and got["calls"] == list(GATED_CALLS)
    assert got["paths"] == {"kernel": 1, "xla": 0}
    assert got["temp_gb"] <= 0.01, got
    if "bf16" in case:
        assert got["stream_sized_f32"] == 0
        assert got["layer_mosaic"] == 2
        assert got["layer_calls"] == list(GATED_CALLS)
        assert got["layer_f32"] == got["layer_f32_under_stage"] == 0


@pytest.mark.parametrize("case", list(ROUTE_CASES))
def test_the_routers_choice_compiles_to_one_call_a_pass(compiled, case):
    """The router whole through its own gate, forward + VJP at a cell's
    shape: ``route_path`` says ``kernel`` (counted), both kernels compile
    for the described v5e within the VMEM they ask for, one Mosaic call a
    pass, and the XLA stage's work is gone from the program — no sort, no
    array as large as [tokens, k, experts]."""
    got = compiled["route-" + case]
    assert got["mosaic"] == 2 and got["calls"] == list(ROUTE_CALLS)
    assert got["paths"] == {"kernel": 1, "xla": 0}
    assert got["sorts"] == 0 and got["pair_by_expert_arrays"] == 0


@pytest.mark.parametrize("case", list(MAMBA_CASES))
def test_state_space_scan_kernels_compile(compiled, case):
    """The selective state-space scan alone through its own gate, forward +
    VJP on the streams at the granite cell's shape in bf16 (1 x 8,192 x 64
    heads of 64 on a state of 128, one group): ``ssd_path`` says ``kernel``,
    both kernels compile for the described v5e within the VMEM they ask for,
    one Mosaic call a pass, no loop left to XLA, the head view [.., H, P]
    nowhere in HBM, and what the program holds beside its arguments and
    results is the kept entering states (8,192 / 128 chunks x 64 heads x 32
    KB = 134 MB) and the float32 partial sums of dB and dC."""
    got = compiled["ssd-" + case]
    assert got["path"] == "kernel"
    assert got["mosaic"] == 2 and got["calls"] == list(SSD_CALLS)
    assert got["loops"] == 0 and got["head_views"] == 0
    batch, seq, heads, d, state = MAMBA_CASES[case]
    kept = 4 * batch * seq // 128 * heads * state * d / 1e9
    assert got["temp_gb"] <= kept + 0.1, (got, kept)


@pytest.mark.parametrize("case", list(KDA_CASES))
def test_delta_rule_kernels_compile(compiled, case):
    """The gated delta rule's scan, forward (keeping what the backward
    takes) and backward, at the Kimi-Linear cell's b1 s16384 h32 d128 in
    bf16 and in the check's float32 and at a 256-wide head: two Mosaic calls
    under their names within the VMEM they ask for, no loop over chunks or
    segments left to XLA, and the layer's [batch, tokens, heads x d] arrays
    read in place — what is kept for the backward (entering states, pair
    terms, inverses: 0.94 GB at the cell's shape) is the temporary memory."""
    got = compiled["kda-" + case]
    assert got["mosaic"] == 2 and got["calls"] == list(KDA_CALLS)
    assert got["loops"] == 0
    batch, seq, heads, d, _ = KDA_CASES[case]
    kept = 4 * batch * heads * seq * (d * d // 64 + 128 + 64) / 1e9
    # and, for arrays given as [batch, tokens, heads, d], their [tokens,
    # heads x d] copies: in a step XLA fuses those into the producers
    streams = batch * seq * heads * d * (3 * 2 + 4 + (4 if "f32" in case
                                                      else 0)) * 2 / 1e9
    assert got["temp_gb"] <= 1.25 * (kept + streams) + 0.05, (got, kept)


def test_gelu_stays_one_erf_behind_the_ffn_up_gemm(compiled):
    """F.gelu under amp O1 at the s128 cell's FFN shape: the fusion rooted
    at linear1's forward gemm evaluates ONE native ``erf`` an element and
    nothing of erfc's three-branch expansion, writes the pre-activation
    and gelu's value in bf16 and nothing else of that shape; no fusion of
    the program hands on a float32 ``[256,128,3072]``. A jax or XLA that
    expands ``erf`` (or brings ``erfc`` back) fails here, not on the chip.
    (The cell's whole step keeps the same two tensors only because gelu's
    value sits behind a barrier there: PERF.md section 6, PR 26.)"""
    got = compiled["ffn-gelu"]
    fusion, = got["linear1_forward"]
    assert fusion["opcodes"].get("erf") == 1
    assert not [op for op in ERFC_OPCODES if op in fusion["opcodes"]]
    wide = f"bf16[{BATCH},{SEQ},{FFN_WIDTH}]"
    assert fusion["result"] == f"({wide}, {wide})"
    assert got["f32_wide_results"] == []
    assert got["erfc_expansions"] == []


def test_batch_norm_statistics_ride_the_convolution_epilogue(compiled):
    """One ResNet-50 bottleneck at the cell's layer-1 shapes, forward +
    backward under amp O1: each of the three forward convolution fusions
    carries TWO float32 ``[C]`` results beside its bf16 output (the sums of
    ``x - c`` and of its square: ``F.batch_norm``'s one-pass statistics),
    and no forward fusion scoped under ``BatchNorm2D`` reads a
    ``[256,C,H,W]`` operand to return only ``[C]``-sized results — the
    standalone variance pass of the two-pass form (53 of them, 5.69 GB a
    step, in the whole ResNet-50 step before PR 28: PERF.md section 5). A
    jax or XLA that stops fusing a second reduction into a convolution's
    epilogue fails here, not on the chip."""
    got = compiled["bottleneck-bn"]
    rows, hw = BOTTLENECK["rows"], BOTTLENECK["hw"]
    widths = sorted(r[0][1][0] for r in got["forward_convolutions"])
    assert widths == [64, 64, 256]
    for result in got["forward_convolutions"]:
        c = result[0][1][0]
        assert result == [["f32", [c]], ["f32", [c]],
                          ["bf16", [rows, c, hw, hw]]]
    assert got["statistics_passes"] == []


def test_announced_mesh_keeps_the_kernel_under_shard_map(compiled):
    """Inside a step traced for an announced mesh the kernel shards itself
    (batch over dp) and stays a Mosaic call, a shard's rows a call."""
    got = compiled["announced-dp4"]
    assert got["routes"] == {"short": 1.0, "xla": 0.0}
    assert got["mosaic"] == 2 and got["a_shards_rows"]


def test_plain_jit_over_mesh_arrays_takes_the_xla_route(compiled):
    """GSPMD cannot partition a Mosaic call, and only the lowering would
    see that a plain jit's arrays live on a mesh: with several devices and
    no announced mesh the gate says xla, counts xla, and XLA partitions
    its own route (a shard's 64 rows a device)."""
    got = compiled["plain-jit-over-mesh-arrays"]
    assert got["routes"] == {"short": 0.0, "xla": 1.0}
    assert got["mosaic"] == 0


if __name__ == "__main__":
    _child()
