#!/usr/bin/env python3
"""hlo_bytes — a byte bound for a compiled program, read from its HLO text.

For every top-level operation of the entry computation (fusions,
convolutions, custom calls, the few plain copies and reductions XLA leaves
unfused): the bytes of its operands plus the bytes of its results, tensors
of any size. Left out: what moves nothing (parameters, tuples,
get-tuple-elements, bitcasts, constants) and the asynchronous copies and
slices (``*-start`` / ``*-done``: prefetches XLA overlaps with compute;
their bytes are printed beside the total). Each operation is put in a class
by what its ``op_name`` says and by what it computes:

- direction: ``bwd`` where the scope path holds ``transpose(``, else ``fwd``
  where it holds a module scope (``<attr>:<Class>``), else ``-``;
- scope: the class of the innermost module scope (``Conv2D``,
  ``BatchNorm2D``, ...), ``-`` without one;
- kind: ``convolution`` where the operation (or the computation a fusion
  calls) holds a convolution; ``[C]-only reduction`` where every result is
  a scalar or a vector and some operand is not; else ``elementwise``.

The sum over the table is what the program moves through HBM if every
operand is read once and every result written once: divided by the chip's
bandwidth it is the least time the step can take while it keeps this set of
fusions. It is a bound computed from shapes, never a measured time.

Recipe for a benchmark cell's step (PERF.md section 5): on a scratch copy,
let ``benchmark/tools/aot_compile.py`` write ``compiled.as_text()`` to a
file, run it with ``--workload <cell>`` (it compiles for a described v5e,
no chip), then ``python3 tools/hlo_bytes.py <file> [--batch 256]``.
"""
import argparse
import collections
import json
import re
import sys

#: bytes an element; a dtype that is not here is an error, not a default
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
               "s4": 1, "u4": 1, "token": 0}
#: top-level opcodes that move nothing through HBM by themselves
MOVES_NOTHING = ("parameter", "tuple", "get-tuple-element", "bitcast",
                 "constant", "iota", "after-all", "partition-id",
                 "replica-id")
HBM_GB_PER_S = 819.0        # one TPU v5e chip (Google Cloud, "TPU v5e")

_ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_MODULE = re.compile(r"([\w.\-]+):([A-Z]\w*)")


def _balanced(text, start):
    """Index just past the parenthesis that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    raise ValueError("unbalanced parentheses: " + text[start:start + 80])


def arrays(type_text):
    """``[(dtype, dims)]`` of a result type, a tuple's members in order;
    layouts (``{...}``) are not part of a type's size and are dropped."""
    bare = re.sub(r"\{[^{}]*\}", "", type_text)
    found = []
    for dtype, dims in _ARRAY.findall(bare):
        if dtype not in DTYPE_BYTES:
            raise ValueError(f"unknown dtype {dtype!r} in {type_text[:80]!r}")
        found.append((dtype, tuple(int(d) for d in dims.split(",") if d)))
    return found


def nbytes(array):
    dtype, dims = array
    n = DTYPE_BYTES[dtype]
    for d in dims:
        n *= d
    return n


def parse(text):
    """``(entry, bodies)``: the entry computation's instructions in order
    (name, result arrays, opcode, operand names, op_name, called
    computation) and, for every computation, the opcodes it holds."""
    entry, bodies, current, in_entry = [], {}, None, False
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            current, in_entry = head.group(2), bool(head.group(1))
            bodies[current] = collections.Counter()
            continue
        if current is None:
            continue
        if line.startswith("}"):
            current = None
            continue
        inst = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if not inst:
            continue
        rest = line[inst.end():]
        end = _balanced(rest, 0) if rest.startswith("(") else rest.index(" ")
        type_text, rest = rest[:end], rest[end:].lstrip()
        opcode = rest[:rest.index("(")]
        bodies[current][opcode] += 1
        called = re.search(r"calls=%([\w.\-]+)", rest)
        if called:
            bodies[current]["calls:" + called.group(1)] += 1
        if not in_entry:
            continue
        args = rest[len(opcode):_balanced(rest, len(opcode))]
        scope = re.search(r'op_name="([^"]*)"', rest)
        entry.append({
            "name": inst.group(1), "result": arrays(type_text),
            "opcode": opcode, "operands": re.findall(r"%([\w.\-]+)", args),
            "op_name": scope.group(1) if scope else "",
            "calls": called.group(1) if called else None})
    return entry, bodies


def _holds_convolution(bodies, computation):
    ops = bodies.get(computation, {})
    return bool(ops.get("convolution")) or any(
        _holds_convolution(bodies, k[len("calls:"):])
        for k in ops if k.startswith("calls:"))


def classify(inst, operand_arrays, bodies):
    path = inst["op_name"]
    modules = _MODULE.findall(path)
    direction = ("bwd" if "transpose(" in path else "fwd") if modules else "-"
    scope = modules[-1][1] if modules else "-"
    if inst["opcode"] == "convolution" or (
            inst["calls"] and _holds_convolution(bodies, inst["calls"])):
        kind = "convolution"
    elif (all(len(dims) <= 1 for _, dims in inst["result"])
          and any(len(dims) > 1 for _, dims in operand_arrays)):
        kind = "[C]-only reduction"
    else:
        kind = "elementwise"
    return direction, scope, kind


def operations(text):
    """``(counted, async_bytes)``: the entry computation's operations that
    move bytes, each as ``(instruction, operand arrays, (direction, scope,
    kind))``, and the bytes of the asynchronous copies beside them."""
    entry, bodies = parse(text)
    types = {inst["name"]: inst["result"] for inst in entry}
    counted, async_bytes = [], 0
    for inst in entry:
        if inst["opcode"] in MOVES_NOTHING:
            continue
        if inst["opcode"].endswith("-done"):    # what arrived: read + written
            async_bytes += 2 * sum(map(nbytes, inst["result"]))
        elif not inst["opcode"].endswith(("-start", "-update")):
            operands = [a for name in inst["operands"] for a in types[name]]
            counted.append((inst, operands, classify(inst, operands, bodies)))
    return counted, async_bytes


def account(text, batch=None):
    """The table as a dict: ``rows`` keyed by (direction, scope, kind) with
    ``ops``, ``bytes`` and ``activation_reads`` (operations with an operand
    of rank 3 or more), ``total_bytes``, ``async_bytes`` (left out of the
    total) and ``f32_batch_bytes``: the part of the total in float32
    tensors of rank 3 or more whose leading dimension is ``batch`` (the
    float32 activations; ``batch`` defaults to the leading dimension that
    carries the most bytes)."""
    counted, async_bytes = operations(text)
    if batch is None:
        lead = collections.Counter()
        for inst, operands, _ in counted:
            for a in operands + inst["result"]:
                if len(a[1]) >= 3:
                    lead[a[1][0]] += nbytes(a)
        batch = lead.most_common(1)[0][0] if lead else 0
    rows = collections.defaultdict(
        lambda: {"ops": 0, "bytes": 0, "activation_reads": 0})
    total = f32_batch = 0
    for inst, operands, key in counted:
        moved = operands + inst["result"]
        moved_bytes = sum(map(nbytes, moved))
        rows[key]["ops"] += 1
        rows[key]["bytes"] += moved_bytes
        rows[key]["activation_reads"] += any(len(a[1]) >= 3 for a in operands)
        total += moved_bytes
        f32_batch += sum(nbytes(a) for a in moved if a[0] == "f32"
                         and len(a[1]) >= 3 and a[1][0] == batch)
    return {"rows": dict(rows), "total_bytes": total, "batch": batch,
            "f32_batch_bytes": f32_batch, "async_bytes": async_bytes}


def render(acc, bandwidth=HBM_GB_PER_S):
    lines = ["| direction | scope | kind | ops | reading an activation "
             "| GB | ms at %g GB/s |" % bandwidth, "|---|---|---|---|---|---|---|"]
    for key, row in sorted(acc["rows"].items(),
                           key=lambda kv: -kv[1]["bytes"]):
        gb = row["bytes"] / 1e9
        lines.append("| %s | %s | %s | %d | %d | %.3f | %.2f |" % (
            *key, row["ops"], row["activation_reads"], gb,
            gb / bandwidth * 1e3))
    total = acc["total_bytes"] / 1e9
    lines.append("| **total** | | | %d | | **%.3f** | **%.2f** |" % (
        sum(r["ops"] for r in acc["rows"].values()), total,
        total / bandwidth * 1e3))
    lines.append("in f32[%d,...] tensors of rank >= 3: %.3f GB (%.1f%%)" % (
        acc["batch"], acc["f32_batch_bytes"] / 1e9,
        100.0 * acc["f32_batch_bytes"] / max(acc["total_bytes"], 1)))
    lines.append("left out, asynchronous copies and slices: %.3f GB"
                 % (acc["async_bytes"] / 1e9))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="hlo_bytes", description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("hlo", help="file holding compiled.as_text()")
    ap.add_argument("--batch", type=int, default=None,
                    help="leading dimension of an activation")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    with open(args.hlo) as f:
        acc = account(f.read(), args.batch)
    if args.json:
        acc["rows"] = [dict(zip(("direction", "scope", "kind"), k), **v)
                       for k, v in acc["rows"].items()]
        print(json.dumps(acc))
    else:
        print(render(acc))


if __name__ == "__main__":
    sys.exit(main())
