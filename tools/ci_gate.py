#!/usr/bin/env python
"""CI gate: tracelint + suppression audit + tier-1 pytest (+ chaos,
+ serving), one exit status.

Usage:
    python tools/ci_gate.py [--paths paddle_tpu]
        [--skip-tests] [--pytest-args "tests/ -q -m 'not slow'"]
        [--disable TPU005,...] [--chaos] [--serving] [--serving-chaos]
        [--elastic] [--artifacts] [--fleet] [--decode] [--disagg]
        [--concurrency] [--protocol] [--protocol-impl NAME=PATH]
        [--resources]
        [--clean-paths paddle_tpu/resilience paddle_tpu/inference
         paddle_tpu/obs paddle_tpu/analysis]

Phase 1 runs ``tools/tracelint.py --format json`` over ``--paths`` and
fails on any error-severity finding (the analyzer gates the codebase
that ships it). Phase 2 audits inline ``# tracelint: disable``
directives: every suppression is listed for reviewers, and any found
under a ``--clean-paths`` prefix (default: the resilience subsystem,
which must stay TPU001–TPU008 clean) fails the gate. Phase 3 runs the
tier-1 pytest command (ROADMAP.md) — ``--skip-tests`` elides it,
``--pytest-args`` overrides the selection. With the default selection
the stage diffs the observed failure set against the committed
``KNOWN_FAILURES.json``: a failure NOT on the list fails the gate even
when the total count matches HEAD's, and a listed test that passes also
fails the gate until it is removed from the list (fixes are recorded,
never silently absorbed); the file's ``tier1_flaky`` entries (each with
the rate seen and the cause) may fail or pass. ``--chaos`` adds a
fourth stage running the fault-injection suite (``-m chaos``) on its
own, so recovery paths are exercised and reported separately from the
functional tests. ``--serving`` adds a stage running the
dynamic-batching serving suite (``-m serving``) — including the
slow-marked cases that tier-1's ``not slow`` filter skips.
``--serving-chaos`` adds a stage running the serving fault-injection
suite (``-m 'chaos and serving'``: scheduler death, poisoned-bucket
quarantine, deadlines, hot reload) so the self-healing invariants gate
releases on their own line. ``--elastic`` adds a stage running the
elastic pod-scale training suite (``-m elastic``: multi-process
preemption consensus, reshard-on-resume, straggler detection —
subprocess pods, so it owns its own budget line). ``--artifacts`` adds
a stage running the compiled-artifact-store suite (``-m artifacts``:
bit-flip / torn-publish / version-skew chaos, multi-process
single-flight warmup races), excluded from tier-1 by the same
compositional double-run guard as serving/elastic. ``--fleet`` adds a
stage running the fleet-tier suite (``-m fleet``: router WFQ fairness /
eject-probe-readmit / retry-on-different-replica / drain-zero-drops
units, the chaos-kill multi-replica e2e), with the same compositional
tier-1 exclusion. ``--decode`` adds a stage running the
continuous-batching decode suite plus the quantized-serving suite (``-m
'decode or quant or prefix'``: bitwise solo-vs-batch equivalence across
join/leave events and every wire dtype, per-token SLO enforcement,
streaming-wire + router-relay tests, the slot-purge chaos audit, and
the ISSUE 13 quant ladder — per-channel axis audit, w8/w8a8/bf16w
export + engine + artifact-key contracts), again with the compositional
tier-1 double-run exclusion of BOTH markers. ``--sharded`` adds a stage
running the sharded multi-chip serving suite (``-m sharded``:
per-(bucket, mesh) pjit-program equivalence at engine AND wire level
per wire dtype, mesh-keyed artifact-store round trips with clean skew
misses, decode solo-vs-batch per mesh, the multi-process gloo mesh over
the PR 9 launcher, mesh fail-fasts), with the same compositional tier-1
exclusion — and when ``--fleet`` runs too, the fleet stage narrows to
``fleet and not sharded`` so the dual-marked router-relay case runs
once. ``--disagg`` adds a stage running the disaggregated
prefill/decode serving suite (``-m disagg``: phase-pool routing +
handoff bitwise equivalence, prefill-death retry and decode-death
resume chaos, pool-at-zero degradation, per-pool autoscaler isolation,
handoff metrics exposition), with the same compositional tier-1
double-run exclusion. ``--concurrency`` adds a stage that (a) runs the
TPU3xx concurrency passes (``tracelint.py --concurrency``) STRICTLY —
any unsuppressed TPU3xx finding, warning or error, fails — and (b) runs
the locktrace smoke: ``tests/test_locktrace.py`` under
``PADDLE_TPU_LOCKTRACE=1``, which drives a real BatchingEngine (and a
chaos scenario) with the runtime lock-order sanitizer recording every
acquisition, so the static lock model is verified against observed
behaviour. ``--protocol`` adds a stage running the TPU4xx wire-contract
passes (``tracelint.py --protocol-only``) STRICTLY — any unsuppressed
TPU4xx finding fails: every implementation of the serving wire protocol
(Python server stack, Go/R/C clients) is extracted and diffed against
``paddle_tpu/inference/wire_spec.py``, and the ok-or-retryable error
taxonomy is statically verified over the Python serving stack, so the
protocol can never drift one language at a time (``--protocol-impl
name=path`` forwards an implementation override to tracelint — the
planted-drift gate tests run the stage against mutated fixture copies
this way). ``--resources`` adds a stage that (a) runs the TPU5xx
resource-lifecycle passes (``tracelint.py --resources-only``) STRICTLY
— any unsuppressed TPU50x finding fails: every declared acquire (KV
slot, pooled router socket, compile lockfile, scratch dir, thread,
breaker trip, signal handler) must have an owner that releases it on
every path — and (b) runs the restrace smoke: the decode/fleet/artifact
suites under ``PADDLE_TPU_RESTRACE=1 PADDLE_TPU_RESTRACE_RAISE=1``, so
the declared lifecycle sites are leak-checked at runtime and a suite
ending with a nonzero live-handle census fails. Exit 1 when any phase
fails; the JSON line printed last summarises all of them for log
scrapers (mirroring tools/check_op_benchmark_result.py's contract).
"""
import argparse
import io
import json
import os
import re
import shlex
import subprocess
import sys
import tokenize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACELINT = os.path.join(REPO, "tools", "tracelint.py")

DEFAULT_PYTEST_ARGS = ("tests/ -q -m 'not slow' "
                       "--continue-on-collection-errors -p no:cacheprovider")
# 'and not serving': the serving fault-injection suite (incl. its slow
# subprocess cases) belongs to the --serving-chaos stage —
# plain --chaos must not balloon by minutes because PR 5 added tests
CHAOS_PYTEST_ARGS = "tests/ -q -m 'chaos and not serving' -p no:cacheprovider"
SERVING_PYTEST_ARGS = "tests/ -q -m serving -p no:cacheprovider"
SERVING_CHAOS_PYTEST_ARGS = ("tests/ -q -m 'chaos and serving' "
                             "-p no:cacheprovider")
# the elastic pod suite: multi-process consensus/reshard/straggler e2e
# (including its slow-marked subprocess cases) runs as its own stage
ELASTIC_PYTEST_ARGS = "tests/ -q -m elastic -p no:cacheprovider"
# the artifact-store suite: chaos (bit-flip / torn publish / version
# skew) + multi-process single-flight warmup cases, including its
# slow-marked subprocess races
ARTIFACTS_PYTEST_ARGS = "tests/ -q -m artifacts -p no:cacheprovider"
# the fleet-tier suite: router/registry units (WFQ fairness,
# eject/readmit, retry-on-different-replica, drain-zero-drops) plus
# the slow chaos-kill e2e
FLEET_PYTEST_ARGS = "tests/ -q -m fleet -p no:cacheprovider"
# the continuous-batching decode suite: bitwise equivalence, per-token
# SLOs, streaming wire/router relay, slot-purge chaos. The
# quantized-serving suite (`quant` marker: per-channel axis audit,
# w8/w8a8/bf16w export + engine + store contracts) rides in this stage
# — quantization is the decode path's bandwidth lever, and a separate
# stage would re-pay the same model/ladder setup
DECODE_PYTEST_ARGS = ("tests/ -q -m 'decode or quant or prefix' "
                      "-p no:cacheprovider")
# the sharded multi-chip serving suite: per-(bucket, mesh) engine/wire
# equivalence, mesh-keyed store round trips + skew misses, the
# multi-process gloo mesh via the PR 9 launcher, mesh fail-fasts —
# subprocess-heavy (sharded engines
# need more devices than the tier-1 process has), so it owns a stage
SHARDED_PYTEST_ARGS = "tests/ -q -m sharded -p no:cacheprovider"
# the disaggregated prefill/decode serving suite: phase-pool routing,
# handoff retry + pool-loss degradation chaos, per-pool autoscaler
# isolation, handoff metrics exposition — subprocess-heavy (one replica
# process per pool member), so it owns a stage
DISAGG_PYTEST_ARGS = "tests/ -q -m disagg -p no:cacheprovider"
# subsystems that must stay suppression-free: resilience (PR 2), the
# serving stack (PRs 4-5), the telemetry layer (PR 7), and the analyzer
# itself (PR 8) fix findings instead of silencing them. One carve-out:
# a `tpu-lint: disable=TPU3xx` (concurrency), `=TPU4xx` (wire
# contract) or `=TPU5xx` (resource lifecycle) with a trailing
# justification is a *documented waiver*
# (e.g. "GIL-atomic heartbeat bump", "intentionally partial client") —
# the audit lists it for reviewers but does not fail the gate; the same
# directive WITHOUT a justification, or any trace-safety `tracelint:`
# suppression, still fails. (Intentionally partial protocol clients
# should prefer narrowing their wire_spec.IMPLEMENTATIONS declaration
# over TPU4xx waivers — the spec documents the gap, a waiver hides
# it.)
DEFAULT_CLEAN_PATHS = ("paddle_tpu/resilience", "paddle_tpu/inference",
                       "paddle_tpu/obs", "paddle_tpu/analysis",
                       "paddle_tpu/serialize")

# The committed record of pre-existing tier-1 failures. The tier-1
# stage diffs its observed failure set against this list: a NEW
# failure can no longer hide inside "same N failures as HEAD", and a
# failure that stops failing must be removed from the list (the gate
# fails until it is) — fixes get recorded, not silently absorbed.
KNOWN_FAILURES_FILE = os.path.join(REPO, "KNOWN_FAILURES.json")

# parsed ONLY inside pytest's "short test summary info" section: a
# failing test that logs at ERROR level emits "ERROR    root:file:5 ..."
# captured-log lines at column 0 earlier in the output, which must not
# be read as nodeids.
_FAILLINE_RE = re.compile(r"^(?:FAILED|ERROR) (.+)$")
_SUMMARY_HDR_RE = re.compile(r"=+ short test summary info =+")


def _nodeid_of_summary_line(rest):
    """Strip pytest's ``" - <message>"`` suffix off a short-summary
    line's tail, leaving the nodeid. The separator is the first
    ``" - "`` OUTSIDE parametrize brackets — a nodeid like
    ``test_x[a - b]`` must survive intact, so a plain split would
    truncate it mid-id."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch == "[":
            depth += 1
        elif ch == "]":
            depth = max(0, depth - 1)
        elif depth == 0 and rest.startswith(" - ", i):
            return rest[:i]
    return rest

LOCKTRACE_PYTEST_ARGS = "tests/test_locktrace.py -q -p no:cacheprovider"
RESTRACE_PYTEST_ARGS = ("tests/test_decode.py tests/test_fleet.py "
                        "tests/test_artifact_store.py -q "
                        "-p no:cacheprovider")

_SUPPRESS_RE = re.compile(
    r"#\s*(tracelint|tpu-lint)\s*:\s*disable(?:=([A-Z0-9,\s]+))?(.*)$")


def _suppression_comments(lines):
    """(lineno, comment_text) for every REAL comment token mentioning a
    directive tag — a docstring that *documents* the suppression syntax
    (the analyzer's own modules do) is prose, not a suppression."""
    src = "".join(lines)
    if "tracelint" not in src and "tpu-lint" not in src:
        return []
    try:
        return [(tok.start[0], tok.string)
                for tok in tokenize.generate_tokens(
                    io.StringIO(src).readline)
                if tok.type == tokenize.COMMENT
                and ("tracelint" in tok.string or "tpu-lint" in tok.string)]
    except (tokenize.TokenError, IndentationError, SyntaxError):
        # unparseable file: fall back to the line scan (over-counting
        # beats silently skipping a real suppression)
        return [(i, line) for i, line in enumerate(lines, start=1)
                if "tracelint" in line or "tpu-lint" in line]


def run_tracelint(paths, disable=""):
    cmd = [sys.executable, TRACELINT, "--format", "json", *paths]
    if disable:
        cmd += ["--disable", disable]
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO)
    try:
        report = json.loads(proc.stdout)
    except json.JSONDecodeError:
        crash = proc.stderr.strip()[-2000:]
        print(f"tracelint crashed:\n{crash}", file=sys.stderr)
        return {"errors": -1, "warnings": 0,
                "findings": [],
                "crash": crash}, 1
    return report, proc.returncode


def audit_suppressions(paths, clean_paths):
    """List every inline tracelint / tpu-lint suppression under `paths`;
    flag those under a `clean_paths` prefix as violations — EXCEPT a
    `tpu-lint: disable=TPU3xx` that carries a trailing justification
    (the documented-waiver form the concurrency passes require: every
    such suppression is still listed and counted for reviewers)."""
    entries, violations = [], []
    # clean prefixes may be repo-relative or absolute
    clean = [os.path.normpath(os.path.join(REPO, c)) for c in clean_paths]
    for path in paths:
        full = os.path.join(REPO, path)
        if os.path.isfile(full):
            files = [full]
        else:
            files = [os.path.join(dp, fn)
                     for dp, _, fns in os.walk(full)
                     for fn in fns if fn.endswith(".py")]
        for f in sorted(files):
            rel = os.path.relpath(f, REPO)
            try:
                with open(f, encoding="utf-8", errors="replace") as fh:
                    lines = fh.readlines()
            except OSError:
                continue
            for i, line in _suppression_comments(lines):
                m = _SUPPRESS_RE.search(line)
                if not m:
                    continue
                tag, codes, rest = m.group(1), m.group(2) or "", m.group(3)
                justified = bool(re.search(r"\w", rest or ""))
                entry = {"file": rel, "line": i, "tag": tag,
                         "codes": [c.strip() for c in codes.split(",")
                                   if c.strip()],
                         "justified": justified,
                         "text": line.strip()[:160]}
                entries.append(entry)
                absf = os.path.normpath(os.path.abspath(f))
                in_clean = any(absf.startswith(c + os.sep) or absf == c
                               for c in clean)
                if not in_clean:
                    continue
                waiver = (tag == "tpu-lint" and justified and entry["codes"]
                          and all(c.startswith(("TPU3", "TPU4", "TPU5"))
                                  for c in entry["codes"]))
                if not waiver:
                    violations.append(entry)
    return entries, violations


def run_pytest(pytest_args):
    cmd = [sys.executable, "-m", "pytest", *shlex.split(pytest_args)]
    env = dict(os.environ, JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS",
                                                        "cpu"))
    proc = subprocess.run(cmd, cwd=REPO, env=env)
    return proc.returncode


def run_pytest_capturing_failures(pytest_args):
    """run_pytest, but stream-capture the output and parse the failed
    nodeids out of pytest's short-summary ``FAILED``/``ERROR`` lines.
    Returns (returncode, sorted failed-nodeid list)."""
    cmd = [sys.executable, "-m", "pytest", *shlex.split(pytest_args)]
    env = dict(os.environ, JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS",
                                                        "cpu"))
    proc = subprocess.Popen(cmd, cwd=REPO, env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    failed = set()
    in_summary = False
    for line in proc.stdout:
        print(line, end="")
        if _SUMMARY_HDR_RE.search(line):
            in_summary = True
            continue
        if not in_summary:
            continue
        m = _FAILLINE_RE.match(line.rstrip("\n"))
        if m:
            failed.add(_nodeid_of_summary_line(m.group(1)))
    proc.stdout.close()
    return proc.wait(), sorted(failed)


def load_known_failures(path=KNOWN_FAILURES_FILE, key="tier1"):
    """The committed tier-1 failure list, or None when no file exists
    (the diff is then skipped and plain rc==0 gates the stage).
    ``key="tier1_flaky"`` reads the tests that fail only some of the
    time: entries are ``{"test", "rate", "cause"}`` objects."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    known = data.get(key)
    if not isinstance(known, list):
        return None
    return sorted(str(k["test"] if isinstance(k, dict) else k)
                  for k in known)


def diff_known_failures(failed, known, flaky=()):
    """-> (new, fixed): failures not in the committed list, and
    committed entries that did not fail (each non-empty list fails the
    gate — the first is a regression, the second a stale KNOWN_FAILURES
    entry that must be removed so the fix is recorded). A ``flaky``
    test is neither: it may fail or pass."""
    failed, known = set(failed) - set(flaky), set(known)
    return sorted(failed - known), sorted(known - failed)


def run_concurrency_lint(paths, disable=""):
    """tracelint --concurrency-only, STRICT on the TPU3xx group: any
    unsuppressed concurrency finding (warning or error) fails — the
    acceptance bar is zero, with every waiver inline-annotated and
    justified (which the suppression audit enforces separately). The
    TPU0xx AST family is NOT rerun here: phase 1 already covered it
    over the same paths."""
    cmd = [sys.executable, TRACELINT, "--format", "json",
           "--concurrency-only", *paths]
    if disable:
        cmd += ["--disable", disable]
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO)
    try:
        report = json.loads(proc.stdout)
    except json.JSONDecodeError:
        crash = proc.stderr.strip()[-2000:]
        # surface the traceback — a crashed stage with no diagnostic is
        # undebuggable from the summary line alone
        print(f"concurrency: tracelint crashed:\n{crash}",
              file=sys.stderr)
        return {"tpu3xx": -1, "crash": crash}, False
    tpu3 = [f for f in report.get("findings", [])
            if str(f.get("code", "")).startswith("TPU3")]
    for f in tpu3:
        print(f"concurrency: {f['filename']}:{f['line']}: "
              f"{f['code']} {f['message']}")
    ok = proc.returncode == 0 and not tpu3
    return {"tpu3xx": len(tpu3),
            "timing_s": report.get("timings_s", {}).get("concurrency")}, ok


def run_protocol_lint(impl_overrides=(), disable=""):
    """tracelint --protocol-only, STRICT on the TPU4xx group: any
    unsuppressed wire-contract finding fails — the acceptance bar is
    zero repo-wide, with intentional partial clients declared in
    wire_spec.IMPLEMENTATIONS (and any rare waiver inline-annotated
    and justified, which the suppression audit enforces separately)."""
    cmd = [sys.executable, TRACELINT, "--format", "json",
           "--protocol-only", "paddle_tpu"]
    for ov in impl_overrides:
        cmd += ["--impl", ov]
    if disable:
        cmd += ["--disable", disable]
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO)
    try:
        report = json.loads(proc.stdout)
    except json.JSONDecodeError:
        crash = proc.stderr.strip()[-2000:]
        print(f"protocol: tracelint crashed:\n{crash}", file=sys.stderr)
        return {"tpu4xx": -1, "crash": crash}, False
    tpu4 = [f for f in report.get("findings", [])
            if str(f.get("code", "")).startswith("TPU4")]
    for f in tpu4:
        print(f"protocol: {f['filename']}:{f['line']}: "
              f"{f['code']} {f['message']}")
    ok = proc.returncode == 0 and not tpu4
    return {"tpu4xx": len(tpu4),
            "timing_s": report.get("timings_s", {}).get("protocol")}, ok


def run_locktrace_smoke(pytest_args):
    """The locktrace-enabled smoke: tests/test_locktrace.py with the
    runtime sanitizer armed for the whole pytest process, so the engine
    and chaos scenarios it drives are order-checked for real."""
    cmd = [sys.executable, "-m", "pytest", *shlex.split(pytest_args)]
    env = dict(os.environ,
               JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"),
               PADDLE_TPU_LOCKTRACE="1")
    proc = subprocess.run(cmd, cwd=REPO, env=env)
    return proc.returncode


def run_resources_lint(paths, disable=""):
    """tracelint --resources-only, STRICT on the TPU5xx group: any
    unsuppressed resource-lifecycle finding fails — the acceptance bar
    is zero, with every waiver inline-annotated and justified (which
    the suppression audit enforces separately)."""
    cmd = [sys.executable, TRACELINT, "--format", "json",
           "--resources-only", *paths]
    if disable:
        cmd += ["--disable", disable]
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO)
    try:
        report = json.loads(proc.stdout)
    except json.JSONDecodeError:
        crash = proc.stderr.strip()[-2000:]
        print(f"resources: tracelint crashed:\n{crash}", file=sys.stderr)
        return {"tpu50x": -1, "crash": crash}, False
    tpu5 = [f for f in report.get("findings", [])
            if str(f.get("code", "")).startswith("TPU5")]
    for f in tpu5:
        print(f"resources: {f['filename']}:{f['line']}: "
              f"{f['code']} {f['message']}")
    ok = proc.returncode == 0 and not tpu5
    return {"tpu50x": len(tpu5),
            "timing_s": report.get("timings_s", {}).get("resources")}, ok


def run_restrace_smoke(pytest_args):
    """The restrace-enabled smoke: the decode/fleet/artifact suites
    with the runtime leak sanitizer armed (and raising) for the whole
    pytest process, so every modeled acquire/release site those suites
    drive is census-checked for real — a test session ending with a
    live handle fails in the conftest teardown."""
    cmd = [sys.executable, "-m", "pytest", *shlex.split(pytest_args)]
    env = dict(os.environ,
               JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"),
               PADDLE_TPU_RESTRACE="1",
               PADDLE_TPU_RESTRACE_RAISE="1")
    proc = subprocess.run(cmd, cwd=REPO, env=env)
    return proc.returncode


def main(argv=None):
    ap = argparse.ArgumentParser(prog="ci_gate")
    ap.add_argument("--paths", nargs="*", default=["paddle_tpu"])
    ap.add_argument("--disable", default="")
    ap.add_argument("--skip-tests", action="store_true")
    ap.add_argument("--pytest-args", default=DEFAULT_PYTEST_ARGS)
    ap.add_argument("--chaos", action="store_true",
                    help="also run the training fault-injection suite "
                         "(-m 'chaos and not serving'; serving chaos "
                         "has its own --serving-chaos stage)")
    ap.add_argument("--chaos-args", default=CHAOS_PYTEST_ARGS)
    ap.add_argument("--serving", action="store_true",
                    help="also run the dynamic-batching serving suite "
                         "(-m serving, including its slow-marked cases)")
    ap.add_argument("--serving-args", default=SERVING_PYTEST_ARGS)
    ap.add_argument("--serving-chaos", action="store_true",
                    help="also run the serving fault-injection suite "
                         "(-m 'chaos and serving': scheduler death, "
                         "quarantine, deadlines, hot reload)")
    ap.add_argument("--serving-chaos-args",
                    default=SERVING_CHAOS_PYTEST_ARGS)
    ap.add_argument("--elastic", action="store_true",
                    help="also run the elastic pod-scale training suite "
                         "(-m elastic: multi-process preemption "
                         "consensus, reshard-on-resume, straggler "
                         "detection)")
    ap.add_argument("--elastic-args", default=ELASTIC_PYTEST_ARGS)
    ap.add_argument("--artifacts", action="store_true",
                    help="also run the compiled-artifact-store suite "
                         "(-m artifacts: corruption/torn-publish/"
                         "version-skew chaos, multi-process single-"
                         "flight warmup)")
    ap.add_argument("--artifacts-args", default=ARTIFACTS_PYTEST_ARGS)
    ap.add_argument("--fleet", action="store_true",
                    help="also run the fleet-tier suite (-m fleet: "
                         "router WFQ/eject/drain units, chaos-kill "
                         "multi-replica e2e)")
    ap.add_argument("--fleet-args", default=FLEET_PYTEST_ARGS)
    ap.add_argument("--decode", action="store_true",
                    help="also run the continuous-batching decode + "
                         "quantized-serving suites (-m 'decode or "
                         "quant': bitwise solo-vs-batch equivalence, "
                         "per-token SLOs, streaming wire/router relay, "
                         "slot-purge chaos, quant axis audit + "
                         "export/engine/store contracts)")
    ap.add_argument("--decode-args", default=DECODE_PYTEST_ARGS)
    ap.add_argument("--sharded", action="store_true",
                    help="also run the sharded multi-chip serving "
                         "suite (-m sharded: per-(bucket, mesh) "
                         "engine/wire equivalence, mesh-keyed store "
                         "round trips, multi-process gloo mesh)")
    ap.add_argument("--sharded-args", default=SHARDED_PYTEST_ARGS)
    ap.add_argument("--disagg", action="store_true",
                    help="also run the disaggregated prefill/decode "
                         "serving suite (-m disagg: phase-pool routing "
                         "+ handoff equivalence, handoff-retry and "
                         "pool-loss chaos, per-pool autoscaler "
                         "isolation, handoff metrics)")
    ap.add_argument("--disagg-args", default=DISAGG_PYTEST_ARGS)
    ap.add_argument("--known-failures", default=KNOWN_FAILURES_FILE,
                    help="JSON file naming the committed pre-existing "
                         "tier-1 failures the stage diffs against")
    ap.add_argument("--concurrency", action="store_true",
                    help="also run the TPU3xx concurrency passes "
                         "strictly (zero unsuppressed findings) plus "
                         "the locktrace-enabled smoke suite")
    ap.add_argument("--locktrace-args", default=LOCKTRACE_PYTEST_ARGS)
    ap.add_argument("--protocol", action="store_true",
                    help="also run the TPU4xx wire-contract passes "
                         "strictly (zero unsuppressed findings): "
                         "cross-language protocol drift vs wire_spec "
                         "+ the ok-or-retryable taxonomy")
    ap.add_argument("--resources", action="store_true",
                    help="also run the TPU5xx resource-lifecycle "
                         "passes strictly (zero unsuppressed findings) "
                         "plus the restrace-enabled smoke suites")
    ap.add_argument("--restrace-args", default=RESTRACE_PYTEST_ARGS)
    ap.add_argument("--protocol-impl", action="append", default=[],
                    metavar="NAME=PATH",
                    help="override one implementation's source file "
                         "for the --protocol stage (repeatable; the "
                         "planted-drift gate tests use this)")
    ap.add_argument("--clean-paths", nargs="*",
                    default=list(DEFAULT_CLEAN_PATHS),
                    help="path prefixes where tracelint suppressions "
                         "fail the gate")
    ns = ap.parse_args(argv)

    report, lint_rc = run_tracelint(ns.paths, ns.disable)
    for f in report.get("findings", []):
        if f.get("severity") == "error":
            print(f"{f['filename']}:{f['line']}: {f['code']} {f['message']}")
    lint_ok = lint_rc == 0

    suppressions, violations = audit_suppressions(ns.paths, ns.clean_paths)
    for s in suppressions:
        tag = "VIOLATION" if s in violations else "noted"
        print(f"suppression ({tag}): {s['file']}:{s['line']}: {s['text']}")
    audit_ok = not violations

    tests_ok = True
    known = load_known_failures(ns.known_failures)
    tier1_new, tier1_fixed = [], []
    if not ns.skip_tests:
        pytest_args = ns.pytest_args
        default_based = pytest_args == DEFAULT_PYTEST_ARGS
        if default_based:
            # double-run guards: a dedicated stage owns its marker, so
            # tier-1 must not pay the same suite twice in one gate run
            excl = []
            if ns.serving:
                excl.append("serving")
            elif ns.serving_chaos:
                excl.append("(chaos and serving)")
            if ns.elastic:
                excl.append("elastic")
            if ns.artifacts:
                excl.append("artifacts")
            if ns.fleet:
                excl.append("fleet")
            if ns.decode:
                # the decode stage owns ALL THREE markers
                # (decode or quant or prefix)
                excl.append("decode")
                excl.append("quant")
                excl.append("prefix")
            if ns.sharded:
                excl.append("sharded")
            if ns.disagg:
                excl.append("disagg")
            if excl:
                pytest_args = pytest_args.replace(
                    "'not slow'",
                    "'not slow and not "
                    + " and not ".join(excl) + "'")
        if known is not None and default_based:
            # diff the observed failure set against the committed list:
            # exact match (in both directions) is the only green state
            rc, failed = run_pytest_capturing_failures(pytest_args)
            tier1_new, tier1_fixed = diff_known_failures(
                failed, known,
                load_known_failures(ns.known_failures, "tier1_flaky")
                or ())
            for t in tier1_new:
                print(f"tier1: NEW failure (not in KNOWN_FAILURES.json): "
                      f"{t}", file=sys.stderr)
            for t in tier1_fixed:
                print(f"tier1: {t} passed but is still listed in "
                      "KNOWN_FAILURES.json — remove it so the fix is "
                      "recorded", file=sys.stderr)
            # rc 0 (nothing failed) or 1 (tests failed) are the states
            # the diff adjudicates; anything else (interrupted, usage
            # error, crash) is a failure regardless of the diff
            tests_ok = (rc in (0, 1) and not tier1_new
                        and not tier1_fixed)
        else:
            # custom selections (or no committed list) can't be diffed
            # against the tier-1 failure record: plain rc gating
            tests_ok = run_pytest(pytest_args) == 0

    chaos_ok = True
    if ns.chaos:
        chaos_ok = run_pytest(ns.chaos_args) == 0

    serving_ok = True
    if ns.serving:
        serving_args = ns.serving_args
        if ns.serving_chaos and serving_args == SERVING_PYTEST_ARGS:
            # same guard: the serving-chaos stage owns chaos+serving
            serving_args = serving_args.replace(
                "-m serving", "-m 'serving and not chaos'")
        serving_ok = run_pytest(serving_args) == 0

    serving_chaos_ok = True
    if ns.serving_chaos:
        serving_chaos_ok = run_pytest(ns.serving_chaos_args) == 0

    elastic_ok = True
    if ns.elastic:
        elastic_ok = run_pytest(ns.elastic_args) == 0

    artifacts_ok = True
    if ns.artifacts:
        artifacts_ok = run_pytest(ns.artifacts_args) == 0

    fleet_ok = True
    if ns.fleet:
        fleet_args = ns.fleet_args
        if ns.sharded and fleet_args == FLEET_PYTEST_ARGS:
            # double-run guard: the sharded stage owns the fleet relay
            # case that carries both markers
            fleet_args = fleet_args.replace(
                "-m fleet", "-m 'fleet and not sharded'")
        fleet_ok = run_pytest(fleet_args) == 0

    decode_ok = True
    if ns.decode:
        decode_ok = run_pytest(ns.decode_args) == 0

    sharded_ok = True
    if ns.sharded:
        sharded_ok = run_pytest(ns.sharded_args) == 0

    disagg_ok = True
    if ns.disagg:
        disagg_ok = run_pytest(ns.disagg_args) == 0

    concurrency_ok = True
    conc_report = {}
    if ns.concurrency:
        conc_report, conc_lint_ok = run_concurrency_lint(ns.paths,
                                                         ns.disable)
        locktrace_ok = run_locktrace_smoke(ns.locktrace_args) == 0
        concurrency_ok = conc_lint_ok and locktrace_ok
        conc_report["locktrace_ok"] = locktrace_ok

    protocol_ok = True
    proto_report = {}
    if ns.protocol:
        proto_report, protocol_ok = run_protocol_lint(ns.protocol_impl,
                                                      ns.disable)

    resources_ok = True
    res_report = {}
    if ns.resources:
        res_report, res_lint_ok = run_resources_lint(ns.paths, ns.disable)
        restrace_ok = run_restrace_smoke(ns.restrace_args) == 0
        resources_ok = res_lint_ok and restrace_ok
        res_report["restrace_ok"] = restrace_ok

    summary = {
        "gate": ("tracelint+suppressions+tier1"
                 + ("+chaos" if ns.chaos else "")
                 + ("+serving" if ns.serving else "")
                 + ("+serving-chaos" if ns.serving_chaos else "")
                 + ("+elastic" if ns.elastic else "")
                 + ("+artifacts" if ns.artifacts else "")
                 + ("+fleet" if ns.fleet else "")
                 + ("+decode" if ns.decode else "")
                 + ("+sharded" if ns.sharded else "")
                 + ("+disagg" if ns.disagg else "")
                 + ("+concurrency" if ns.concurrency else "")
                 + ("+protocol" if ns.protocol else "")
                 + ("+resources" if ns.resources else "")),
        "lint_ok": lint_ok,
        "lint_errors": report.get("errors", -1),
        "lint_warnings": report.get("warnings", 0),
        "suppressions": len(suppressions),
        "suppression_violations": len(violations),
        "audit_ok": audit_ok,
        "tests_ok": tests_ok,
        "tests_skipped": bool(ns.skip_tests),
        "known_failures": len(known) if known is not None else -1,
        "tier1_new_failures": len(tier1_new),
        "tier1_fixed_known": len(tier1_fixed),
        "chaos_ok": chaos_ok,
        "chaos_run": bool(ns.chaos),
        "serving_ok": serving_ok,
        "serving_run": bool(ns.serving),
        "serving_chaos_ok": serving_chaos_ok,
        "serving_chaos_run": bool(ns.serving_chaos),
        "elastic_ok": elastic_ok,
        "elastic_run": bool(ns.elastic),
        "artifacts_ok": artifacts_ok,
        "artifacts_run": bool(ns.artifacts),
        "fleet_ok": fleet_ok,
        "fleet_run": bool(ns.fleet),
        "decode_ok": decode_ok,
        "decode_run": bool(ns.decode),
        "sharded_ok": sharded_ok,
        "sharded_run": bool(ns.sharded),
        "disagg_ok": disagg_ok,
        "disagg_run": bool(ns.disagg),
        "concurrency_ok": concurrency_ok,
        "concurrency_run": bool(ns.concurrency),
        "concurrency_tpu3xx": conc_report.get("tpu3xx", 0),
        "locktrace_ok": conc_report.get("locktrace_ok", True),
        "protocol_ok": protocol_ok,
        "protocol_run": bool(ns.protocol),
        "protocol_tpu4xx": proto_report.get("tpu4xx", 0),
        "resources_ok": resources_ok,
        "resources_run": bool(ns.resources),
        "resources_tpu50x": res_report.get("tpu50x", 0),
        "restrace_ok": res_report.get("restrace_ok", True),
    }
    print(json.dumps(summary))
    if not (lint_ok and audit_ok and tests_ok and chaos_ok
            and serving_ok and serving_chaos_ok and elastic_ok
            and artifacts_ok and fleet_ok and decode_ok and sharded_ok
            and disagg_ok and concurrency_ok and protocol_ok
            and resources_ok):
        print("ci_gate: FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
