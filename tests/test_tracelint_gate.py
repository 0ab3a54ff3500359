"""tools/ci_gate.py pass/fail contract (mirroring
tests/test_check_op_benchmark.py): lint phase gates on error findings,
test phase gates on the pytest exit status, and the last stdout line is
a machine-readable JSON summary."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATE = os.path.join(REPO, "tools", "ci_gate.py")

BAD_SRC = ("from paddle_tpu.jit import to_static\n"
           "@to_static\n"
           "def f(x):\n    return float(x.mean())\n")
GOOD_SRC = "def f(x):\n    return x\n"


def _run(args):
    return subprocess.run([sys.executable, GATE, *args],
                          capture_output=True, text=True, cwd=REPO)


def _summary(r):
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_lint_clean_skip_tests_passes(tmp_path):
    f = tmp_path / "good.py"
    f.write_text(GOOD_SRC)
    r = _run(["--paths", str(f), "--skip-tests"])
    assert r.returncode == 0, r.stdout + r.stderr
    s = _summary(r)
    assert s["lint_ok"] and s["tests_skipped"] and s["lint_errors"] == 0


def test_lint_error_fails_gate(tmp_path):
    f = tmp_path / "bad.py"
    f.write_text(BAD_SRC)
    r = _run(["--paths", str(f), "--skip-tests"])
    assert r.returncode == 1
    s = _summary(r)
    assert not s["lint_ok"] and s["lint_errors"] >= 1
    assert "TPU004" in r.stdout  # error findings are listed before the summary
    assert "FAILED" in r.stderr


def test_disable_clears_the_gate(tmp_path):
    f = tmp_path / "bad.py"
    f.write_text(BAD_SRC)
    r = _run(["--paths", str(f), "--skip-tests", "--disable", "TPU004"])
    assert r.returncode == 0
    assert _summary(r)["lint_ok"]


def test_pytest_phase_gates(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    ok_test = tmp_path / "test_ok.py"
    ok_test.write_text("def test_ok():\n    assert True\n")
    r = _run(["--paths", str(good), "--pytest-args",
              f"{ok_test} -q -p no:cacheprovider"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert _summary(r)["tests_ok"]

    fail_test = tmp_path / "test_fail.py"
    fail_test.write_text("def test_no():\n    assert False\n")
    r = _run(["--paths", str(good), "--pytest-args",
              f"{fail_test} -q -p no:cacheprovider"])
    assert r.returncode == 1
    s = _summary(r)
    assert s["lint_ok"] and not s["tests_ok"]


def test_suppression_audit_notes_but_allows_outside_clean_paths(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text("x = 1  # tracelint: disable=TPU007\n")
    r = _run(["--paths", str(f), "--skip-tests"])
    assert r.returncode == 0
    s = _summary(r)
    assert s["suppressions"] == 1 and s["suppression_violations"] == 0
    assert "suppression (noted)" in r.stdout


def test_suppression_in_clean_path_fails_gate(tmp_path):
    sub = tmp_path / "resilience"
    sub.mkdir()
    f = sub / "mod.py"
    f.write_text("x = 1  # tracelint: disable=TPU007\n")
    r = _run(["--paths", str(tmp_path), "--skip-tests",
              "--clean-paths", str(sub)])
    assert r.returncode == 1
    s = _summary(r)
    assert s["suppression_violations"] == 1 and not s["audit_ok"]
    assert "VIOLATION" in r.stdout


def test_resilience_subsystem_is_suppression_free():
    """The shipped clean-zone policy holds: no inline suppressions under
    paddle_tpu/resilience (fix findings there, don't silence them)."""
    r = _run(["--paths", "paddle_tpu/resilience", "--skip-tests"])
    assert r.returncode == 0, r.stdout + r.stderr
    s = _summary(r)
    assert s["suppression_violations"] == 0 and s["lint_errors"] == 0


def test_inference_subsystem_is_suppression_free():
    """The serving stack is a clean zone too (DEFAULT_CLEAN_PATHS): no
    inline tracelint suppressions under paddle_tpu/inference."""
    r = _run(["--paths", "paddle_tpu/inference", "--skip-tests"])
    assert r.returncode == 0, r.stdout + r.stderr
    s = _summary(r)
    assert s["suppression_violations"] == 0 and s["lint_errors"] == 0


def test_obs_subsystem_is_suppression_free():
    """The telemetry layer is a clean zone too (DEFAULT_CLEAN_PATHS):
    no inline tracelint suppressions under paddle_tpu/obs."""
    r = _run(["--paths", "paddle_tpu/obs", "--skip-tests"])
    assert r.returncode == 0, r.stdout + r.stderr
    s = _summary(r)
    assert s["suppression_violations"] == 0 and s["lint_errors"] == 0


def test_inference_is_a_default_clean_path():
    """All clean zones ship in the gate's DEFAULT clean paths (a
    suppression under any fails without any --clean-paths override;
    planting a violation inside the real tree is too invasive to test
    end-to-end, so pin the default list itself)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("ci_gate", GATE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert "paddle_tpu/inference" in mod.DEFAULT_CLEAN_PATHS
    assert "paddle_tpu/resilience" in mod.DEFAULT_CLEAN_PATHS
    assert "paddle_tpu/obs" in mod.DEFAULT_CLEAN_PATHS
    assert "paddle_tpu/analysis" in mod.DEFAULT_CLEAN_PATHS


# --------------------------------------- concurrency stage + audit policy

DEADLOCK_SRC = """
import threading
class Eng:
    def __init__(self):
        self._la = threading.Lock()
        self._lb = threading.Lock()
    def one(self):
        with self._la:
            with self._lb:
                pass
    def two(self):
        with self._lb:
            with self._la:
                pass
"""


def test_concurrency_stage_gates(tmp_path):
    ok_test = tmp_path / "test_smoke_ok.py"
    ok_test.write_text("def test_ok():\n    assert True\n")
    lt_args = f"{ok_test} -q -p no:cacheprovider"

    bad = tmp_path / "bad.py"
    bad.write_text(DEADLOCK_SRC)
    r = _run(["--paths", str(bad), "--skip-tests", "--concurrency",
              "--locktrace-args", lt_args])
    assert r.returncode == 1
    s = _summary(r)
    assert s["concurrency_run"] and not s["concurrency_ok"]
    assert s["concurrency_tpu3xx"] >= 1
    assert "+concurrency" in s["gate"]
    assert "TPU301" in r.stdout

    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    r = _run(["--paths", str(good), "--skip-tests", "--concurrency",
              "--locktrace-args", lt_args])
    assert r.returncode == 0, r.stdout + r.stderr
    s = _summary(r)
    assert s["concurrency_ok"] and s["locktrace_ok"]
    assert s["concurrency_tpu3xx"] == 0


def test_concurrency_stage_fails_on_locktrace_smoke(tmp_path):
    """A red locktrace smoke fails the stage even when the static
    passes are clean."""
    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    bad_test = tmp_path / "test_smoke_bad.py"
    bad_test.write_text("def test_no():\n    assert False\n")
    r = _run(["--paths", str(good), "--skip-tests", "--concurrency",
              "--locktrace-args", f"{bad_test} -q -p no:cacheprovider"])
    assert r.returncode == 1
    s = _summary(r)
    assert s["concurrency_run"] and not s["locktrace_ok"]
    assert not s["concurrency_ok"]


def test_concurrency_summary_keys_present_when_not_run(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    r = _run(["--paths", str(good), "--skip-tests"])
    s = _summary(r)
    assert s["concurrency_run"] is False and s["concurrency_ok"] is True
    assert s["locktrace_ok"] is True and s["concurrency_tpu3xx"] == 0


def test_justified_tpu_lint_waiver_noted_not_violation(tmp_path):
    """The clean-path carve-out: a TPU3xx tpu-lint suppression WITH a
    one-line justification is listed but allowed; the same directive
    without one (or any tracelint trace-safety suppression) still
    fails the gate."""
    sub = tmp_path / "inference"
    sub.mkdir()
    f = sub / "mod.py"
    f.write_text("x = 1  # tpu-lint: disable=TPU305  # benign GIL-atomic "
                 "bump\n")
    r = _run(["--paths", str(tmp_path), "--skip-tests",
              "--clean-paths", str(sub)])
    assert r.returncode == 0, r.stdout + r.stderr
    s = _summary(r)
    assert s["suppressions"] == 1 and s["suppression_violations"] == 0

    f.write_text("x = 1  # tpu-lint: disable=TPU305\n")  # no justification
    r = _run(["--paths", str(tmp_path), "--skip-tests",
              "--clean-paths", str(sub)])
    assert r.returncode == 1
    assert _summary(r)["suppression_violations"] == 1

    # trace-safety suppressions get no waiver, justified or not
    f.write_text("x = 1  # tracelint: disable=TPU007  # because reasons\n")
    r = _run(["--paths", str(tmp_path), "--skip-tests",
              "--clean-paths", str(sub)])
    assert r.returncode == 1
    assert _summary(r)["suppression_violations"] == 1


def test_real_tree_waivers_pass_the_default_gate():
    """The shipped dogfood annotations under paddle_tpu/inference are
    all justified waivers: the default-clean-path audit stays green."""
    r = _run(["--paths", "paddle_tpu/inference", "--skip-tests"])
    assert r.returncode == 0, r.stdout + r.stderr
    s = _summary(r)
    assert s["suppressions"] >= 5  # the PR 8 waivers are listed
    assert s["suppression_violations"] == 0


def test_chaos_stage_gates(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    bad_chaos = tmp_path / "test_chaos_fail.py"
    bad_chaos.write_text(
        "import pytest\n"
        "@pytest.mark.chaos\n"
        "def test_boom():\n    assert False\n")
    r = _run(["--paths", str(good), "--skip-tests", "--chaos",
              "--chaos-args", f"{bad_chaos} -q -m chaos "
                              f"-p no:cacheprovider"])
    assert r.returncode == 1
    s = _summary(r)
    assert s["chaos_run"] and not s["chaos_ok"]
    ok_chaos = tmp_path / "test_chaos_ok.py"
    ok_chaos.write_text(
        "import pytest\n"
        "@pytest.mark.chaos\n"
        "def test_fine():\n    assert True\n")
    r = _run(["--paths", str(good), "--skip-tests", "--chaos",
              "--chaos-args", f"{ok_chaos} -q -m chaos "
                              f"-p no:cacheprovider"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert _summary(r)["chaos_ok"]


def test_elastic_stage_gates(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    bad = tmp_path / "test_elastic_fail.py"
    bad.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.elastic\n"
        "def test_boom():\n    assert False\n")
    r = _run(["--paths", str(good), "--skip-tests", "--elastic",
              "--elastic-args",
              f"{bad} -q -m elastic -p no:cacheprovider"])
    assert r.returncode == 1
    s = _summary(r)
    assert s["elastic_run"] and not s["elastic_ok"]
    assert "+elastic" in s["gate"]
    ok = tmp_path / "test_elastic_ok.py"
    ok.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.elastic\n"
        "def test_fine():\n    assert True\n")
    r = _run(["--paths", str(good), "--skip-tests", "--elastic",
              "--elastic-args",
              f"{ok} -q -m elastic -p no:cacheprovider"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert _summary(r)["elastic_ok"]


def test_elastic_summary_keys_present_when_not_run(tmp_path):
    f = tmp_path / "good.py"
    f.write_text(GOOD_SRC)
    r = _run(["--paths", str(f), "--skip-tests"])
    s = _summary(r)
    assert s["elastic_run"] is False and s["elastic_ok"] is True


def test_elastic_double_run_guard_narrows_tier1():
    """With --elastic, the tier-1 phase must exclude the elastic
    marker (the elastic stage owns it) — checked via the gate module's
    own arg plumbing rather than by paying two pytest runs."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("ci_gate", GATE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    captured = {}

    real_run_pytest = mod.run_pytest
    real_capturing = mod.run_pytest_capturing_failures

    def fake_run_pytest(args):
        captured.setdefault("args", []).append(args)
        return 0

    def fake_capturing(args):
        # the tier-1 phase routes through the failure-capturing runner
        # (KNOWN_FAILURES.json diff); report the committed failures so
        # the diff is clean
        captured.setdefault("args", []).append(args)
        return 1, mod.load_known_failures()

    mod.run_pytest = fake_run_pytest
    mod.run_pytest_capturing_failures = fake_capturing
    mod.run_tracelint = lambda *a, **k: ({"errors": 0, "warnings": 0,
                                          "findings": []}, 0)
    mod.audit_suppressions = lambda *a, **k: ([], [])
    try:
        rc = mod.main(["--elastic"])
    finally:
        mod.run_pytest = real_run_pytest
        mod.run_pytest_capturing_failures = real_capturing
    assert rc == 0
    tier1 = captured["args"][0]
    assert "not elastic" in tier1 and "not slow" in tier1
    assert captured["args"][1] == mod.ELASTIC_PYTEST_ARGS


def test_serving_chaos_stage_gates(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    bad = tmp_path / "test_serving_chaos_fail.py"
    bad.write_text(
        "import pytest\n"
        "pytestmark = [pytest.mark.chaos, pytest.mark.serving]\n"
        "def test_boom():\n    assert False\n")
    r = _run(["--paths", str(good), "--skip-tests", "--serving-chaos",
              "--serving-chaos-args",
              f"{bad} -q -m 'chaos and serving' -p no:cacheprovider"])
    assert r.returncode == 1
    s = _summary(r)
    assert s["serving_chaos_run"] and not s["serving_chaos_ok"]
    assert "+serving-chaos" in s["gate"]
    ok = tmp_path / "test_serving_chaos_ok.py"
    ok.write_text(
        "import pytest\n"
        "pytestmark = [pytest.mark.chaos, pytest.mark.serving]\n"
        "def test_fine():\n    assert True\n")
    r = _run(["--paths", str(good), "--skip-tests", "--serving-chaos",
              "--serving-chaos-args",
              f"{ok} -q -m 'chaos and serving' -p no:cacheprovider"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert _summary(r)["serving_chaos_ok"]


# ------------------------------------ artifacts stage + KNOWN_FAILURES diff

def _gate_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location("ci_gate", GATE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_artifacts_stage_gates(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    bad = tmp_path / "test_artifacts_fail.py"
    bad.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.artifacts\n"
        "def test_boom():\n    assert False\n")
    r = _run(["--paths", str(good), "--skip-tests", "--artifacts",
              "--artifacts-args",
              f"{bad} -q -m artifacts -p no:cacheprovider"])
    assert r.returncode == 1
    s = _summary(r)
    assert s["artifacts_run"] and not s["artifacts_ok"]
    assert "+artifacts" in s["gate"]
    ok = tmp_path / "test_artifacts_ok.py"
    ok.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.artifacts\n"
        "def test_fine():\n    assert True\n")
    r = _run(["--paths", str(good), "--skip-tests", "--artifacts",
              "--artifacts-args",
              f"{ok} -q -m artifacts -p no:cacheprovider"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert _summary(r)["artifacts_ok"]


def test_artifacts_summary_keys_present_when_not_run(tmp_path):
    f = tmp_path / "good.py"
    f.write_text(GOOD_SRC)
    r = _run(["--paths", str(f), "--skip-tests"])
    s = _summary(r)
    assert s["artifacts_run"] is False and s["artifacts_ok"] is True


def test_artifacts_double_run_guard_narrows_tier1():
    """With --artifacts, tier-1 must exclude the artifacts marker (the
    artifacts stage owns it, including its slow subprocess cases)."""
    mod = _gate_module()
    captured = {}

    def fake_capturing(args):
        captured.setdefault("args", []).append(args)
        return 1, mod.load_known_failures()

    mod.run_pytest = lambda args: (
        captured.setdefault("args", []).append(args) or 0)
    mod.run_pytest_capturing_failures = fake_capturing
    mod.run_tracelint = lambda *a, **k: ({"errors": 0, "warnings": 0,
                                          "findings": []}, 0)
    mod.audit_suppressions = lambda *a, **k: ([], [])
    rc = mod.main(["--artifacts"])
    assert rc == 0
    tier1 = captured["args"][0]
    assert "not artifacts" in tier1 and "not slow" in tier1
    assert captured["args"][1] == mod.ARTIFACTS_PYTEST_ARGS


def test_decode_stage_gates(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    bad = tmp_path / "test_decode_fail.py"
    bad.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.decode\n"
        "def test_boom():\n    assert False\n")
    r = _run(["--paths", str(good), "--skip-tests", "--decode",
              "--decode-args",
              f"{bad} -q -m decode -p no:cacheprovider"])
    assert r.returncode == 1
    s = _summary(r)
    assert s["decode_run"] and not s["decode_ok"]
    assert "+decode" in s["gate"]
    ok = tmp_path / "test_decode_ok.py"
    ok.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.decode\n"
        "def test_fine():\n    assert True\n")
    r = _run(["--paths", str(good), "--skip-tests", "--decode",
              "--decode-args",
              f"{ok} -q -m decode -p no:cacheprovider"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert _summary(r)["decode_ok"]


def test_decode_summary_keys_present_when_not_run(tmp_path):
    f = tmp_path / "good.py"
    f.write_text(GOOD_SRC)
    r = _run(["--paths", str(f), "--skip-tests"])
    s = _summary(r)
    assert s["decode_run"] is False and s["decode_ok"] is True


def test_decode_double_run_guard_narrows_tier1():
    """With --decode, tier-1 must exclude ALL THREE markers the decode
    stage owns ('-m decode or quant or prefix', including the
    quant-ladder and prefix/spec contracts)."""
    mod = _gate_module()
    captured = {}

    def fake_capturing(args):
        captured.setdefault("args", []).append(args)
        return 1, mod.load_known_failures()

    mod.run_pytest = lambda args: (
        captured.setdefault("args", []).append(args) or 0)
    mod.run_pytest_capturing_failures = fake_capturing
    mod.run_tracelint = lambda *a, **k: ({"errors": 0, "warnings": 0,
                                          "findings": []}, 0)
    mod.audit_suppressions = lambda *a, **k: ([], [])
    rc = mod.main(["--decode"])
    assert rc == 0
    tier1 = captured["args"][0]
    assert "not decode" in tier1 and "not slow" in tier1
    assert "not quant" in tier1
    assert "not prefix" in tier1
    assert captured["args"][1] == mod.DECODE_PYTEST_ARGS
    assert "decode or quant or prefix" in mod.DECODE_PYTEST_ARGS


def test_prefix_marker_rides_decode_stage(tmp_path):
    """Red/green for the prefix marker through the decode stage: a
    failing prefix-marked test must gate --decode red; a passing one
    leaves it green (the marker is folded, not a separate stage)."""
    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    bad = tmp_path / "test_prefix_fail.py"
    bad.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.prefix\n"
        "def test_boom():\n    assert False\n")
    r = _run(["--paths", str(good), "--skip-tests", "--decode",
              "--decode-args",
              f"{bad} -q -m 'decode or quant or prefix' "
              "-p no:cacheprovider"])
    assert r.returncode == 1
    s = _summary(r)
    assert s["decode_run"] and not s["decode_ok"]
    ok = tmp_path / "test_prefix_ok.py"
    ok.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.prefix\n"
        "def test_fine():\n    assert True\n")
    r = _run(["--paths", str(good), "--skip-tests", "--decode",
              "--decode-args",
              f"{ok} -q -m 'decode or quant or prefix' "
              "-p no:cacheprovider"])
    assert _summary(r)["decode_ok"]


def test_sharded_stage_gates(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    bad = tmp_path / "test_sharded_fail.py"
    bad.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.sharded\n"
        "def test_boom():\n    assert False\n")
    r = _run(["--paths", str(good), "--skip-tests", "--sharded",
              "--sharded-args",
              f"{bad} -q -m sharded -p no:cacheprovider"])
    assert r.returncode == 1
    s = _summary(r)
    assert s["sharded_run"] and not s["sharded_ok"]
    assert "+sharded" in s["gate"]
    ok = tmp_path / "test_sharded_ok.py"
    ok.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.sharded\n"
        "def test_fine():\n    assert True\n")
    r = _run(["--paths", str(good), "--skip-tests", "--sharded",
              "--sharded-args",
              f"{ok} -q -m sharded -p no:cacheprovider"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert _summary(r)["sharded_ok"]


def test_sharded_summary_keys_present_when_not_run(tmp_path):
    f = tmp_path / "good.py"
    f.write_text(GOOD_SRC)
    r = _run(["--paths", str(f), "--skip-tests"])
    s = _summary(r)
    assert s["sharded_run"] is False and s["sharded_ok"] is True


def test_sharded_double_run_guard_narrows_tier1_and_fleet():
    """With --sharded, tier-1 excludes the sharded marker; with
    --fleet AND --sharded, the fleet stage narrows to 'fleet and not
    sharded' so the dual-marked router-relay case runs exactly once
    (in the sharded stage, which owns -m sharded)."""
    mod = _gate_module()
    captured = {}

    def fake_capturing(args):
        captured.setdefault("args", []).append(args)
        return 1, mod.load_known_failures()

    mod.run_pytest = lambda args: (
        captured.setdefault("args", []).append(args) or 0)
    mod.run_pytest_capturing_failures = fake_capturing
    mod.run_tracelint = lambda *a, **k: ({"errors": 0, "warnings": 0,
                                          "findings": []}, 0)
    mod.audit_suppressions = lambda *a, **k: ([], [])
    rc = mod.main(["--fleet", "--sharded"])
    assert rc == 0
    tier1 = captured["args"][0]
    assert "not sharded" in tier1 and "not fleet" in tier1 \
        and "not slow" in tier1
    stage_args = captured["args"][1:]
    assert "'fleet and not sharded'" in stage_args[0]
    assert stage_args[1] == mod.SHARDED_PYTEST_ARGS
    # --fleet alone keeps the full fleet selection
    captured.clear()
    rc = mod.main(["--fleet"])
    assert rc == 0
    assert captured["args"][1] == mod.FLEET_PYTEST_ARGS


def test_disagg_stage_gates(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(GOOD_SRC)
    bad = tmp_path / "test_disagg_fail.py"
    bad.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.disagg\n"
        "def test_boom():\n    assert False\n")
    r = _run(["--paths", str(good), "--skip-tests", "--disagg",
              "--disagg-args",
              f"{bad} -q -m disagg -p no:cacheprovider"])
    assert r.returncode == 1
    s = _summary(r)
    assert s["disagg_run"] and not s["disagg_ok"]
    assert "+disagg" in s["gate"]
    ok = tmp_path / "test_disagg_ok.py"
    ok.write_text(
        "import pytest\n"
        "pytestmark = pytest.mark.disagg\n"
        "def test_fine():\n    assert True\n")
    r = _run(["--paths", str(good), "--skip-tests", "--disagg",
              "--disagg-args",
              f"{ok} -q -m disagg -p no:cacheprovider"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert _summary(r)["disagg_ok"]


def test_disagg_summary_keys_present_when_not_run(tmp_path):
    f = tmp_path / "good.py"
    f.write_text(GOOD_SRC)
    r = _run(["--paths", str(f), "--skip-tests"])
    s = _summary(r)
    assert s["disagg_run"] is False and s["disagg_ok"] is True


def test_disagg_double_run_guard_narrows_tier1():
    """With --disagg, tier-1 excludes the disagg marker (the stage owns
    -m disagg, including its slow cases) and the stage runs
    the full DISAGG_PYTEST_ARGS selection."""
    mod = _gate_module()
    captured = {}

    def fake_capturing(args):
        captured.setdefault("args", []).append(args)
        return 1, mod.load_known_failures()

    mod.run_pytest = lambda args: (
        captured.setdefault("args", []).append(args) or 0)
    mod.run_pytest_capturing_failures = fake_capturing
    mod.run_tracelint = lambda *a, **k: ({"errors": 0, "warnings": 0,
                                          "findings": []}, 0)
    mod.audit_suppressions = lambda *a, **k: ([], [])
    rc = mod.main(["--disagg"])
    assert rc == 0
    tier1 = captured["args"][0]
    assert "not disagg" in tier1 and "not slow" in tier1
    assert captured["args"][1] == mod.DISAGG_PYTEST_ARGS
    assert "-m disagg" in mod.DISAGG_PYTEST_ARGS


def test_serialize_subsystem_is_suppression_free():
    """The artifact-store subsystem is a clean zone (DEFAULT_CLEAN_PATHS):
    no inline tracelint suppressions under paddle_tpu/serialize."""
    r = _run(["--paths", "paddle_tpu/serialize", "--skip-tests"])
    assert r.returncode == 0, r.stdout + r.stderr
    s = _summary(r)
    assert s["suppression_violations"] == 0 and s["lint_errors"] == 0


def test_serialize_is_a_default_clean_path():
    mod = _gate_module()
    assert "paddle_tpu/serialize" in mod.DEFAULT_CLEAN_PATHS


def test_diff_known_failures_logic():
    mod = _gate_module()
    known = ["tests/test_a.py::test_one", "tests/test_b.py::test_two"]
    # exact match both ways = clean
    assert mod.diff_known_failures(list(known), known) == ([], [])
    # a new failure is flagged even though the total count matches
    new, fixed = mod.diff_known_failures(
        ["tests/test_a.py::test_one", "tests/test_c.py::test_new"], known)
    assert new == ["tests/test_c.py::test_new"]
    assert fixed == ["tests/test_b.py::test_two"]
    # everything passing flags every stale known entry
    new, fixed = mod.diff_known_failures([], known)
    assert new == [] and fixed == known
    # a flaky test is neither new when it fails nor stale when it passes
    flaky = ["tests/test_f.py::test_sometimes"]
    assert mod.diff_known_failures(known + flaky, known, flaky) == ([], [])
    assert mod.diff_known_failures(list(known), known, flaky) == ([], [])


def test_run_pytest_capturing_failures_parses_nodeids(tmp_path):
    mod = _gate_module()
    f = tmp_path / "test_mixed.py"
    # the failing test logs at ERROR level: pytest echoes a column-0
    # "ERROR    root:test_mixed.py:N boom" captured-log line that must
    # NOT be parsed as a nodeid (only the short-summary section counts)
    f.write_text("import logging\n"
                 "def test_ok():\n    assert True\n"
                 "def test_bad():\n"
                 "    logging.getLogger().error('boom')\n"
                 "    assert False\n")
    rc, failed = mod.run_pytest_capturing_failures(
        f"{f} -q -p no:cacheprovider")
    assert rc == 1
    # nodeids print rootdir-relative (tier-1's own tests come out as
    # the canonical tests/... form KNOWN_FAILURES.json records)
    assert len(failed) == 1
    assert failed[0].endswith("test_mixed.py::test_bad")
    rc, failed = mod.run_pytest_capturing_failures(
        f"{f} -q -p no:cacheprovider -k test_ok")
    assert rc == 0 and failed == []


def test_nodeid_of_summary_line_handles_param_ids_with_separator():
    mod = _gate_module()
    fn = mod._nodeid_of_summary_line
    assert fn("tests/t.py::test_x - AssertionError: boom") == \
        "tests/t.py::test_x"
    # a ' - ' INSIDE parametrize brackets belongs to the nodeid
    assert fn("tests/t.py::test_x[a - b] - AssertionError") == \
        "tests/t.py::test_x[a - b]"
    assert fn("tests/t.py::test_x[a - b]") == "tests/t.py::test_x[a - b]"
    # collection-error lines have a bare path
    assert fn("tests/t.py - ImportError: nope") == "tests/t.py"


def test_known_failures_file_is_well_formed():
    """The committed KNOWN_FAILURES.json parses, is sorted, and only
    names tests in files that exist (a deleted test must leave the
    list)."""
    mod = _gate_module()
    known = mod.load_known_failures()
    assert known is not None and len(known) >= 1
    assert known == sorted(known)
    flaky = mod.load_known_failures(key="tier1_flaky")
    assert flaky is not None and not set(flaky) & set(known)
    for nodeid in known + flaky:
        path = nodeid.split("::", 1)[0]
        assert os.path.exists(os.path.join(REPO, path)), nodeid


def test_known_failures_diff_gates_main():
    """End-to-end through main()'s glue (stubbed runners): a new
    failure fails the gate, a stale known entry fails the gate, the
    exact committed set passes."""
    mod = _gate_module()
    mod.run_tracelint = lambda *a, **k: ({"errors": 0, "warnings": 0,
                                          "findings": []}, 0)
    mod.audit_suppressions = lambda *a, **k: ([], [])
    known = mod.load_known_failures()

    def with_failures(failures, rc=1):
        mod.run_pytest_capturing_failures = lambda args: (rc, failures)
        return mod.main([])

    assert with_failures(list(known)) == 0  # same set as committed
    flaky = mod.load_known_failures(key="tier1_flaky")
    assert with_failures(list(known) + flaky) == 0  # flaky may fail
    assert with_failures(list(known) + ["tests/test_x.py::test_new"]) == 1
    assert with_failures(list(known)[1:]) == 1  # a stale known entry
    assert with_failures([], rc=0) == 1  # all fixed but still listed
