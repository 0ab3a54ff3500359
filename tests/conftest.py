"""Test configuration: force an 8-device virtual CPU mesh BEFORE any jax
backend initialisation (SURVEY §4: tests run CPU-backed; multi-chip tests
use the forced host-platform device count).

An outer ``JAX_PLATFORMS`` (or a site-wide platform default) is
overridden back to cpu here — conftest imports before any test module
touches jax, and no backend is initialised yet.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Opt-in runtime lock-order sanitizer (PR 8): with PADDLE_TPU_LOCKTRACE=1
# every threading.Lock/RLock the suite creates from here on records its
# per-thread acquisition order, and an A->B / B->A inversion is recorded
# as a violation (tests/test_locktrace.py asserts cleanliness around the
# engine + chaos scenarios; tools/ci_gate.py --concurrency runs that
# file with the knob set). The module is loaded STANDALONE (stdlib-only
# file, registered under its canonical name so the later package import
# binds this same instance) — importing it through paddle_tpu.analysis
# would execute the whole paddle_tpu __init__ first and create the
# import-time subsystem locks (the global obs Registry, tracing,
# goodput, ledger) with the stock factory, untraced.
if os.environ.get("PADDLE_TPU_LOCKTRACE", "0") not in ("0", "", "false"):
    import importlib.util
    import sys as _sys

    _lt_name = "paddle_tpu.analysis.locktrace"
    if _lt_name in _sys.modules:
        _locktrace = _sys.modules[_lt_name]
    else:
        _lt_spec = importlib.util.spec_from_file_location(
            _lt_name,
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
                "paddle_tpu", "analysis", "locktrace.py"))
        _locktrace = importlib.util.module_from_spec(_lt_spec)
        _sys.modules[_lt_name] = _locktrace
        _lt_spec.loader.exec_module(_locktrace)
    _locktrace.enable()

# Opt-in runtime resource-leak sanitizer (TPU5xx counterpart of
# locktrace): with PADDLE_TPU_RESTRACE=1 the declared acquire/release
# sites of every traced resource kind (KV slots, pooled router
# sockets, compile lockfiles, scratch dirs, signal handlers) record
# per-kind live-handle censuses, and the session-scoped guard below
# fails the run if the suite ends with a live handle. Unlike
# locktrace, restrace patches named definition sites (not a lock
# factory), so the ordinary package import is safe here.
_RESTRACE_ARMED = False
if os.environ.get("PADDLE_TPU_RESTRACE", "0") not in ("0", "", "false"):
    from paddle_tpu.analysis import restrace as _restrace

    _RESTRACE_ARMED = _restrace.maybe_enable_from_env()


# Persistent XLA compile cache (the rule chip_smoke.py and
# benchmark/run.py share — paddle_tpu.utils.compile_cache): most of the
# tier-1 wall clock is repeated big compiles, and a warm cache cuts the
# suite roughly in half. Placed after the locktrace block, which must
# run before the package import this needs.
from paddle_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()


@pytest.fixture(autouse=True, scope="session")
def _restrace_census_guard():
    """End-of-suite leak check: when restrace is armed, a nonzero
    live-handle census (or any recorded violation) fails the session
    — this is how ci_gate --resources runs the decode/fleet/artifact
    suites."""
    yield
    if _RESTRACE_ARMED:
        from paddle_tpu.analysis import restrace

        if restrace.enabled():
            restrace.assert_clean()


@pytest.fixture
def residual_counts():
    """Reader of ``paddle_tpu_recompute_residual_total`` (ops/residuals.py):
    ``{(name, event): count}`` over every name and event; with an earlier
    reading ``since``, what was counted after it."""
    from paddle_tpu.ops import residuals

    def counts(since=None):
        return {(name, event): residuals._RESIDUAL_TOTAL.value(
                    name=name, event=event)
                - (since[name, event] if since else 0)
                for name in residuals.NAMES
                for event in ("offered", "kept")}
    return counts


@pytest.fixture(autouse=True)
def _seed():
    import numpy as np

    import paddle_tpu as paddle

    np.random.seed(0)
    paddle.seed(0)
    yield
