"""Regression tests for the persistent-compile-cache corruption guard
(paddle_tpu.utils.compile_cache, called by tests/conftest.py): a truncated or garbage ``.jax_compile_cache``
entry — the realistic leftovers of a run killed mid-write — must never
fail tier-1. jax itself degrades a corrupt entry to a warning +
recompile at read time; the conftest guard additionally scrubs
zero-byte entries up front. The cache is placed from outside through
``JAX_COMPILATION_CACHE_DIR`` — no code sets a directory when it is set. Both properties are pinned here with real
subprocesses so a jax upgrade that turns corrupt-cache reads into hard
errors is caught by the suite, not by a mysteriously red tier-1.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE_SNIPPET = """\
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, numpy as np
jax.config.update("jax_platforms", "cpu")
assert jax.config.jax_compilation_cache_dir == {cache!r}
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
out = jax.jit(lambda x: x @ x + 1.0)(np.ones((32, 32), np.float32))
assert float(np.asarray(out)[0, 0]) == 33.0
print("COMPILED_OK")
"""


def _run_compile(cache_dir):
    return subprocess.run(
        [sys.executable, "-c",
         _COMPILE_SNIPPET.format(cache=str(cache_dir))],
        env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache_dir)),
        capture_output=True, text=True, timeout=180)


def test_corrupt_cache_entry_degrades_to_recompile(tmp_path):
    """Plant REAL cache entries, then corrupt them in place (garbage
    bytes + truncation): a fresh process hitting the same cache keys
    must recompile and produce correct output, not crash."""
    cache = tmp_path / "cache"
    cache.mkdir()
    r = _run_compile(cache)
    assert "COMPILED_OK" in r.stdout, r.stderr
    entries = [f for f in os.listdir(cache)
               if os.path.isfile(os.path.join(cache, f))]
    assert entries, "expected the compile to populate the cache"
    # corrupt every entry: garbage for one half, zero-byte for the rest
    for i, fn in enumerate(sorted(entries)):
        full = os.path.join(cache, fn)
        with open(full, "wb") as f:
            if i % 2 == 0:
                f.write(b"\x00garbage not a cache entry\xff" * 3)
    r2 = _run_compile(cache)
    assert "COMPILED_OK" in r2.stdout, r2.stderr


def test_tier1_collects_and_passes_with_poisoned_cache(tmp_path):
    """The satellite contract: a poisoned compile-cache dir pointed at
    by JAX_COMPILATION_CACHE_DIR must not fail the suite — it
    still collects, runs, and passes (a fast representative slice)."""
    cache = tmp_path / "cache"
    cache.mkdir()
    # a garbage entry named like a real jax cache key, and a truncated
    # (zero-byte) one the conftest guard should scrub
    (cache / ("jit__lambda_-" + "ab" * 32 + "-cache")).write_bytes(
        b"definitely not zstandard")
    zero = cache / ("jit_f-" + "cd" * 32 + "-cache")
    zero.write_bytes(b"")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    r = subprocess.run(
        [sys.executable, "-m", "pytest",
         "tests/test_artifact_store.py", "-q", "-p", "no:cacheprovider",
         "-x", "-k", "TestKey or TestPutGet"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    # the conftest guard scrubbed the truncated entry
    assert not zero.exists()
    # the garbage (non-empty) entry is left for jax to degrade on read
    assert (cache / ("jit__lambda_-" + "ab" * 32 + "-cache")).exists()
