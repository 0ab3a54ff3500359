"""Where the persistent XLA compilation cache lives — the one rule
shared by chip_smoke.py, benchmark/run.py and the test harness.

The directory is part of the cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (jax
reads the variable itself; no directory is set in code then), else the
fixed ``<checkout>/.jax_compile_cache``. Never a temp name, a pid or a
timestamp.
"""
import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir():
    """The directory the rule above names (also what a parent exports
    as ``JAX_COMPILATION_CACHE_DIR`` to a child that configures
    nothing itself)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_compile_cache"))


def configure_compile_cache():
    """Point jax's persistent compilation cache at ``compile_cache_dir()``
    and set the write thresholds (compiles >= 1 s, any size). Call
    before the first compile. Returns the directory."""
    import jax

    cache_dir = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    # a run killed mid-write can leave zero-byte entries behind; jax
    # degrades a garbage entry to a warning + recompile at read time
    # (tests/test_compile_cache_guard.py), zero-byte ones are the cheap
    # torn write to detect, so scrub them up front
    for name in os.listdir(cache_dir):
        full = os.path.join(cache_dir, name)
        try:
            if os.path.isfile(full) and os.path.getsize(full) == 0:
                os.unlink(full)
        except OSError:
            pass  # raced with another process's scrub or write
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
