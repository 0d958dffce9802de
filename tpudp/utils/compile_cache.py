"""Persistent XLA compilation cache.

The dominant cost of a cold start is the first compile of each step
program (tens of seconds for the fused train step, more for the serve
engine's program set).  JAX's persistent compilation cache removes that
cost for every process after the first successful one: the serialized
executable is stored on disk keyed by program hash, and later processes
deserialize it instead of recompiling.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` if the environment
sets it — JAX reads its own variable, and this module then never touches
``jax_compilation_cache_dir`` — otherwise the fixed in-checkout
:data:`DEFAULT_DIR`.  That path is a constant, never a temp name: a
cache directory that moves between runs never hits.

Accelerator backends only: on XLA:CPU the AOT loader re-checks the host
feature string on every cache hit and prints multi-line "machine type
mismatch ... SIGILL" errors (the compile-side string carries XLA
preference pseudo-features like ``+prefer-no-gather`` that the runtime
probe never reports), drowning trainer output for a cache the CPU smoke
path doesn't benefit from anyway — so the helper checks the RESOLVED
backend itself and no-ops on CPU.  Call it after any
``jax.config.update("jax_platforms", ...)`` override.

The reference has no analogue (eager torch compiles nothing); this is
TPU-runtime machinery.
"""

import os


# Inside the checkout and inside bench_results/ (gitignored by the
# `bench_results/*` rule).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "bench_results", "xla_cache")


def enable_persistent_cache(*, force: bool = False) -> str | None:
    """Turn on JAX's on-disk executable cache; returns the dir, or None
    on the CPU backend (where it stays off).

    Must run before the first compile (config flags are read per-compile).
    ``force=True`` skips the CPU-backend check (tests).  Both size/time
    thresholds are zeroed so every program is cached.  The directory is
    ``JAX_COMPILATION_CACHE_DIR`` when set (left entirely to JAX — no
    ``jax_compilation_cache_dir`` update happens here), else
    :data:`DEFAULT_DIR`.  A failure (unwritable directory, renamed config
    flag) propagates: a caller told "cached" must be cached.
    """
    import jax

    if not force and jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
