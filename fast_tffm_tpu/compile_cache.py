"""The one persistent-XLA-compile-cache policy (README "Compile cache").

Every entry point that compiles — ``run_tffm.py``, the fleet's replica
child, ``benchmarks/``, ``tools/offload_smoke.py`` — calls
``enable_compilation_cache`` before its first jit. First compile of the
train/score programs costs tens of seconds on a TPU; without the cache
every process pays it again (predict right after train, a restarted
serving replica re-warming its shape ladder).

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads the variable itself and
  this code sets NO directory.
- Unset: ``<checkout>/.jax_cache``, derived from this package's own
  location. The path is part of what makes a cache findable again, so
  it is never a home directory, a temp name, a pid or a time.
- Either way every program is cached, sub-second compiles included: the
  CLI's cost is many medium programs, not one giant one.
- An entry is keyed by its operations' metadata too (jax leaves op
  paths and source lines out of the key by default): an executable
  carries the ``jax.named_scope`` paths it was compiled with into every
  profiler trace, and a directory that outlives a change of the source
  would otherwise hand back a program whose trace names the old code
  (benchmarks/readers/scope_device_ms.py reads those paths).

An unusable directory is an error, not a silently uncached run.

Importing this module imports nothing heavy (jax only inside
``enable_compilation_cache``): chip_smoke.py's jax-free parent reads
the same directory rule from here.
"""

from __future__ import annotations

import contextlib
import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compilation_cache(logger=None) -> str:
    """Turn the persistent cache on; returns the directory in use and,
    given a ``logger``, says where it is and whether this process
    starts cold. Only updates jax config — no backend is initialised
    here, so a supervisor that never computes (serve/fleet.py) stays
    off the chip."""
    import jax
    path, from_env = cache_dir()
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    if "://" not in path:  # gs:// and friends are jax's to open
        os.makedirs(path, exist_ok=True)
        if not os.access(path, os.W_OK | os.X_OK):
            raise PermissionError(
                f"compile cache directory {path} is not writable")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if logger is not None:
        n = cache_entries(path)
        logger.info("compile cache: %s (%s), %d programs at start (%s)",
                    path, CACHE_DIR_ENV if from_env else "checkout default",
                    n, "warm" if n else "cold")
    return path


def cache_dir(environ=None) -> "tuple[str, bool]":
    """(directory, whether it came from the environment)."""
    path = (os.environ if environ is None else environ).get(
        CACHE_DIR_ENV, "")
    return (path, True) if path else (DEFAULT_CACHE_DIR, False)


def cache_entries(path: str) -> int:
    """Compiled programs already in a local cache directory (0 = this
    process starts cold). jax keeps one ``*-cache`` file per program
    beside its ``*-atime`` bookkeeping."""
    if "://" in path or not os.path.isdir(path):
        return 0
    return sum(1 for name in os.listdir(path)
               if not name.endswith("-atime"))


@contextlib.contextmanager
def uncached():
    """Compile in this process: the body's programs are neither read
    from the persistent cache nor written to it (``models/fm.py``,
    ``_Relabel``, says who needs that and why). The switch is
    process-wide for the body's duration, jax has no narrower one: a
    compile on another thread meanwhile misses the cache and nothing
    else."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
