"""Where this program keeps JAX's persistent compilation cache.

A cold process compiles every executable of the serving path (73 s of a
108 s chip smoke at 7B widths — my chip run, PR 21); the cache's directory
is part of its key, so it only ever hits when every run names the same
path. One rule, called by every entry point (``cli.main``,
``benchmark/server.py``, ``chip_smoke.py``) before the first trace:

* ``JAX_COMPILATION_CACHE_DIR`` set — whoever runs the program placed the
  cache; JAX reads the variable itself and no directory is set in code.
* unset — ``<checkout>/.jax_cache``: a fixed path beside the package
  (git-ignored), never built from a pid, a clock or a temporary name.

Either way every executable is kept: JAX's default skips compiles under a
second, which on the chip left a warm second process still compiling 113
of its 128 executables (my chip run, PR 21).

The same call starts the process's count of the programs it loads
(``utils/tracing.py:PROGRAM_LOADS``, behind ``engine_program_loads`` and
``engine_program_load_seconds``), so an entry point counts from before its
first trace, the weights' and the set-up's programs included. The seconds
are kept by stage (``engine_program_load_{trace,lower,compile,cache_read}_
seconds``: a load that this cache answered is ``cache_read``, one the
backend compiled is ``compile``, and tracing and lowering are paid either
way, since the cache's key is made from the lowered module) and by program
(``PROGRAM_LOADS.programs``, the ``"programs"`` of ``/debug/ticks``).
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache", "cache_entries"]

#: ``<checkout>/.jax_cache`` — the checkout is the directory that holds the
#: package directory.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Apply the rule above; returns the directory the cache lives in."""
    import jax

    from .tracing import PROGRAM_LOADS

    PROGRAM_LOADS.install()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


def cache_entries(path: str) -> int:
    """Executables stored under ``path`` (0 for a directory not made yet)."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
