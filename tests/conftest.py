"""Test configuration: force an 8-device virtual CPU platform.

Multi-device tests exercise mesh sharding, ppermute pipelines, and collective
correctness without a real pod (SURVEY §4's test strategy): XLA's host
platform is split into 8 virtual devices.

The tests run on the CPU wherever they are started: ``jax_platforms`` is set
through ``jax.config.update`` (which also holds when something imported jax
before this file and the ``JAX_PLATFORMS`` variable was read too early), and
``XLA_FLAGS`` is still honored because the CPU client is created lazily at
first use. On a machine with a chip this keeps the suite off it — a chip
belongs to one process at a time.

The suite checks what the programs compute on models of two or three layers;
how fast XLA:CPU runs them is read by nobody, so ``XLA_FLAGS`` also gains
``--xla_backend_optimization_level=0`` and
``--xla_llvm_disable_expensive_passes=true``. Each of the three flags is
appended unless the caller's ``XLA_FLAGS`` already names it.

The persistent compilation cache is ON for the session, in a directory that
is this process's own (``tempfile.mkdtemp``; every xdist worker imports this
file and so makes one, shared with nobody) and is removed when the session
ends. ``InferenceEngine`` builds its programs as ``jax.jit`` of closures, so
two engines of one configuration share no executable in memory: the second
and later compile of one module inside one run is what the directory
answers, and it starts empty in every process. ``JAX_COMPILATION_CACHE_DIR``
names it, set before ``import jax``: JAX reads the variable itself, and
``utils/compile_cache.py`` sets ``<checkout>/.jax_cache`` in code only where
the variable is unset, so ``cli.main`` called in-process (and a child process
that inherits the environment) leaves the cache here and writes nothing into
the checkout. Every executable is kept, as ``enable_compile_cache`` keeps
them. A test that needs a cold compile says so itself, around itself, with
the ``compiles_cold`` fixture below.
"""

import collections
import contextlib
import os
import shutil
import tempfile

_XLA_FLAGS = (
    "--xla_force_host_platform_device_count=8",
    "--xla_backend_optimization_level=0",
    "--xla_llvm_disable_expensive_passes=true",
)
flags = os.environ.get("XLA_FLAGS", "")
for flag in _XLA_FLAGS:
    if flag.lstrip("-").split("=")[0] not in flags:
        flags = f"{flags} {flag}".strip()
os.environ["XLA_FLAGS"] = flags

_CACHE_DIR = tempfile.mkdtemp(prefix=f"jax_test_cache_{os.getpid()}_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _CACHE_DIR

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
jax.config.update("jax_enable_compilation_cache", True)
jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def pytest_sessionstart(session):
    assert jax.default_backend() == "cpu", (
        "tests must run on the virtual CPU platform, not on an accelerator"
    )
    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"


def pytest_sessionfinish(session, exitstatus):
    shutil.rmtree(_CACHE_DIR, ignore_errors=True)


def pytest_configure(config):
    # under ``--dist loadfile`` xdist hands the files out by their number of
    # cases, most first; the order below is kept instead
    config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    """The files under ``tests/bench/`` first, then the others, each group by
    its number of cases, most first (a file's own order is kept). A file is
    one worker's from its first case to its last, and the longest by far run
    whole programs as child processes in a handful of cases
    (``tests/bench/test_benchmark_rehearsal.py``: 13 cases, 360-400 s;
    ``test_benchmark_extend.py``: 4 cases, 180 s): handed out by their number
    of cases they start when the run is two thirds through and end it, alone,
    150 s after the other workers ran out of work (PR 63)."""
    cases = collections.Counter(item.path for item in items)
    items.sort(key=lambda item: (
        "bench" not in item.path.parts, -cases[item.path], item.path
    ))


import pytest  # noqa: E402


@contextlib.contextmanager
def _compiles_cold():
    from jax.experimental.compilation_cache import compilation_cache

    # JAX asks once a process whether the cache is in use and remembers the
    # answer: the option alone changes nothing after the first compile.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def compiles_cold():
    """A context manager inside which the persistent compilation cache
    neither answers nor keeps a compile (``with compiles_cold(): ...``), for
    the test that reads a cold compile's own events or compiles what a later
    read could not load. Outside it the session's cache is back, at the same
    directory."""
    return _compiles_cold


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Cap per-process compiler/executable state growth: with r4's test
    count (~250), the long single-process suite accumulated enough XLA:CPU
    state that the compiler segfaulted (CHECK-less, in
    backend_compile_and_load) near the end of the run, reproducibly at
    ~87%, never in isolation or in fresh tail runs. Dropping compiled
    executables between modules keeps the process under the threshold;
    shared module fixtures (param arrays) are unaffected, and what a module
    compiles again the session's cache answers from disk.

    Weighed at PR 63 under six xdist workers, and kept: the one whole run
    without it lost a worker to a segmentation fault, two thirds through,
    inside ``compilation_cache.put_executable_and_time`` (jaxlib 0.9.0
    serializing an executable for this process's own directory, in
    ``tests/test_keye.py``); no whole run with it has lost one."""
    yield
    jax.clear_caches()
