"""Test configuration: force an 8-device virtual CPU platform.

Multi-device tests exercise mesh sharding, ppermute pipelines, and collective
correctness without a real pod (SURVEY §4's test strategy): XLA's host
platform is split into 8 virtual devices.

The tests run on the CPU wherever they are started: ``jax_platforms`` is set
through ``jax.config.update`` (which also holds when something imported jax
before this file and the ``JAX_PLATFORMS`` variable was read too early), and
``XLA_FLAGS`` is still honored because the CPU client is created lazily at
first use. On a machine with a chip this keeps the suite off it — a chip
belongs to one process at a time. The persistent compilation cache is off for
the whole session: ``cli.main`` names a cache directory when a test calls it
in-process, and nothing the tests compile should be written there.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
jax.config.update("jax_enable_compilation_cache", False)


def pytest_sessionstart(session):
    assert jax.default_backend() == "cpu", (
        "tests must run on the virtual CPU platform, not on an accelerator"
    )
    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Cap per-process compiler/executable state growth: with r4's test
    count (~250), the long single-process suite accumulated enough XLA:CPU
    state that the compiler segfaulted (CHECK-less, in
    backend_compile_and_load) near the end of the run — reproducibly at
    ~87%, never in isolation or in fresh tail runs. Dropping compiled
    executables between modules keeps the process under the threshold;
    shared module fixtures (param arrays) are unaffected, and each module
    recompiles only its own small graphs."""
    yield
    jax.clear_caches()
