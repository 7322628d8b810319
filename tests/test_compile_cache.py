"""Where the program keeps JAX's persistent compilation cache
(``utils/compile_cache.py``): placed from outside through
``JAX_COMPILATION_CACHE_DIR``, else one fixed directory in the checkout."""

import os
import re

import jax
import pytest

from distributed_llm_inference_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_OPTIONS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture
def restore_config():
    was = {name: getattr(jax.config, name) for name in _OPTIONS}
    yield was
    for name, value in was.items():
        jax.config.update(name, value)


def test_env_placed_cache_sets_no_directory_in_code(monkeypatch, restore_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    # JAX read the variable itself at import (or not, if set since): the
    # helper leaves the option exactly as it found it.
    assert (
        jax.config.jax_compilation_cache_dir
        == restore_config["jax_compilation_cache_dir"]
    )


def test_unset_env_uses_the_checkout(monkeypatch, restore_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


@pytest.mark.parametrize("placed", [None, "/somewhere/else"])
def test_every_executable_is_kept(monkeypatch, restore_config, placed):
    """JAX's default skips compiles under a second, so a warm second process
    would still compile the small ones."""
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    compile_cache.enable_compile_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_the_call_starts_the_count_of_the_programs_the_process_loads(
    monkeypatch, restore_config
):
    """An entry point counts from before its first trace: the one listener
    of ``utils/tracing.py:PROGRAM_LOADS`` is there after the call, once
    however often it is called, and hears the next compile."""
    from distributed_llm_inference_tpu.utils.tracing import PROGRAM_LOADS

    registered = []
    monkeypatch.setattr(
        jax.monitoring, "register_event_duration_secs_listener",
        registered.append,
    )
    monkeypatch.setattr(PROGRAM_LOADS, "_installed", False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    compile_cache.enable_compile_cache()
    compile_cache.enable_compile_cache()
    assert registered == [PROGRAM_LOADS._duration]
    before = PROGRAM_LOADS.loads
    registered[0]("/jax/core/compile/backend_compile_duration", 0.5,
                  fun_name="jit_f")
    assert PROGRAM_LOADS.loads == before + 1


def test_a_test_session_keeps_its_cache_to_itself(restore_config):
    """``conftest``: the cache is on, in a directory outside the checkout
    that this process made for itself, and ``enable_compile_cache()`` called
    in-process (as ``cli.main`` calls it) leaves it there: what a test
    compiles lands in that directory and not in ``<checkout>/.jax_cache``."""
    import jax.numpy as jnp

    path = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert jax.config.jax_enable_compilation_cache
    assert jax.config.jax_compilation_cache_dir == path
    assert os.path.isdir(path)
    assert os.path.relpath(path, REPO).startswith("..")
    assert f"_{os.getpid()}_" in os.path.basename(path)
    assert compile_cache.enable_compile_cache() == path
    assert jax.config.jax_compilation_cache_dir == path
    here = compile_cache.cache_entries(path)
    checkout = compile_cache.cache_entries(compile_cache.CHECKOUT_CACHE_DIR)
    jax.jit(lambda x: x * 3 + os.getpid())(jnp.arange(7)).block_until_ready()
    assert compile_cache.cache_entries(path) > here
    assert compile_cache.cache_entries(compile_cache.CHECKOUT_CACHE_DIR) == checkout


def test_checkout_path_is_fixed():
    """No pid, clock or temporary component: the directory is part of the
    cache key, so a path that moves never hits."""
    path = compile_cache.CHECKOUT_CACHE_DIR
    rel = os.path.relpath(path, REPO)
    assert rel == ".jax_cache"
    assert str(os.getpid()) not in rel and not re.search(r"\d", rel)
    assert "tmp" not in rel.lower() and "temp" not in rel.lower()
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_entries_counts_executables(tmp_path):
    assert compile_cache.cache_entries(str(tmp_path / "missing")) == 0
    for name in ("a-cache", "a-atime", "b-cache"):
        (tmp_path / name).write_bytes(b"x")
    assert compile_cache.cache_entries(str(tmp_path)) == 2
