"""Native C++ safetensors reader vs the Rust/Python wheel (parity + errors)."""

import os
import struct

import numpy as np
import pytest

from distributed_llm_inference_tpu.utils import streader

pytestmark = pytest.mark.skipif(
    not streader.native_available(), reason="native streader did not build"
)


def _write_st(path, tensors):
    from safetensors.numpy import save_file

    save_file(tensors, str(path))


@pytest.fixture()
def sample(tmp_path):
    r = np.random.RandomState(0)
    tensors = {
        "a": r.randn(16, 32).astype(np.float32),
        "b": r.randn(8).astype(np.float16),
        "c": r.randint(-128, 127, size=(4, 4, 4)).astype(np.int8),
        "d": r.randint(0, 2**31, size=(5,)).astype(np.int64),
    }
    path = tmp_path / "sample.safetensors"
    _write_st(path, tensors)
    return str(path), tensors


def test_read_parity(sample):
    path, tensors = sample
    with streader.NativeSafetensors(path) as f:
        assert set(f.keys()) == set(tensors)
        for name, ref in tensors.items():
            got = f.read(name)
            assert got.dtype == ref.dtype and got.shape == ref.shape
            np.testing.assert_array_equal(got, ref)


def test_read_many_parity_and_subset(sample):
    path, tensors = sample
    with streader.NativeSafetensors(path, threads=4) as f:
        out = f.read_many(["a", "c"])
    assert set(out) == {"a", "c"}
    np.testing.assert_array_equal(out["a"], tensors["a"])
    np.testing.assert_array_equal(out["c"], tensors["c"])


def test_bf16_roundtrip(tmp_path):
    import jax.numpy as jnp
    from safetensors.flax import save_file

    arr = jnp.arange(64, dtype=jnp.bfloat16).reshape(8, 8) / 7
    path = tmp_path / "bf.safetensors"
    save_file({"x": arr}, str(path))
    with streader.NativeSafetensors(str(path)) as f:
        got = f.read("x")
    np.testing.assert_array_equal(got, np.asarray(arr))


def test_missing_file_raises(tmp_path):
    with pytest.raises(OSError):
        streader.NativeSafetensors(str(tmp_path / "nope.safetensors"))


def test_truncated_file_rejected(tmp_path, sample):
    path, _ = sample
    data = open(path, "rb").read()
    bad = tmp_path / "trunc.safetensors"
    bad.write_bytes(data[: len(data) // 2])
    with pytest.raises((OSError, ValueError)):
        with streader.NativeSafetensors(str(bad)) as f:
            for k in f.keys():
                f.read(k)


def test_header_len_overflow_rejected(tmp_path):
    bad = tmp_path / "bad.safetensors"
    bad.write_bytes(struct.pack("<Q", 1 << 40) + b"{}")
    with pytest.raises(OSError):
        streader.NativeSafetensors(str(bad))


def test_checkpoint_loader_uses_native(tmp_path, monkeypatch):
    """block_state_dict must produce identical tensors whether the native
    reader or the wheel serves the reads."""
    from distributed_llm_inference_tpu.utils import checkpoint
    from tests.test_checkpoint import CFG, _hf_state, _write_sharded

    state = _hf_state(CFG)
    _write_sharded(str(tmp_path), state)

    native = checkpoint.block_state_dict(str(tmp_path), [0, 1])
    monkeypatch.setattr(streader, "native_available", lambda: False)
    wheel = checkpoint.block_state_dict(str(tmp_path), [0, 1])
    assert set(native) == set(wheel)
    for k in native:
        np.testing.assert_array_equal(native[k], wheel[k])


def test_native_library_is_rebuilt_when_its_source_changes(tmp_path):
    """Staleness is the source's hash, not file times: the libraries are
    git-ignored yet travel with a copied tree, and a copy need not keep
    mtimes (``utils/native_build.py``)."""
    import shutil

    from distributed_llm_inference_tpu.utils.native_build import build_shared

    src, so = str(tmp_path / "streader.cc"), str(tmp_path / "_streader.so")
    shutil.copy(streader._SRC, src)
    build_shared(src, so)
    built = os.stat(so).st_mtime_ns
    os.utime(src)  # a newer source with the same bytes: still current
    build_shared(src, so)
    assert os.stat(so).st_mtime_ns == built
    with open(src, "a") as f:
        f.write("\n// changed\n")
    os.utime(so, (2e9, 2e9))  # a library "newer" than its changed source
    build_shared(src, so)
    assert os.stat(so).st_mtime_ns != int(2e9 * 1e9)
    assert not [n for n in os.listdir(tmp_path) if ".tmp." in n]
