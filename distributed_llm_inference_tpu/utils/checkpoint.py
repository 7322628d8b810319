"""Per-block checkpoint streaming: load ONLY the layers this node serves.

TPU-native rebuild of the reference's weight loader
(``/root/reference/distributed_llm_inference/utils/model.py``):

* index discovery over the same four layouts — safetensors index, single
  ``model.safetensors``, torch ``.bin`` index, single ``.bin``
  (``utils/model.py:13,27-34``);
* ``weight_map`` prefix filtering so a node serving layers ``[i..j]`` opens
  only those layers' shard files (``utils/model.py:40-44``);
* tensors come out as numpy, get cast to ``bfloat16`` (the reference casts
  non-integer tensors to fp16 for CUDA, ``utils/model.py:66-68``; bf16 is the
  TPU-native choice) and converted to this package's stacked-layer layout.
  The layer stacks are returned as HOST arrays and placed by whoever serves
  them — quantized leaf by leaf, or ``device_put`` with their
  ``NamedSharding`` — so the unquantized whole is never resident on one
  device; placement *is* the sharding story, replacing accelerate's
  ``set_module_tensor_to_device`` (``utils/model.py:70``).

Paths are local snapshot directories (an HF hub cache dir works as-is); a
``resolve`` callable parameterizes filename→path lookup so a hub/remote
resolver can be plugged in where the reference used ``cached_file``
(``utils/model.py:29``).
"""

from __future__ import annotations

import json
import os
import warnings
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig
from ..models import llama

__all__ = [
    "find_index",
    "block_state_dict",
    "load_block_params",
    "load_model_params",
    "load_client_params",
    "load_config",
    "save_safetensors",
    "shard_put",
]

INDEX_FILE_PATTERNS = (
    "model.safetensors.index.json",
    "model.safetensors",
    "pytorch_model.bin.index.json",
    "pytorch_model.bin",
)

_NON_LAYER_KEYS = (
    "model.embed_tokens.weight",
    "model.norm.weight",
    "lm_head.weight",
)


def _default_resolve(model_dir: str) -> Callable[[str], Optional[str]]:
    def resolve(name: str) -> Optional[str]:
        path = os.path.join(model_dir, name)
        return path if os.path.exists(path) else None

    return resolve


def find_index(resolve: Callable[[str], Optional[str]]) -> str:
    """First existing checkpoint entry file, in the reference's pattern order
    (``utils/model.py:13,27-34``)."""
    for pattern in INDEX_FILE_PATTERNS:
        path = resolve(pattern)
        if path is not None:
            return path
    raise FileNotFoundError(
        f"no checkpoint index/weights found (tried {INDEX_FILE_PATTERNS})"
    )


def _read_tensors_safetensors(path: str, wanted: Callable[[str], bool]):
    # Native C++ reader first (mmap + multithreaded copies,
    # ``native/streader.cc`` — the data-loader tier the reference delegates
    # to the Rust safetensors extension); pure-Python wheel as fallback.
    from . import streader

    if streader.native_available():
        try:
            with streader.NativeSafetensors(path) as f:
                return f.read_many([k for k in f.keys() if wanted(k)])
        except Exception as e:  # host file I/O only — no device in reach
            warnings.warn(
                f"native read of {path!r} failed ({e!r}); falling back to "
                "the safetensors wheel"
            )
    from safetensors import safe_open

    out: Dict[str, np.ndarray] = {}
    with safe_open(path, framework="numpy") as f:
        for key in f.keys():
            if wanted(key):
                out[key] = f.get_tensor(key)
    return out


def _read_tensors_torch(path: str, wanted: Callable[[str], bool]):
    import torch

    state = torch.load(path, map_location="cpu", weights_only=True)
    return {
        k: v.to(torch.float32).numpy() if v.dtype == torch.bfloat16 else v.numpy()
        for k, v in state.items()
        if wanted(k)
    }


def _read_tensors(path: str, wanted: Callable[[str], bool]):
    if path.endswith(".safetensors"):
        return _read_tensors_safetensors(path, wanted)
    return _read_tensors_torch(path, wanted)


def block_state_dict(
    model_dir: str,
    layer_ids: Optional[Sequence[int]] = None,
    include_non_layer: bool = False,
    resolve: Optional[Callable[[str], Optional[str]]] = None,
) -> Dict[str, np.ndarray]:
    """HF-keyed numpy state dict for the given layers, reading only the shard
    files that contain them.

    ``layer_ids=None`` loads every layer. ``include_non_layer`` adds the
    embedding / final-norm / lm_head tensors (the client-side weights a
    mid-pipeline node never needs — the reference's loader is layers-only,
    ``utils/model.py:40``).
    """
    resolve = resolve or _default_resolve(model_dir)
    entry = find_index(resolve)

    prefixes = None
    if layer_ids is not None:
        prefixes = tuple(f"model.layers.{i}." for i in layer_ids)

    def wanted(key: str) -> bool:
        if prefixes is None:
            return include_non_layer or key.startswith("model.layers.")
        if key.startswith(prefixes):
            return True
        return include_non_layer and key in _NON_LAYER_KEYS

    if entry.endswith(".index.json"):
        with open(entry) as f:
            index = json.load(f)
        if "weight_map" not in index:
            raise ValueError(f"{entry} has no weight_map")
        shard_files = sorted({
            shard for key, shard in index["weight_map"].items() if wanted(key)
        })
        state: Dict[str, np.ndarray] = {}
        for shard in shard_files:
            path = resolve(shard)
            if path is None:
                raise FileNotFoundError(f"shard {shard} listed in index not found")
            state.update(_read_tensors(path, wanted))
        return state
    return _read_tensors(entry, wanted)


def load_block_params(
    model_dir: str,
    cfg: ModelConfig,
    layer_ids: Sequence[int],
    dtype=jnp.bfloat16,
    resolve: Optional[Callable[[str], Optional[str]]] = None,
    cache_dir: Optional[str] = None,
) -> Dict[str, Any]:
    """Stacked layer params for the block a node serves — the analog of
    ``load_block`` (``utils/model.py:75-90``), returning ``{"layers": …}``
    ready for :func:`models.llama.block_apply`.

    ``cache_dir`` enables the pre-converted on-disk cache (SURVEY §5.4): the
    first load writes the already-stacked/transposed arrays there; repeat
    bring-up of the same block then skips the HF-layout conversion and the
    unrelated-layer shard reads entirely.
    """
    def build():
        state = block_state_dict(model_dir, layer_ids, resolve=resolve)
        return llama.convert_hf_state_dict(
            cfg, state, layer_ids, dtype, consume=True
        )

    return _cached_load(
        build, model_dir, cache_dir, layer_ids, dtype, resolve, tag="block"
    )


def load_model_params(
    model_dir: str,
    cfg: ModelConfig,
    dtype=jnp.bfloat16,
    resolve: Optional[Callable[[str], Optional[str]]] = None,
    cache_dir: Optional[str] = None,
) -> Dict[str, Any]:
    """Full-model params (embedding + all layers + head) for single-node /
    client use. ``cache_dir``: see :func:`load_block_params`."""
    def build():
        state = block_state_dict(
            model_dir, None, include_non_layer=True, resolve=resolve
        )
        return llama.convert_hf_state_dict(
            cfg, state, None, dtype, consume=True
        )

    return _cached_load(
        build, model_dir, cache_dir, None, dtype, resolve, tag="model"
    )


# ---------------------------------------------------------------------------
# Pre-converted on-disk cache (SURVEY §5.4: "optional on-disk cache of
# pre-sharded arrays" — the reference re-parses HF shards on every bring-up)
# ---------------------------------------------------------------------------


def _flatten_params(params: Mapping[str, Any], prefix="") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in params.items():
        key = f"{prefix}{k}"
        if isinstance(v, Mapping):
            out.update(_flatten_params(v, prefix=f"{key}."))
        else:
            out[key] = v
    return out


def _unflatten_params(flat: Mapping[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for key, v in flat.items():
        node = out
        parts = key.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def _cache_key(
    entry_path: str,
    layer_ids: Optional[Sequence[int]],
    dtype,
    tag: str,
    resolve: Callable[[str], Optional[str]],
) -> str:
    """Content key: identity (path + size + mtime) of the entry file, every
    shard it maps to, and config.json, × layer span × dtype × layout
    version — so replacing any shard (or the model config) invalidates the
    cache even when the index file itself is byte-identical."""
    def ident(path: Optional[str]):
        if path is None or not os.path.exists(path):
            return None
        st = os.stat(path)
        return [os.path.abspath(path), st.st_size, int(st.st_mtime_ns)]

    files = [ident(entry_path)]
    if entry_path.endswith(".index.json"):
        with open(entry_path) as f:
            shards = sorted(set(json.load(f).get("weight_map", {}).values()))
        files += [ident(resolve(s)) for s in shards]
    files.append(ident(resolve("config.json")))
    blob = json.dumps([
        "v1", tag, files,
        list(layer_ids) if layer_ids is not None else None,
        str(jnp.dtype(dtype)),
    ])
    import hashlib

    return hashlib.sha1(blob.encode()).hexdigest()[:20]


def _cached_load(build, model_dir, cache_dir, layer_ids, dtype, resolve, tag):
    if cache_dir is None:
        return build()
    # NOTE: numpy framework (via save_safetensors' forced host-contiguous
    # copies), NOT safetensors.flax — flax's writer serializes TPU-resident
    # buffers with their padded tile layout, silently corrupting
    # non-tile-aligned shapes (observed on v5e). bf16 round-trips as
    # ml_dtypes.bfloat16.
    from safetensors.numpy import load_file

    resolve = resolve or _default_resolve(model_dir)
    entry = find_index(resolve)
    key = _cache_key(entry, layer_ids, dtype, tag, resolve)
    path = os.path.join(cache_dir, f"{tag}-{key}.safetensors")
    if os.path.exists(path):
        try:
            flat = load_file(path)
        except Exception:
            pass  # corrupt/partial cache entry: rebuild below
        else:
            # Layer stacks stay on the host, as from a fresh conversion
            # (llama.convert_hf_state_dict): the consumer places them.
            return _unflatten_params({
                k: v if k.startswith("layers") else jnp.asarray(v)
                for k, v in flat.items()
            })
    params = build()
    os.makedirs(cache_dir, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    save_safetensors(_flatten_params(params), tmp)
    os.replace(tmp, path)  # atomic: concurrent loaders see whole files only
    return params


def load_client_params(
    model_dir: str,
    cfg: ModelConfig,
    dtype=jnp.bfloat16,
    resolve: Optional[Callable[[str], Optional[str]]] = None,
) -> Dict[str, Any]:
    """Embedding + final-norm + lm_head ONLY — what ``DistributedClient``
    runs locally. Skips every decoder layer's shards, so a client fronting a
    70B chain loads megabytes, not the full model."""
    state = block_state_dict(model_dir, [], include_non_layer=True, resolve=resolve)
    return llama.convert_hf_non_layer(cfg, state, dtype)


def save_safetensors(state: Mapping[str, Any], path: str) -> None:
    """Write an HF-keyed state dict as a ``.safetensors`` file (the save path
    the reference lacks — its loader is read-only, ``utils/model.py``).

    Every tensor is forced C-contiguous first: safetensors' numpy writer
    serializes the array's underlying buffer without consulting strides, so a
    transposed view — or an array fetched from a TPU device, which may come
    back with a non-row-major layout — would be silently written with its
    bytes permuted.
    """
    from safetensors.numpy import save_file

    save_file(
        {k: np.ascontiguousarray(np.asarray(v)) for k, v in state.items()},
        path,
    )


def load_config(
    model_dir: str,
    validate: bool = True,
    resolve: Optional[Callable[[str], Optional[str]]] = None,
) -> ModelConfig:
    """``config.json`` → :class:`ModelConfig` (the ``AutoConfig`` role,
    ``utils/model.py:83``, without requiring transformers).

    ``validate`` checks the model family against the registry — an
    unsupported ``model_type`` fails HERE rather than silently running the
    llama program over a foreign architecture's weights. ``resolve`` lets a
    remote resolver (``utils/hub.py``) fetch the config like any other
    checkpoint file.
    """
    resolve = resolve or _default_resolve(model_dir)
    path = resolve("config.json")
    if path is None:
        raise FileNotFoundError(f"no config.json under {model_dir!r}")
    with open(path) as f:
        cfg = ModelConfig.from_hf_config(json.load(f))
    if validate:
        from ..models import registry

        registry.validate_config(cfg)
    return cfg


def shard_put(params: Dict[str, Any], mesh, use_pp: bool = False):
    """Place a loaded param pytree onto the mesh with its TP/PP shardings
    (replaces ``set_module_tensor_to_device`` + ``.to("cuda")``,
    ``utils/model.py:70,121``)."""
    from ..parallel import tp

    return tp.shard_pytree(params, mesh, tp.param_pspecs(params, use_pp))
