"""Device-mesh construction for dp/pp/tp/sp parallelism.

The reference has no multi-device story at all — its only "tensor parallelism"
is single-device weight slicing
(``/root/reference/distributed_llm_inference/models/llama/modules.py:44-59``)
and its inter-node fabric was to be hivemind's DHT/gRPC
(``server/backend.py:4-7``). TPU-native, both collapse into one object: a
``jax.sharding.Mesh`` whose axes XLA compiles onto ICI links, with
``NamedSharding`` annotations doing the work of process groups + NCCL.

Axis meaning (order fixed, outer→inner for ICI locality):
    ``dp``   data parallel — batch rows, independent replicas
    ``pp``   pipeline parallel — layer-block stages (``parallel/pipeline.py``)
    ``ep``   expert parallel — MoE experts (``ops/moe.py``)
    ``tp``   tensor parallel — attention heads / MLP features
    ``sp``   sequence/context parallel — sequence chunks (``parallel/ring.py``)

``tp`` and ``sp`` are innermost so their heavy collectives (all-reduce of
row-parallel matmuls, ring permutes of KV blocks) ride the fastest ICI hops.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..config import MeshConfig

__all__ = [
    "build_mesh",
    "initialize_distributed",
    "single_device_mesh",
    "named_sharding",
]


def initialize_distributed(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    local_device_ids: Optional[Sequence[int]] = None,
) -> None:
    """Join this process to a multi-host SPMD job (``jax.distributed``).

    The multi-HOST half of the two-tier design (SURVEY §5.8): within one
    pod slice, N processes (one per host) initialize against a coordinator
    and ``jax.devices()`` becomes the GLOBAL device list — after which
    :func:`build_mesh` lays dp/pp/ep/tp/sp over every chip in the slice and
    XLA compiles the collectives onto ICI/DCN exactly as it does
    single-host (the role the reference delegated to hivemind's DHT +
    NCCL process groups and never finished, ``server/backend.py:4-7``).
    Meshes BIGGER than one slice remain the relay tier's job
    (``distributed/`` — one engine or node per slice, activations over
    TCP).

    Call once per process before any other JAX API. On CPU test rigs the
    same call builds a gloo-backed multi-process platform (see
    tests/test_multihost.py, which runs a REAL 2-process global mesh).
    """
    kwargs = {}
    if local_device_ids is not None:
        kwargs["local_device_ids"] = list(local_device_ids)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )


def build_mesh(
    mesh_cfg: MeshConfig, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build the ``(dp, pp, tp, sp)`` mesh from a :class:`MeshConfig`.

    Uses ``mesh_utils.create_device_mesh`` when the requested shape covers all
    devices of the default backend (it picks an ICI-friendly physical layout on
    real TPU slices, and a plain reshape on CPU) — a shape it cannot lay over
    the slice is an error, not a reason to serve from an enumeration-order
    mesh whose tp/sp collectives cross the slice; a subset of the devices
    (virtual CPU meshes, tests) is laid out in order.
    """
    n = mesh_cfg.num_devices
    if devices is None:
        devices = jax.devices()
    if n > len(devices):
        raise ValueError(
            f"mesh {mesh_cfg.shape} needs {n} devices, have {len(devices)}"
        )
    if n == len(devices):
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(
            mesh_cfg.shape, devices=list(devices)
        )
        return Mesh(dev_array, mesh_cfg.axis_names)
    dev_array = np.asarray(list(devices)[:n]).reshape(mesh_cfg.shape)
    return Mesh(dev_array, mesh_cfg.axis_names)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    """An all-ones mesh — lets all sharded code paths run unchanged on one chip."""
    if device is None:
        device = jax.devices()[0]
    cfg = MeshConfig()
    return Mesh(
        np.asarray([device]).reshape(cfg.shape), cfg.axis_names
    )


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))
