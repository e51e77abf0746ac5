"""Device mesh construction and global parallel state.

TPU-native replacement for the reference's process-group world
(vllm/distributed/parallel_state.py:1050 ``initialize_model_parallel``
builds ExternalDP x (DP|TKNP) x PP x TP NCCL groups): here the same axes
become dimensions of one ``jax.sharding.Mesh`` and XLA inserts the
collectives over ICI/DCN. Explicit groups survive only where control
matters (PP send/recv, MoE all2all, KV-pull), expressed via shard_map.
"""

from contextlib import contextmanager
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from vllm_distributed_tpu.config import (MESH_AXIS_DATA, MESH_AXIS_EXPERT,
                                         MESH_AXIS_MODEL, MESH_AXIS_PIPE,
                                         MESH_AXIS_TOKEN, ParallelConfig)
from vllm_distributed_tpu.logger import init_logger

logger = init_logger(__name__)

shard_map = jax.shard_map

_GLOBAL_MESH: Optional[Mesh] = None

AXIS_ORDER = (MESH_AXIS_DATA, MESH_AXIS_TOKEN, MESH_AXIS_PIPE,
              MESH_AXIS_MODEL)


def build_mesh(parallel_config: ParallelConfig,
               devices: Optional[list] = None) -> Mesh:
    """Build the engine's device mesh.

    Axis order is (data, token, pipe, model), outermost to innermost:
    jax.experimental.mesh_utils would give ICI-contiguous innermost axes;
    we keep np.reshape ordering which matches device enumeration on a
    single slice (model-parallel neighbors are ICI neighbors).
    """
    if devices is None:
        devices = jax.devices()
    shape = parallel_config.mesh_shape
    sizes = tuple(shape[a] for a in AXIS_ORDER)
    world = int(np.prod(sizes))
    if world > len(devices):
        raise ValueError(
            f"mesh {dict(shape)} needs {world} devices, "
            f"only {len(devices)} available")
    pp = shape.get(MESH_AXIS_PIPE, 1) if isinstance(shape, dict) else 1
    try:
        procs = jax.process_count()
    except Exception:  # noqa: BLE001 - uninitialized backends
        procs = 1
    dpp = world // max(procs, 1)
    if pp > 1 and procs > 1 and dpp % pp == 0:
        # Multi-process pipeline: carve stages out of each process's
        # LOCAL devices so every process contributes devices to every
        # stage — the per-stage activation handoff (pp_runner's
        # device_put) then only moves data between locally-addressable
        # shards. Stages that own whole processes would strand the
        # handoff: the destination process holds no source shard.
        d, k, p_, m = (sizes[AXIS_ORDER.index(a)] for a in AXIS_ORDER)
        arr = np.array(devices[:world]).reshape(procs, pp, dpp // pp)
        stage_major = arr.transpose(1, 0, 2).reshape(pp, world // pp)
        dev_array = stage_major.reshape(pp, d, k, m).transpose(1, 2, 0,
                                                               3)
    else:
        dev_array = np.array(devices[:world]).reshape(sizes)
    return Mesh(dev_array, AXIS_ORDER)


def set_global_mesh(mesh: Mesh) -> None:
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_global_mesh() -> Mesh:
    assert _GLOBAL_MESH is not None, "mesh not initialized"
    return _GLOBAL_MESH


def has_global_mesh() -> bool:
    return _GLOBAL_MESH is not None


@contextmanager
def global_mesh(mesh: Mesh):
    global _GLOBAL_MESH
    prev = _GLOBAL_MESH
    _GLOBAL_MESH = mesh
    try:
        yield mesh
    finally:
        _GLOBAL_MESH = prev


def sharding(spec: PartitionSpec,
             mesh: Optional[Mesh] = None) -> NamedSharding:
    return NamedSharding(mesh or get_global_mesh(), spec)


def replicated(mesh: Optional[Mesh] = None) -> NamedSharding:
    return sharding(PartitionSpec(), mesh)


# Common parameter specs -----------------------------------------------------

P = PartitionSpec


def tp_size(mesh: Optional[Mesh] = None) -> int:
    return (mesh or get_global_mesh()).shape[MESH_AXIS_MODEL]


def dp_size(mesh: Optional[Mesh] = None) -> int:
    return (mesh or get_global_mesh()).shape[MESH_AXIS_DATA]


def tknp_size(mesh: Optional[Mesh] = None) -> int:
    return (mesh or get_global_mesh()).shape[MESH_AXIS_TOKEN]
