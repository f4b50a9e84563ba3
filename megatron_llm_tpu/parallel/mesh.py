"""Device-mesh topology — the TPU-native ``parallel_state`` equivalent.

The reference builds NCCL process subgroups for data/tensor/pipeline/model/
embedding parallelism from a flat world, with TP innermost and DP strided
(reference: megatron/core/parallel_state.py:51-214 and group getters
:217-481).  On TPU the whole topology is one ``jax.sharding.Mesh`` with named
axes; collectives are expressed against axis names and placement against
``PartitionSpec``s, so the group-getter zoo becomes pure functions of the
mesh.  Axis order is (dp, fsdp, pp, cp, ep, tp, sp): tp fastest-varying so
TP collectives ride ICI neighbors; dp outermost so multi-slice deployments
put dp on DCN (reference rank-order parity: parallel_state.py docstring
example).  fsdp (serving weight residency) and sp (named-but-size-1
sequence axis) exist for the serving re-layout's partition rules.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import ParallelConfig

# Canonical axis names.
DATA_AXIS = "dp"
# Serving weight-residency axis (ParallelConfig.fsdp): weights split
# 1/fsdp along their non-tp dim under the serving re-layout
# (models/sharding.py:serving_param_specs).  Size 1 in training meshes.
FSDP_AXIS = "fsdp"
PIPELINE_AXIS = "pp"
CONTEXT_AXIS = "cp"
EXPERT_AXIS = "ep"
TENSOR_AXIS = "tp"
# Named sequence axis for the ("dp","fsdp","sp")-family partition rules
# (SNIPPETS exemplars).  Always size 1 here: decode runs one token per
# step and prefill activations already shard via cp/tp, so "sp" exists
# purely so specs naming it resolve against every mesh.
SEQ_AXIS = "sp"
AXIS_ORDER = (DATA_AXIS, FSDP_AXIS, PIPELINE_AXIS, CONTEXT_AXIS,
              EXPERT_AXIS, TENSOR_AXIS, SEQ_AXIS)


def build_mesh(
    parallel: ParallelConfig,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Create the (dp, fsdp, pp, cp, ep, tp, sp) mesh.

    Replaces ``mpu.initialize_model_parallel(tp, pp, vpp, split_rank)``
    (reference: megatron/core/parallel_state.py:51).  The first
    ``prod(shape)`` of ``devices`` are assigned by
    ``mesh_utils.create_device_mesh``, which follows the physical ICI
    topology on a TPU (on a v5e 2x2, tp pairs are ring neighbours) and is
    a plain reshape elsewhere.  If it cannot place the shape it raises,
    and so does this: a naive reshape in its stead would silently trade
    the ICI layout away.  The trailing sp axis is always size 1 (see
    SEQ_AXIS).

    A mesh over some but not all of the process's devices (a serving
    replica's submesh, ``--tp 2`` on a four-chip host) turns the
    persistent compile cache off for the process
    (utils/compile_cache.py:disable_compile_cache says why).
    """
    if devices is None:
        devices = jax.devices()
    shape = (
        parallel.data_parallel,
        getattr(parallel, "fsdp", 1),
        parallel.pipeline_parallel,
        parallel.context_parallel,
        parallel.expert_parallel,
        parallel.tensor_parallel,
        1,
    )
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(
            f"mesh shape {shape} needs {n} devices, have {len(devices)}"
        )
    if 1 < n < jax.device_count():
        from ..utils.compile_cache import disable_compile_cache

        disable_compile_cache(
            f"a mesh over {n} of {jax.device_count()} devices — cached "
            "executables for a submesh halt the TPU")
    from jax.experimental import mesh_utils

    return Mesh(mesh_utils.create_device_mesh(shape, devices=devices[:n]),
                AXIS_ORDER)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    if device is None:
        device = jax.devices()[0]
    return Mesh(np.asarray([device]).reshape((1,) * len(AXIS_ORDER)),
                AXIS_ORDER)


# ---------------------------------------------------------------------------
# Topology queries (group getters, reference parallel_state.py:217-481)
# ---------------------------------------------------------------------------


def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def tensor_parallel_size(mesh: Mesh) -> int:
    return axis_size(mesh, TENSOR_AXIS)


def pipeline_parallel_size(mesh: Mesh) -> int:
    return axis_size(mesh, PIPELINE_AXIS)


def data_parallel_size(mesh: Mesh) -> int:
    return axis_size(mesh, DATA_AXIS)


def fsdp_size(mesh: Mesh) -> int:
    return axis_size(mesh, FSDP_AXIS) if FSDP_AXIS in mesh.axis_names else 1


def context_parallel_size(mesh: Mesh) -> int:
    return axis_size(mesh, CONTEXT_AXIS)


def expert_parallel_size(mesh: Mesh) -> int:
    return axis_size(mesh, EXPERT_AXIS)


def pipeline_stage_layers(num_layers: int, pp: int, vpp: int = 1) -> list[int]:
    """Layers per pipeline stage (must divide evenly, like the reference's
    num_layers // transformer_pipeline_model_parallel_size at
    megatron/model/transformer.py:845-895)."""
    chunks = pp * vpp
    assert num_layers % chunks == 0, (
        f"num_layers {num_layers} must divide pipeline stages {chunks}"
    )
    return [num_layers // chunks] * chunks


def stage_layer_ranges(num_layers: int, pp: int) -> list[tuple[int, int]]:
    """Per-stage ``[lo, hi)`` layer ranges of the contiguous stage split.

    The serving layer-sharded layout (models/sharding.py:
    serving_param_specs with pp > 1) places the stacked layer axis over
    'pp', so stage ``s`` holds exactly ``[lo, hi)`` of the flat layer
    stack — this is the introspection mirror used by the GET /kv
    per-stage pool section (serving/engine.py:kv_snapshot)."""
    per = pipeline_stage_layers(num_layers, pp)[0]
    return [(s * per, (s + 1) * per) for s in range(pp)]


def is_first_stage(stage: int) -> bool:
    return stage == 0


def is_last_stage(stage: int, pp: int) -> bool:
    return stage == pp - 1


def prev_stage(stage: int, pp: int) -> int:
    """Reference: get_pipeline_model_parallel_prev_rank
    (parallel_state.py:463-471) — cyclic neighbor on the pp axis."""
    return (stage - 1) % pp


def next_stage(stage: int, pp: int) -> int:
    return (stage + 1) % pp


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


class _MeshStack(threading.local):
    """Per-thread mesh stack.  ``Mesh.__enter__`` is already thread-local
    in jax; this stack must match, or two sharded serving engines whose
    scheduler threads each sit inside their own ``use_mesh`` would read
    each other's mesh through ``current_mesh()`` (the router runs one
    engine thread per replica submesh)."""

    def __init__(self):
        self.stack: list[Mesh] = []


_MESH_STACK = _MeshStack()


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Context manager establishing the active mesh (and jax's own
    ``jax.sharding.use_mesh`` scope when available)."""
    _MESH_STACK.stack.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _MESH_STACK.stack.pop()


def current_mesh() -> Optional[Mesh]:
    if _MESH_STACK.stack:
        return _MESH_STACK.stack[-1]
    return None


def replica_submeshes(parallel: ParallelConfig, replicas: int,
                      devices: Optional[Sequence[jax.Device]] = None,
                      ) -> list[Mesh]:
    """Partition the device list into ``replicas`` disjoint submeshes of
    ``parallel``'s per-replica geometry (serving: pp·tp·fsdp devices
    each).

    The replicated-router serving topology is dp-at-the-front: instead of
    one mesh with a dp axis (which would make every dispatch a global
    program over all replicas), each engine replica gets its own
    independent mesh over a contiguous device slice, so replicas fail,
    drain, and compile independently — the sharded-worker / replicated-
    frontend split (serving/cluster/).
    """
    if devices is None:
        devices = jax.devices()
    per = (parallel.pipeline_parallel * parallel.tensor_parallel
           * parallel.context_parallel * parallel.expert_parallel
           * parallel.data_parallel * getattr(parallel, "fsdp", 1))
    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    if replicas * per > len(devices):
        raise ValueError(
            f"{replicas} replicas of {per} devices each need "
            f"{replicas * per} devices, have {len(devices)}")
    return [build_mesh(parallel, devices=devices[i * per:(i + 1) * per])
            for i in range(replicas)]


# ---------------------------------------------------------------------------
# Deterministic RNG (replaces the CUDA rng-state tracker,
# reference: megatron/core/tensor_parallel/random.py:64-172)
# ---------------------------------------------------------------------------

# The reference forks CUDA RNG state so TP ranks share the data-parallel
# dropout stream but differ inside TP regions (seed = base + 2718 + tp_rank).
# In JAX, randomness is functional: fold the axis index into the key inside
# shard_map/vmap when per-shard streams are needed, otherwise keys are global
# and XLA generates identical streams on replicated program text.

TP_SALT = 2718  # parity with reference seed offset (random.py:160-172)
PP_SALT = 100  # per-stage seed offset (reference: initialize.py:179-193)


def fold_in_axis(key: jax.Array, axis_name: str, salt: int = TP_SALT) -> jax.Array:
    """Inside shard_map: derive a per-shard key along ``axis_name``."""
    idx = jax.lax.axis_index(axis_name)
    return jax.random.fold_in(jax.random.fold_in(key, salt), idx)


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Bundle of logical-axis → mesh-axis assignments used by the sharding
    rules in models/sharding.py.  Kept as a dataclass so alternative layouts
    (e.g. 2D tp×ep) can be introduced without touching model code."""

    dp: str = DATA_AXIS
    fsdp: str = FSDP_AXIS
    pp: str = PIPELINE_AXIS
    cp: str = CONTEXT_AXIS
    ep: str = EXPERT_AXIS
    tp: str = TENSOR_AXIS
    sp: str = SEQ_AXIS
