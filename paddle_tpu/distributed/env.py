"""Distributed environment: mesh state, axis context, rank/world info.

TPU-native replacement for the reference's env-variable + NCCL-ring world
(PADDLE_TRAINER_ID/PADDLE_TRAINER_ENDPOINTS, collective_helper.h ring
registry): here the world is a jax.sharding.Mesh with named axes
(dp/tp/pp/sp/ep …), and "being inside a ring" becomes "tracing inside a
shard_map over an axis". Collective ops consult this module to find the
active axis.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

_state = threading.local()
_global_mesh: Optional[Mesh] = None

# canonical axis names, mirroring the reference's parallelism taxonomy
DATA_AXIS = "dp"
TENSOR_AXIS = "tp"
PIPE_AXIS = "pp"
SEQUENCE_AXIS = "sp"
EXPERT_AXIS = "ep"


def build_mesh(mesh_shape: Dict[str, int] = None,
               devices: Sequence[jax.Device] = None) -> Mesh:
    """Create a named device mesh. mesh_shape e.g. {"dp": 2, "tp": 4}."""
    devs = list(devices) if devices is not None else jax.devices()
    if not mesh_shape:
        mesh_shape = {DATA_AXIS: len(devs)}
    names = tuple(mesh_shape.keys())
    sizes = tuple(int(v) for v in mesh_shape.values())
    n = int(np.prod(sizes))
    if n > len(devs):
        raise ValueError(
            f"mesh {mesh_shape} needs {n} devices, have {len(devs)}")
    arr = np.asarray(devs[:n]).reshape(sizes)
    return Mesh(arr, names)


def set_mesh(mesh: Optional[Mesh]):
    global _global_mesh
    _global_mesh = mesh
    return mesh


def get_mesh() -> Optional[Mesh]:
    return _global_mesh


def ensure_mesh(mesh_shape=None) -> Mesh:
    global _global_mesh
    if _global_mesh is None or mesh_shape is not None:
        _global_mesh = build_mesh(mesh_shape)
    return _global_mesh


# -- axis context: which mesh axes are "live" in the current trace ----------

def _axis_stack() -> List[Tuple[str, ...]]:
    if not hasattr(_state, "axes"):
        _state.axes = []
    return _state.axes


class axis_context:
    """Marks a region as tracing inside shard_map over the given axes, so
    collective ops can pick their axis (ring_id analogue)."""

    def __init__(self, *axes: str):
        self.axes = axes

    def __enter__(self):
        _axis_stack().append(self.axes)
        return self

    def __exit__(self, *exc):
        _axis_stack().pop()


def current_axes() -> Tuple[str, ...]:
    stack = _axis_stack()
    out = []
    for axes in stack:
        out.extend(axes)
    return tuple(out)


def current_axis_name(preferred: str = None) -> Optional[str]:
    axes = current_axes()
    if not axes:
        return None
    if preferred is not None and preferred in axes:
        return preferred
    return axes[0]


# -- step mesh: the mesh a GSPMD-sharded step is being traced for ------------

class step_mesh:
    """Marks a region as tracing one jit whose arrays are GSPMD-sharded
    over `mesh`, batch dim over `batch_axes` (static.TrainStep enters
    it around the forward). F.flash_attention hands it to its op, which
    shard_maps the Mosaic kernel — something the compiler cannot
    partition by itself — over the same mesh; everything else ignores
    it."""

    def __init__(self, mesh: Mesh, batch_axes: Sequence[str]):
        self.mesh = mesh
        self.batch_axes = tuple(batch_axes)

    def __enter__(self):
        self._prev = getattr(_state, "step_mesh", None)
        _state.step_mesh = self
        return self

    def __exit__(self, *exc):
        _state.step_mesh = self._prev


def current_step_mesh() -> Optional[step_mesh]:
    return getattr(_state, "step_mesh", None)


# -- process-level rank info (multi-host; single-host => rank 0/1) ----------

def get_rank() -> int:
    return int(os.environ.get("PADDLE_TRAINER_ID",
                              getattr(jax, "process_index", lambda: 0)()))


def get_world_size() -> int:
    env = os.environ.get("PADDLE_TRAINERS_NUM")
    if env:
        return int(env)
    try:
        return jax.process_count()
    except RuntimeError:
        return 1


def device_count() -> int:
    return len(jax.devices())
