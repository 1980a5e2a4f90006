"""init_parallel_env + DataParallel (eager DDP surface).

Reference: distributed/parallel.py:57 init_parallel_env (TCP store + NCCL
comm bootstrap), fluid/dygraph/parallel.py:322 DataParallel + C++ Reducer
(imperative/reducer.cc — bucketed fused allreduce on backward hooks).

TPU-native: inside one process, "replicas" are mesh devices. DataParallel
shards the input batch over the dp axis with jax.device_put; every eager
op then executes SPMD (computation follows sharding) and XLA inserts the
gradient all-reduce during backward — the Reducer's bucketing/fusion is
the XLA partitioner's job now. Multi-host: jax.distributed.initialize
(coordination service ≡ gen_comm_id TCP bootstrap).
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..framework import Tensor
from ..nn.layer.layers import Layer
from .env import DATA_AXIS, build_mesh, ensure_mesh, get_mesh, set_mesh

__all__ = ["init_parallel_env", "DataParallel", "ParallelEnv"]


def init_parallel_env(mesh_shape=None):
    """Reference parallel.py:57. Multi-host: initialize the coordination
    service from the launcher's env (PADDLE_TRAINER_ID/ENDPOINTS or
    JAX_COORDINATOR); always: build + install the global mesh."""
    coord = os.environ.get("PADDLE_MASTER",
                           os.environ.get("MASTER_ADDR"))
    nproc = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if coord and nproc > 1 and jax.process_count() == 1:
        port = os.environ.get("MASTER_PORT", "8476")
        jax.distributed.initialize(f"{coord}:{port}", num_processes=nproc,
                                   process_id=rank)
    mesh = build_mesh(mesh_shape)
    set_mesh(mesh)
    return ParallelEnv()


class ParallelEnv:
    """Reference fluid/dygraph/parallel.py ParallelEnv parity."""

    @property
    def rank(self):
        from .env import get_rank
        return get_rank()

    @property
    def world_size(self):
        from .env import get_world_size
        return get_world_size()

    @property
    def device_id(self):
        return 0

    @property
    def nranks(self):
        return self.world_size

    @property
    def local_rank(self):
        return self.rank


class DataParallel(Layer):
    """paddle.DataParallel: wrap a layer for data-parallel training.

    Shards each forward input's batch dim over the 'dp' mesh axis; jax
    executes all following eager ops SPMD across devices, and backward
    produces correctly all-reduced parameter grads (the Reducer's job,
    done by the partitioner). scale_loss/apply_collective_grads kept as
    identity shims for API parity — loss scaling by 1/nranks is implicit
    in mean-reduction over the global batch.
    """

    def __init__(self, layers: Layer, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None, comm_config=None, plan=None):
        super().__init__()
        self._layers = layers
        self.find_unused_parameters = find_unused_parameters
        # MeshPlan: one layout declaration drives placement — params
        # land on plan.param_spec (fsdp shards them; XLA then places
        # the param all-gathers / grad reduce-scatters), inputs ride
        # plan.data_spec's (dp, fsdp) batch axes. plan=None keeps the
        # classic dp-only behavior bit-for-bit.
        self._plan = plan
        mesh = plan.mesh if plan is not None else ensure_mesh()
        self._dp_sharding = None
        self._data_axes = (DATA_AXIS,)
        if plan is not None:
            axes = tuple(a for a in ("dp", "fsdp")
                         if plan.sizes[a] > 1)
            if axes:
                self._dp_sharding = mesh
                self._data_axes = axes
            for name, t in layers.state_dict().items():
                if isinstance(t, Tensor) and t._data.ndim > 0:
                    t._data = jax.device_put(
                        t._data, NamedSharding(
                            mesh, plan.param_spec(name, t)))
        elif DATA_AXIS in mesh.axis_names and \
                mesh.shape[DATA_AXIS] > 1:
            self._dp_sharding = mesh
        # comm-optimized explicit grad sync (distributed.comm): a
        # CommConfig turns apply_collective_grads() from the identity
        # shim into the real bucketed/planned/quantized fused
        # all-reduce over the dp axis (CommConfig.bucket_bytes is the
        # reference Reducer's comm_buffer_size knob, in bytes).
        self._comm_sync = None
        self._comm_state = None
        if comm_config is not None:
            from .comm import CommConfig, GradSynchronizer
            if not isinstance(comm_config, CommConfig):
                raise TypeError(
                    f"comm_config must be a distributed.comm.CommConfig,"
                    f" got {type(comm_config).__name__}")
            self._comm_sync = GradSynchronizer(comm_config)

    def forward(self, *inputs, **kwargs):
        if self._dp_sharding is not None:
            placed = []
            for t in inputs:
                if isinstance(t, Tensor) and t._data.ndim > 0:
                    batch = self._data_axes if len(self._data_axes) > 1 \
                        else self._data_axes[0]
                    spec = P(*([batch] + [None] * (t._data.ndim - 1)))
                    arr = jax.device_put(
                        t._data, NamedSharding(self._dp_sharding, spec))
                    nt = Tensor(arr, stop_gradient=t.stop_gradient)
                    placed.append(nt)
                else:
                    placed.append(t)
            inputs = tuple(placed)
        return self._layers(*inputs, **kwargs)

    def scale_loss(self, loss):
        return loss

    def apply_collective_grads(self):
        """Fused post-backward gradient sync (fluid Reducer analogue).

        Without a comm_config this stays the API-parity no-op (under
        SPMD sharding the partitioner already all-reduced the grads).
        With one, every trainable param's .grad runs through the
        bucketed planned all-reduce — in the eager single-controller
        world the collective is the world-size-1 identity, but the
        bucketing/compression (and their comm.* receipts) are the
        real thing: int8_ef quantizes grads with error feedback
        exactly as it would on a pod, so convergence behavior is
        testable off-hardware. Inside a shard_map trace the fused
        collectives lower to real ICI traffic."""
        if self._comm_sync is None:
            return None
        from ..framework import Tensor
        named = self._layers.state_dict()
        grads = {k: t.grad._data for k, t in named.items()
                 if not t.stop_gradient and t.grad is not None}
        if not grads:
            return None
        if self._comm_state is None:
            self._comm_state = self._comm_sync.init_state(grads)
        synced, self._comm_state = self._comm_sync(grads,
                                                   self._comm_state)
        for k, g in synced.items():
            named[k].grad = Tensor(g)
        return None

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, state_dict, *a, **k):
        return self._layers.set_state_dict(state_dict, *a, **k)
