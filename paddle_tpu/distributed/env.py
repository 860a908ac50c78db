"""Distributed environment: rank/world accessors + multi-controller init.

Reference parity: env parsing in `python/paddle/distributed/collective.py`
(`init_parallel_env`: PADDLE_TRAINER_ID / PADDLE_TRAINER_ENDPOINTS,
TCPStore rendezvous, ProcessGroupNCCL default group) [UNVERIFIED — empty
reference mount].

TPU-native: there is one JAX process per host (multi-controller); global
device count = world size in chips.  ``init_parallel_env`` performs
``jax.distributed.initialize`` when multi-host env vars are present, then
builds the global device Mesh.  PADDLE_* env vars are honored for
launcher compatibility.
"""
from __future__ import annotations

import os

import numpy as np
import jax

__all__ = ["init_parallel_env", "get_rank", "get_world_size",
           "is_initialized", "parallel_device_count", "global_mesh",
           "set_global_mesh", "ParallelEnv", "device_mesh_shape"]

_initialized = False
_global_mesh = None


def is_initialized():
    return _initialized


def get_rank(group=None):
    if group is not None:
        return group.get_group_rank()
    return int(os.environ.get("PADDLE_TRAINER_ID", jax.process_index()))


def get_world_size(group=None):
    """Device-level SPMD world size (one rank per chip).

    NB: deliberately NOT PADDLE_TRAINERS_NUM — that env var counts
    controller PROCESSES (one per host, set by the launch CLI) and only
    feeds jax.distributed.initialize; the mesh/topology world is the
    global chip count, which jax.device_count() reports across all
    processes once the runtime is initialized."""
    if group is not None:
        return group.nranks
    return jax.device_count()


def parallel_device_count():
    return jax.local_device_count()


def init_parallel_env(strategy=None):
    """Initialize the distributed runtime.

    Multi-host: uses jax.distributed coordination (reference: TCPStore +
    nccl comm init).  Single-host: builds the mesh over local devices.
    """
    global _initialized, _global_mesh
    if _initialized:
        return ParallelEnv()
    coord = os.environ.get("PADDLE_MASTER") or \
        os.environ.get("MASTER_ADDR")
    # total controller processes: set by the launch CLI
    # (= nnodes * nproc_per_node); one per host on TPU
    nprocs = int(os.environ.get(
        "PADDLE_TRAINERS_NUM", os.environ.get("PADDLE_NNODES", "1")))
    if nprocs > 1 and coord and not jax.distributed.is_initialized():
        port = os.environ.get("MASTER_PORT", "8476")
        jax.distributed.initialize(
            coordinator_address=f"{coord.split(':')[0]}:{port}",
            num_processes=nprocs,
            process_id=int(os.environ.get("PADDLE_TRAINER_ID", "0")))
    _initialized = True
    if _global_mesh is None:
        devs = np.array(jax.devices())
        _global_mesh = jax.sharding.Mesh(devs, ("dp",))
    return ParallelEnv()


def global_mesh():
    """The framework-wide device mesh (created lazily).

    An active :class:`~.auto_parallel.sharding.MeshPlan`
    (``PADDLE_TPU_MESH`` or ``set_mesh_plan``) defines the topology;
    otherwise every visible device forms a 1-D ``dp`` mesh."""
    global _global_mesh
    if _global_mesh is None:
        from .auto_parallel.sharding import get_mesh_plan
        plan = get_mesh_plan()
        if plan is not None and not plan.is_virtual:
            _global_mesh = plan.mesh
        else:
            devs = np.array(jax.devices())
            _global_mesh = jax.sharding.Mesh(devs, ("dp",))
    return _global_mesh


def set_global_mesh(mesh):
    global _global_mesh
    _global_mesh = mesh


def device_mesh_shape():
    m = global_mesh()
    return dict(zip(m.axis_names, m.devices.shape))


class ParallelEnv:
    """Reference parity: paddle.distributed.ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def local_rank(self):
        return int(os.environ.get("PADDLE_LOCAL_RANK", "0"))

    @property
    def world_size(self):
        return get_world_size()

    @property
    def nranks(self):
        return get_world_size()

    @property
    def device_id(self):
        return self.local_rank

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "127.0.0.1:0")

    @property
    def trainer_endpoints(self):
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        return eps.split(",") if eps else []
