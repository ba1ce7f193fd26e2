"""ray_tpu: a TPU-native distributed computing framework.

A ground-up rebuild of the reference framework's capabilities (tasks, actors,
distributed objects, Data/Train/Tune/Serve libraries) designed TPU-first:
TPU chips and ICI topology are first-class schedulable resources, training
parallelism is expressed as `jax.sharding` meshes compiled by XLA/GSPMD, and
collectives ride ICI — never NCCL.

Public API mirrors the reference's top-level surface
(python/ray/__init__.py): ``init, shutdown, remote, get, put, wait, kill,
cancel, get_actor, ...``.
"""

from ray_tpu import exceptions
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.worker import (ClientContext, available_resources,
                                     broadcast, cluster_usage,
                                     cancel, cluster_resources, free, get,
                                     get_actor, get_tpu_devices,
                                     get_tpu_ids, init,
                                     is_initialized, kill, nodes, put,
                                     shutdown, start_head_server, wait)
from ray_tpu.actor import ActorClass, ActorHandle, method
from ray_tpu.remote_function import RemoteFunction, remote
from ray_tpu.runtime_context import get_runtime_context

__version__ = "0.1.0"

# GPU-era alias: the accelerator resource on this framework is the TPU.
get_gpu_ids = get_tpu_ids

__all__ = [
    "ActorClass",
    "method",
    "ActorHandle",
    "ClientContext",
    "ObjectRef",
    "RemoteFunction",
    "__version__",
    "available_resources",
    "broadcast",
    "cluster_usage",
    "cancel",
    "cluster_resources",
    "exceptions",
    "free",
    "get",
    "get_actor",
    "get_gpu_ids",
    "get_runtime_context",
    "get_tpu_devices",
    "get_tpu_ids",
    "init",
    "is_initialized",
    "kill",
    "nodes",
    "put",
    "remote",
    "shutdown",
    "start_head_server",
    "wait",
]
