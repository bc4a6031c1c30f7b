"""Training backends: per-framework worker-group setup hooks.

Reference: ``python/ray/train/backend.py`` (``Backend``/``BackendConfig``) and
``train/torch/config.py:23,63,107`` (``_setup_torch_process_group`` — TCP
rendezvous + NCCL/Gloo).  The TPU-native backend instead forms ONE
``jax.distributed`` namespace: rank 0's node hosts the coordinator; every
worker calls ``jax.distributed.initialize(coordinator, num_processes, rank)``
and from then on ``jax.devices()`` spans all hosts — the mesh/pjit layer
(ray_tpu.parallel) does the rest.  There is no NCCL analogue to manage:
collectives are compiled into XLA programs and ride ICI/DCN.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, TYPE_CHECKING

if TYPE_CHECKING:
    from .worker_group import WorkerGroup


@dataclasses.dataclass
class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


class Backend:
    """Hooks around worker-group lifecycle."""

    def __init__(self, config: BackendConfig):
        self.config = config

    def on_start(self, worker_group: "WorkerGroup") -> None:
        pass

    def on_training_start(self, worker_group: "WorkerGroup") -> None:
        pass

    def on_shutdown(self, worker_group: "WorkerGroup") -> None:
        pass


@dataclasses.dataclass
class JaxBackendConfig(BackendConfig):
    """Forms the jax.distributed namespace across workers.

    distributed=None (auto): initialize only when num_workers > 1 — a single
    worker already sees its whole local slice.  coordinator_port=0 picks a
    free port on the rank-0 worker's host.
    """
    distributed: Optional[bool] = None
    coordinator_port: int = 0

    @property
    def backend_cls(self):
        return JaxBackend


def _setup_jax_distributed(coordinator: str, num_processes: int,
                           process_id: int) -> None:
    import jax

    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def _pick_coordinator(port: int) -> str:
    import socket
    hostname = socket.gethostbyname(socket.gethostname())
    if port == 0:
        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
    return f"{hostname}:{port}"


class JaxBackend(Backend):
    def on_start(self, worker_group: "WorkerGroup") -> None:
        cfg: JaxBackendConfig = self.config
        n = len(worker_group)
        do_dist = cfg.distributed if cfg.distributed is not None else n > 1
        if not do_dist:
            return
        coordinator = worker_group.execute_single(
            0, _pick_coordinator, cfg.coordinator_port)
        worker_group.execute(
            lambda rank=None: None)  # barrier: ensure all workers alive
        futures = [
            worker_group.execute_single_async(
                i, _setup_jax_distributed, coordinator, n, i)
            for i in range(n)
        ]
        import ray_tpu
        ray_tpu.get(futures, timeout=120)


@dataclasses.dataclass
class TorchBackendConfig(BackendConfig):
    """Forms a ``torch.distributed`` process group across the worker actors.

    Reference: ``python/ray/train/torch/config.py:63-160``
    (``_setup_torch_process_group`` — TCP-store rendezvous, backend
    nccl/gloo).  On TPU hosts torch is CPU-only, so the default backend is
    gloo; this exists for data pipelines and models that train with torch
    while the TPU path uses JaxBackend.
    """
    backend: str = "gloo"
    init_timeout_s: float = 120.0

    @property
    def backend_cls(self):
        return TorchBackend


def _setup_torch_process_group(init_method: str, backend: str, rank: int,
                               world_size: int, timeout_s: float) -> None:
    import datetime
    import torch.distributed as dist
    dist.init_process_group(
        backend=backend, init_method=init_method, rank=rank,
        world_size=world_size,
        timeout=datetime.timedelta(seconds=timeout_s))


def _teardown_torch_process_group() -> None:
    import torch.distributed as dist
    if dist.is_initialized():
        dist.destroy_process_group()


class TorchBackend(Backend):
    def on_start(self, worker_group: "WorkerGroup") -> None:
        cfg: TorchBackendConfig = self.config
        n = len(worker_group)
        coordinator = worker_group.execute_single(0, _pick_coordinator, 0)
        init_method = f"tcp://{coordinator}"
        futures = [
            worker_group.execute_single_async(
                i, _setup_torch_process_group, init_method, cfg.backend,
                i, n, cfg.init_timeout_s)
            for i in range(n)
        ]
        import ray_tpu
        ray_tpu.get(futures, timeout=cfg.init_timeout_s + 30)

    def on_shutdown(self, worker_group: "WorkerGroup") -> None:
        try:
            worker_group.execute(_teardown_torch_process_group)
        except Exception:
            pass


def prepare_torch_model(model):
    """Wrap a torch model in DistributedDataParallel when a process group is
    up (reference: ``train/torch/train_loop_utils.py:263`` prepare_model)."""
    import torch.distributed as dist
    if dist.is_available() and dist.is_initialized() \
            and dist.get_world_size() > 1:
        from torch.nn.parallel import DistributedDataParallel
        return DistributedDataParallel(model)
    return model
