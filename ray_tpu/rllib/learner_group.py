"""Cross-process LearnerGroup: learner ACTORS spanning processes/hosts.

Reference: ``rllib/core/learner/learner_group.py:61`` — N learner workers,
each on its own accelerator(s), DDP-synced with NCCL allreduce; the
Algorithm ships batches to the group and pulls weights back.

TPU-first redesign: the N learner actors form ONE ``jax.distributed``
namespace (the seam proven by ``tests/test_train.py``'s two-process mesh
test) and build a single global ``Mesh`` over every process's devices.  The
update stays the same jitted program as the local path — each actor feeds
its process-local batch slice, ``jax.make_array_from_process_local_data``
assembles the global batch, and XLA inserts the cross-process gradient psum
(ICI on a real pod, gloo on the CPU CI mesh).  There is no hand-written
allreduce anywhere.

On a real multi-host TPU pod: one LearnerWorker per host (placement-group
STRICT_SPREAD), each seeing its local chips; here in CI: N processes on one
box, each with the 8-device virtual CPU mesh.
"""

from __future__ import annotations

import socket
from typing import Any, Dict, List, Optional, Type

import numpy as np


def _node_ip() -> str:
    """Best-effort routable IP for the jax.distributed coordinator (falls
    back to loopback on a single box, which is the CI case)."""
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("8.8.8.8", 80))
        ip = s.getsockname()[0]
        s.close()
        return ip
    except Exception:
        return "127.0.0.1"


class LearnerWorker:
    """One learner process of the group (runs as a ray_tpu actor).

    ``__init__`` stores config only; ``setup`` joins the jax.distributed
    namespace and builds the learner — split so the group can first ask
    rank 0 for a coordinator address, then set every rank up concurrently
    (``jax.distributed.initialize`` blocks until all ranks connect).
    """

    def __init__(self, model_spec: Dict[str, Any], train_cfg: Dict[str, Any],
                 learner_cls: Optional[Type] = None, seed: int = 0,
                 devices_per_learner: int = 1):
        self._spec = dict(model_spec)
        self._cfg = dict(train_cfg)
        self._learner_cls = learner_cls
        self._seed = seed
        self._per = int(devices_per_learner)
        self.learner = None
        self.rank = 0

    def pick_coordinator(self) -> str:
        s = socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        return f"{_node_ip()}:{port}"

    def setup(self, coordinator: str, rank: int, world: int) -> Dict[str, int]:
        import jax

        if world > 1:
            jax.distributed.initialize(coordinator_address=coordinator,
                                       num_processes=world, process_id=rank)
        from .learner import Learner
        from .models import build_model

        model = build_model(self._spec)
        # dp mesh over the first devices_per_learner devices of EVERY
        # process, in process order (reference num_gpus_per_learner); the
        # process-major order keeps each rank's batch block contiguous.
        by_proc: Dict[int, list] = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, []).append(d)
        devs = np.array([d for p in sorted(by_proc)
                         for d in by_proc[p][:self._per]])
        mesh = jax.sharding.Mesh(devs, ("dp",))
        cls = self._learner_cls or Learner
        self.learner = cls(model, self._cfg, mesh=mesh, seed=self._seed)
        self.rank = rank
        return {"rank": rank, "num_devices": len(devs),
                "num_processes": jax.process_count()}

    def update(self, shard: Dict[str, np.ndarray]) -> Optional[Dict[str, float]]:
        """Run the collective update on this rank's batch slice.  Every rank
        MUST be called with its slice of the same global batch (the group
        guarantees this); only rank 0 returns metrics."""
        metrics = self.learner.update(shard)
        return metrics if self.rank == 0 else None

    def get_weights(self) -> Dict[str, np.ndarray]:
        return self.learner.get_weights()


class DistributedLearnerGroup:
    """N learner actors, one jax.distributed mesh, same Learner interface.

    Drop-in for ``LearnerGroup``: ``update(rollout)`` splits the global
    batch's env axis across ranks and blocks on all of them (the psum is a
    barrier anyway); ``get_weights`` reads rank 0's replicated params.
    """

    def __init__(self, model_spec: Dict[str, Any], train_cfg: Dict[str, Any],
                 num_learners: int, seed: int = 0,
                 learner_cls: Optional[Type] = None,
                 devices_per_learner: int = 1):
        import ray_tpu

        self.world = int(num_learners)
        self.dp_shards = self.world * int(devices_per_learner)
        actor_cls = ray_tpu.remote(LearnerWorker)
        self.workers = [
            actor_cls.options(num_cpus=1).remote(
                model_spec, train_cfg, learner_cls, seed,
                devices_per_learner)
            for _ in range(self.world)]
        try:
            coordinator = ray_tpu.get(
                self.workers[0].pick_coordinator.remote(), timeout=120)
            self.info = ray_tpu.get(
                [w.setup.remote(coordinator, i, self.world)
                 for i, w in enumerate(self.workers)], timeout=600)[0]
        except BaseException:
            # a rank failing setup leaves the others blocked inside
            # jax.distributed.initialize — reap them all before raising
            self.shutdown()
            raise

    def _split(self, rollout: Dict[str, np.ndarray]) -> List[Dict[str, np.ndarray]]:
        shards: List[Dict[str, np.ndarray]] = [dict() for _ in range(self.world)]
        for k, v in rollout.items():
            v = np.asarray(v)
            axis = 0 if v.ndim <= 1 else 1
            b = v.shape[axis]
            if b % self.dp_shards:
                raise ValueError(
                    f"batch axis {b} of '{k}' not divisible by the dp mesh "
                    f"({self.world} learners x devices_per_learner = "
                    f"{self.dp_shards} shards); size the per-update env "
                    f"axis (PPO: env_runners x num_envs; IMPALA: num_envs "
                    f"of ONE fragment) to a multiple of it")
            for i, piece in enumerate(np.split(v, self.world, axis=axis)):
                shards[i][k] = piece
        return shards

    def update(self, rollout: Dict[str, np.ndarray]) -> Dict[str, float]:
        import ray_tpu

        shards = self._split(rollout)
        out = ray_tpu.get(
            [w.update.remote(s) for w, s in zip(self.workers, shards)],
            timeout=600)
        return out[0]

    def get_weights(self) -> Dict[str, np.ndarray]:
        import ray_tpu

        return ray_tpu.get(self.workers[0].get_weights.remote(), timeout=300)

    def shutdown(self):
        import ray_tpu

        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
