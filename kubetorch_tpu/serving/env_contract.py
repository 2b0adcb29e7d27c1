"""The pod environment contract: how controller metadata and rank identity
reach user code.

Reference contract (``serving/design.md:266-278`` + ``_apply_metadata``
``http_server.py:254``): controller pushes workload metadata over WS, the
server exports it as ``KT_*`` env vars, and each rank subprocess additionally
gets framework-specific distributed env vars (``spmd/{pytorch,jax,
tensorflow}_process.py``).

TPU-first deltas:
- JAX is the primary framework: ``JaxEnv`` wires
  ``jax.distributed.initialize`` coordinates and — critically on TPU — the
  per-host TPU visibility vars. One process per TPU *host* (megacore), not
  per chip.
- Multi-host worlds also get the TPU runtime vars (``TPU_WORKER_ID``,
  ``TPU_WORKER_HOSTNAMES``) so libtpu agrees with the mesh about host ordering.
- The persistent compile cache is not part of this contract: every rank of
  every framework places it at start (``compile_cache.ensure_compile_cache``).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, List, Optional

# Metadata env keys (pushed controller → pod, applied by the server)
KT_MODULE_NAME = "KT_MODULE_NAME"
KT_CLS_OR_FN_NAME = "KT_CLS_OR_FN_NAME"
KT_FILE_PATH = "KT_FILE_PATH"
KT_PROJECT_ROOT = "KT_PROJECT_ROOT"
KT_INIT_ARGS = "KT_INIT_ARGS"
KT_CALLABLE_TYPE = "KT_CALLABLE_TYPE"          # fn | cls | app | cmd
KT_DISTRIBUTED_CONFIG = "KT_DISTRIBUTED_CONFIG"
KT_LAUNCH_ID = "KT_LAUNCH_ID"
KT_SERVICE_NAME = "KT_SERVICE_NAME"
KT_NAMESPACE = "KT_NAMESPACE"
KT_ALLOWED_SERIALIZATION = "KT_ALLOWED_SERIALIZATION"
KT_RUNTIME_CONFIG = "KT_RUNTIME_CONFIG"

METADATA_KEYS = [
    KT_MODULE_NAME, KT_CLS_OR_FN_NAME, KT_FILE_PATH, KT_PROJECT_ROOT,
    KT_INIT_ARGS, KT_CALLABLE_TYPE, KT_DISTRIBUTED_CONFIG, KT_LAUNCH_ID,
    KT_SERVICE_NAME, KT_NAMESPACE, KT_ALLOWED_SERIALIZATION, KT_RUNTIME_CONFIG,
]


def apply_metadata(metadata: Dict[str, object]) -> None:
    """Export workload metadata as env vars (values json-encoded if not str)."""
    for key, value in metadata.items():
        env_key = key if key.startswith("KT_") else f"KT_{key.upper()}"
        if value is None:
            os.environ.pop(env_key, None)
        elif isinstance(value, str):
            os.environ[env_key] = value
        else:
            os.environ[env_key] = json.dumps(value)


def read_metadata() -> Dict[str, str]:
    return {k: os.environ[k] for k in METADATA_KEYS if k in os.environ}


@dataclass
class RankInfo:
    """Identity of one rank subprocess in the global job."""

    node_rank: int
    local_rank: int
    nproc_per_node: int
    num_nodes: int
    pod_ips: List[str]

    @property
    def world_size(self) -> int:
        return self.num_nodes * self.nproc_per_node

    @property
    def rank(self) -> int:
        return self.node_rank * self.nproc_per_node + self.local_rank

    @property
    def master_ip(self) -> str:
        return self.pod_ips[0] if self.pod_ips else "127.0.0.1"


class FrameworkEnv:
    """Base: generic SPMD env contract (reference process_worker.py:75-102)."""

    name = "spmd"
    needs_restart_between_calls = False
    # Whether rank identity may be rebound per request (worker-subset calls,
    # reference spmd_supervisor.py:345-364 assembles env per call). True for
    # frameworks whose collectives initialize inside the request (pytorch
    # gloo/NCCL process groups, TF strategies, generic SPMD). False when
    # identity is physically fixed at process spawn.
    per_call_identity = True

    def env(self, info: RankInfo) -> Dict[str, str]:
        return {
            "WORLD_SIZE": str(info.world_size),
            "RANK": str(info.rank),
            "LOCAL_RANK": str(info.local_rank),
            "NODE_RANK": str(info.node_rank),
            "POD_IPS": ",".join(info.pod_ips),
        }

    def auto_nproc(self) -> int:
        """Processes per node when the user didn't specify."""
        return 1

    def worker_cleanup(self) -> None:
        """Called in the rank subprocess on reload/teardown."""


class JaxEnv(FrameworkEnv):
    """JAX on TPU: one process per host, chips exclusively owned.

    Coordinator = rank-0 pod IP. ``jax.distributed.initialize`` picks these
    up from env (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID)
    so user code needs zero boilerplate.
    """

    name = "jax"
    coordinator_port = 1234
    # TPU chips are exclusively owned from spawn and jax.distributed
    # initializes once per process — the compiled mesh's identity cannot be
    # rebound per request. Worker-subset calls keep deployment-wide identity
    # (use shard_map sub-meshes inside the program to address chip subsets).
    per_call_identity = False

    def env(self, info: RankInfo) -> Dict[str, str]:
        e = super().env(info)
        e.update({
            "JAX_COORDINATOR_ADDRESS": f"{info.master_ip}:{self.coordinator_port}",
            "JAX_NUM_PROCESSES": str(info.world_size),
            "JAX_PROCESS_ID": str(info.rank),
        })
        if info.num_nodes > 1:
            # libtpu host ordering must agree with the JAX process ids. One
            # host needs neither: libtpu reads these, and a single-host world
            # named by a loopback alias is not a slice it can resolve
            e.update({
                "TPU_WORKER_ID": str(info.rank),
                "TPU_WORKER_HOSTNAMES": ",".join(info.pod_ips),
            })
        return e

    def auto_nproc(self) -> int:
        # one process per TPU host (it owns all local chips / megacore)
        return 1

    def worker_cleanup(self) -> None:
        # Release the TPU: libtpu holds chips per-process, so a clean reload
        # must shut the distributed client down before respawn (SURVEY §7
        # hard-part 3).
        try:
            import jax
            jax.distributed.shutdown()
        except Exception:
            pass


class PyTorchEnv(FrameworkEnv):
    name = "pytorch"
    master_port = 12355

    def env(self, info: RankInfo) -> Dict[str, str]:
        e = super().env(info)
        e.update({
            "MASTER_ADDR": info.master_ip,
            "MASTER_PORT": str(self.master_port),
        })
        return e

    def auto_nproc(self) -> int:
        try:
            import torch
            if torch.cuda.is_available():
                return torch.cuda.device_count()
        except Exception:
            pass
        return 1

    def worker_cleanup(self) -> None:
        try:
            import torch.distributed as dist
            if dist.is_initialized():
                dist.destroy_process_group()
        except Exception:
            pass


class TensorflowEnv(FrameworkEnv):
    name = "tensorflow"
    port = 2222

    def env(self, info: RankInfo) -> Dict[str, str]:
        e = super().env(info)
        cluster = {
            "cluster": {"worker": [f"{ip}:{self.port}" for ip in info.pod_ips]},
            "task": {"type": "worker", "index": info.node_rank},
        }
        e["TF_CONFIG"] = json.dumps(cluster)
        return e


FRAMEWORKS: Dict[str, type] = {
    "spmd": FrameworkEnv,
    "jax": JaxEnv,
    "pytorch": PyTorchEnv,
    "torch": PyTorchEnv,
    "tensorflow": TensorflowEnv,
    "tf": TensorflowEnv,
}


def framework_for(name: Optional[str]) -> FrameworkEnv:
    cls = FRAMEWORKS.get((name or "spmd").lower(), FrameworkEnv)
    return cls()
