"""Multi-node store-ring harnesses for the replication/chaos suites.

Two flavors, same surface (``urls``, ``roots``, ``client_env()``):

- :class:`ThreadedStoreFleet` — N in-process store apps (one event loop
  thread each) with an explicitly injected ring view. Fast enough for
  tier-1: replication forwarding, proxy reads, epoch mismatch, TTL-based
  re-replication are all provable here. "Killing" a node closes its
  server (clients see connection-refused — indistinguishable from death
  on the wire), it just can't be SIGKILLed mid-write.
- :class:`SubprocessStoreFleet` — N real ``store_server`` subprocesses,
  SIGKILL-able at any byte (the chaos acceptance tests; pair with the
  ``kill-store-node[:SIG]@OP_INDEX`` chaos verb to die deterministically
  at the K-th client request). Ports are allocated up front so every
  member starts already knowing the full membership list.

Clients talk to a fleet by setting ``KT_STORE_NODES`` (see
``client_env()``); ``kubetorch_tpu.data_store.ring.ring_for`` picks the
fleet up from there. Call ``ring.reset_rings()`` between tests that
reuse URLs/ports.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
from typing import Dict, List, Optional

from kubetorch_tpu.utils.procs import (free_port, kill_process_tree,
                                       wait_for_port)

from .threaded_server import ThreadedAiohttpServer

DEFAULT_FLEET_ENV = {
    # CI fleets are throwaway: skip the fsync tax, keep the scrubber
    # manual (POST /scrub/run drives re-replication deterministically)
    "KT_STORE_FSYNC": "0",
    "KT_SCRUB_INTERVAL_S": "0",
}


def _alloc_ports(n: int) -> List[int]:
    ports: List[int] = []
    while len(ports) < n:
        p = free_port()
        if p not in ports:
            ports.append(p)
    return ports


class ThreadedStoreFleet:
    """``with ThreadedStoreFleet(tmp_path, n=3) as fleet:`` — N in-process
    ring members. ``fleet.stop_node(i)`` simulates node death (connection
    refused); ``fleet.post_ring(...)`` drives a membership change."""

    def __init__(self, base_dir, n: int = 3, replication: int = 2,
                 write_quorum: int = 2, node_ttl_s: float = 1.0,
                 epoch: int = 1):
        self.base_dir = base_dir
        self.n = n
        self.replication = replication
        self.write_quorum = write_quorum
        self.node_ttl_s = node_ttl_s
        self.epoch = epoch
        self.ports = _alloc_ports(n)
        self.urls = [f"http://127.0.0.1:{p}" for p in self.ports]
        self.roots = [os.path.join(str(base_dir), f"node{i}")
                      for i in range(n)]
        self.servers: List[Optional[ThreadedAiohttpServer]] = [None] * n

    def __enter__(self) -> "ThreadedStoreFleet":
        from kubetorch_tpu.data_store.store_server import (RingState,
                                                           create_store_app)

        for i in range(self.n):
            ring = RingState(self.urls[i], list(self.urls),
                             epoch=self.epoch,
                             replication=self.replication,
                             quorum=self.write_quorum,
                             ttl_s=self.node_ttl_s)
            factory = (lambda root=self.roots[i], r=ring:
                       create_store_app(root, ring=r))
            srv = ThreadedAiohttpServer(factory, port=self.ports[i])
            srv.__enter__()
            self.servers[i] = srv
        return self

    def __exit__(self, *exc) -> None:
        for i in range(self.n):
            self.stop_node(i)

    def stop_node(self, i: int) -> None:
        srv = self.servers[i]
        if srv is not None:
            self.servers[i] = None
            srv.__exit__()

    def client_env(self) -> Dict[str, str]:
        return {"KT_STORE_NODES": ",".join(self.urls),
                "KT_STORE_REPLICATION": str(self.replication),
                "KT_STORE_WRITE_QUORUM": str(self.write_quorum),
                "KT_STORE_NODE_TTL_S": str(self.node_ttl_s)}

    def post_ring(self, nodes: List[str], epoch: int) -> None:
        """Push a new membership view to every live member."""
        import requests

        for i, url in enumerate(self.urls):
            if self.servers[i] is None:
                continue
            requests.post(f"{url}/ring",
                          json={"nodes": nodes, "epoch": epoch}, timeout=10)


class SubprocessStoreFleet:
    """N real store-server processes forming one ring — the harness for
    SIGKILL chaos. ``chaos={i: spec}`` arms ``KT_CHAOS`` on node i only."""

    def __init__(self, base_dir, n: int = 3, replication: int = 2,
                 write_quorum: int = 2, node_ttl_s: float = 1.0,
                 chaos: Optional[Dict[int, str]] = None,
                 extra_env: Optional[Dict[str, str]] = None):
        self.base_dir = base_dir
        self.n = n
        self.replication = replication
        self.write_quorum = write_quorum
        self.node_ttl_s = node_ttl_s
        self.chaos = chaos or {}
        self.extra_env = extra_env or {}
        self.ports = _alloc_ports(n)
        self.urls = [f"http://127.0.0.1:{p}" for p in self.ports]
        self.roots = [os.path.join(str(base_dir), f"node{i}")
                      for i in range(n)]
        self.procs: List[Optional[subprocess.Popen]] = [None] * n

    def __enter__(self) -> "SubprocessStoreFleet":
        for i in range(self.n):
            self.start_node(i)
        return self

    def __exit__(self, *exc) -> None:
        for i, proc in enumerate(self.procs):
            if proc is not None and proc.poll() is None:
                kill_process_tree(proc.pid)
            self.procs[i] = None

    def start_node(self, i: int) -> None:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.update(DEFAULT_FLEET_ENV)
        env.update({
            "KT_STORE_NODES": ",".join(self.urls),
            "KT_STORE_SELF_URL": self.urls[i],
            "KT_STORE_REPLICATION": str(self.replication),
            "KT_STORE_WRITE_QUORUM": str(self.write_quorum),
            "KT_STORE_NODE_TTL_S": str(self.node_ttl_s),
        })
        env.pop("KT_CHAOS", None)
        if i in self.chaos:
            env["KT_CHAOS"] = self.chaos[i]
            env.setdefault("KT_CHAOS_SEED", "1234")
        env.update(self.extra_env)
        self.procs[i] = subprocess.Popen(
            [sys.executable, "-m", "kubetorch_tpu.data_store.store_server",
             "--host", "127.0.0.1", "--port", str(self.ports[i]),
             "--root", self.roots[i]],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        assert wait_for_port("127.0.0.1", self.ports[i], timeout=30), \
            f"store node {i} did not start"

    def kill_node(self, i: int, sig: int = signal.SIGKILL) -> None:
        proc = self.procs[i]
        if proc is not None and proc.poll() is None:
            proc.send_signal(sig)
            proc.wait(timeout=30)

    def wait_node_dead(self, i: int, timeout: float = 60.0) -> bool:
        proc = self.procs[i]
        if proc is None:
            return True
        try:
            proc.wait(timeout=timeout)
            return True
        except subprocess.TimeoutExpired:
            return False

    def client_env(self) -> Dict[str, str]:
        return {"KT_STORE_NODES": ",".join(self.urls),
                "KT_STORE_REPLICATION": str(self.replication),
                "KT_STORE_WRITE_QUORUM": str(self.write_quorum),
                "KT_STORE_NODE_TTL_S": str(self.node_ttl_s)}
