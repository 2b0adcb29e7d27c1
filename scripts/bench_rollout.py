#!/usr/bin/env python
"""Live weight rollout bench (ISSUE 11 / ROADMAP item 2): fleet-wide
rollout latency and origin egress vs replica count and delta size, through
the REAL stack — a store-server subprocess, N replica subprocesses each
running a :class:`~kubetorch_tpu.serve.rollout.WeightRollout` against a
CPU-proxy :class:`~kubetorch_tpu.serve.rollout.HostEngine`, and the
trainer-side ``train.checkpoint.publish_rollout`` delta push.

Two topologies on the same push:

- **tree**  replicas fetch over the P2P broadcast tree (``/route`` with
  depth-aware, fanout-bounded parent assignment; completed fetchers serve
  ``/_kt/data/`` to later joiners) — origin egress should stay ~flat as
  the fleet grows (O(delta));
- **star**  the pre-tree baseline: every replica fetches the delta from
  the origin directly — egress grows O(replicas × delta).

The acceptance claims this bench owns: origin bytes ~flat vs replica
count under the tree where the star grows linearly, and **exactly zero
dropped requests** across a fleet-wide swap under open-loop load (every
``/generate`` fired during the rollout window must succeed — the swap
happens between decode batches, never under a request).

Run: ``make bench-rollout`` or
``python scripts/bench_rollout.py [--replicas 3,6,12] [--leaves 24]
[--leaf-kb 64] [--delta-frac 0.25] [--qps 40]``.
Prints a table plus a JSON blob (same convention as bench.py).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# CPU-only (see Makefile PY_CPU)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


# ---------------------------------------------------------------------------
# replica mode: one serving pod proxy (subprocess entry)
# ---------------------------------------------------------------------------


def run_replica(args) -> None:
    """One fleet member: HostEngine + WeightRollout poll loop + the pod
    surface the tree needs (``/_kt/data`` peer serving) and the bench
    reads (``/generate``, ``/rollout/status``, ``/metrics``)."""
    import asyncio

    import numpy as np
    from aiohttp import web

    from kubetorch_tpu import telemetry
    from kubetorch_tpu.data_store.peer_cache import cache_get
    from kubetorch_tpu.serve.rollout import (HostEngine, WeightRollout,
                                             local_status)

    elems = args.leaf_kb * 256
    params = {"layers": {f"l{i}": np.zeros(elems, np.float32)
                         for i in range(args.leaves)}}
    engine = HostEngine(params, step_s=args.step_ms / 1000.0).start()
    wr = WeightRollout(engine, args.service, store_url=args.store,
                       replica_id=args.replica_id, peer=bool(args.peer),
                       poll_s=0.1).start()

    async def health(request):
        return web.json_response({"status": "ok"})

    async def status(request):
        return web.json_response({"rollouts": local_status()})

    async def metrics(request):
        return web.Response(body=telemetry.REGISTRY.render().encode(),
                            content_type="text/plain")

    async def generate(request):
        body = await request.json()
        req = engine.submit(int(body.get("tokens", 4)))
        ok = await asyncio.get_event_loop().run_in_executor(
            None, req["done"].wait, 30.0)
        if not ok or req["error"] is not None:
            return web.json_response(
                {"error": str(req["error"] or "timeout")}, status=500)
        return web.json_response({"ok": True, "version": wr.version})

    async def serve_cached(request):
        key = request.match_info["key"]
        entry = await asyncio.get_event_loop().run_in_executor(
            None, cache_get, key)
        if entry is None:
            return web.json_response({"error": "not cached"}, status=404)
        data, meta = entry
        return web.Response(body=data,
                            content_type="application/octet-stream",
                            headers={"X-KT-Meta": json.dumps(meta)})

    # the chaos middleware a real pod server installs (KT_CHAOS): how the
    # drills SIGKILL this replica at its Nth broadcast transfer
    # (kill-peer@N) while it serves as an interior tree parent
    from kubetorch_tpu.chaos import maybe_chaos_middleware
    chaos_mw, _engine = maybe_chaos_middleware()
    app = web.Application(client_max_size=1 << 30,
                          middlewares=[chaos_mw] if chaos_mw else [])
    app.router.add_get("/health", health)
    app.router.add_get("/rollout/status", status)
    app.router.add_get("/metrics", metrics)
    app.router.add_post("/generate", generate)
    app.router.add_get("/_kt/data/{key:.+}", serve_cached)
    web.run_app(app, host="127.0.0.1", port=args.port,
                print=lambda *_: None)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def _spawn_store(root: str) -> "tuple":
    from kubetorch_tpu.utils.procs import free_port, wait_for_port

    port = free_port()
    env = dict(os.environ)
    env.update({"KT_STORE_FSYNC": "0", "KT_SCRUB_INTERVAL_S": "0"})
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.data_store.store_server",
         "--host", "127.0.0.1", "--port", str(port), "--root", root],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    assert wait_for_port("127.0.0.1", port, timeout=30), "store did not start"
    return proc, f"http://127.0.0.1:{port}"


def _spawn_replica(i: int, base_dir: str, store_url: str, service: str,
                   peer: bool, args) -> "tuple":
    from kubetorch_tpu.utils.procs import free_port

    port = free_port()
    cache = os.path.join(base_dir, f"cache-{i}")
    env = dict(os.environ)
    env.update({
        "POD_IP": "127.0.0.1",
        "KT_SERVER_PORT": str(port),
        "KT_DATA_CACHE_DIR": cache,
        "KT_PEER_WAIT_S": "30",
        "KT_STORE_FSYNC": "0",
    })
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--replica",
         "--port", str(port), "--service", service, "--store", store_url,
         "--peer", "1" if peer else "0", "--replica-id", f"replica-{i}",
         "--leaves", str(args.leaves), "--leaf-kb", str(args.leaf_kb),
         "--step-ms", str(args.step_ms)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    return proc, f"http://127.0.0.1:{port}"


def _wait_all_healthy(urls: List[str], timeout: float = 60.0) -> None:
    import requests

    deadline = time.monotonic() + timeout
    pending = list(urls)
    while pending and time.monotonic() < deadline:
        still = []
        for u in pending:
            try:
                if requests.get(f"{u}/health", timeout=2).status_code != 200:
                    still.append(u)
            except requests.RequestException:
                still.append(u)
        pending = still
        if pending:
            time.sleep(0.2)
    if pending:
        raise RuntimeError(f"replicas never became healthy: {pending}")


def _fleet_status(urls: List[str]) -> Dict[str, Dict]:
    import requests

    out = {}
    for u in urls:
        try:
            st = requests.get(f"{u}/rollout/status", timeout=5).json()
            out[u] = (st.get("rollouts") or [{}])[0]
        except requests.RequestException:
            out[u] = {}
    return out


def _wait_converged(urls: List[str], version: int, fingerprint: str,
                    timeout: float) -> float:
    """Seconds until EVERY replica reports (version, fingerprint); raises
    on timeout or a replica surfacing a rollout error."""
    t0 = time.monotonic()
    deadline = t0 + timeout
    while time.monotonic() < deadline:
        st = _fleet_status(urls)
        rows = list(st.values())
        if rows and all(r.get("version") == version
                        and r.get("fingerprint") == fingerprint
                        for r in rows):
            return time.monotonic() - t0
        errs = [r.get("last_error") for r in rows if r.get("last_error")]
        if errs:
            raise RuntimeError(f"rollout error on a replica: {errs[0]}")
        time.sleep(0.1)
    raise RuntimeError(
        f"fleet did not converge to v{version} within {timeout}s: "
        f"{[(r.get('version'), r.get('fingerprint')) for r in rows]}")


class _OpenLoopLoad:
    """Fixed-rate /generate traffic across the fleet while a swap is in
    flight; every failure is a dropped request (the acceptance number)."""

    def __init__(self, urls: List[str], qps: float, tokens: int = 4):
        self.urls = urls
        self.qps = qps
        self.tokens = tokens
        self.sent = 0
        self.dropped = 0
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._lock = threading.Lock()

    def _fire(self, url: str) -> None:
        import requests

        try:
            r = requests.post(f"{url}/generate",
                              json={"tokens": self.tokens}, timeout=30)
            ok = r.status_code == 200
        except requests.RequestException:
            ok = False
        with self._lock:
            self.sent += 1
            if not ok:
                self.dropped += 1

    def _run(self) -> None:
        i = 0
        interval = 1.0 / max(self.qps, 0.1)
        while not self._stop.is_set():
            url = self.urls[i % len(self.urls)]
            i += 1
            t = threading.Thread(target=self._fire, args=(url,), daemon=True)
            t.start()
            self._threads.append(t)
            self._stop.wait(interval)

    def start(self) -> "_OpenLoopLoad":
        self._pump = threading.Thread(target=self._run, daemon=True)
        self._pump.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._pump.join(timeout=5)
        for t in self._threads:
            t.join(timeout=30)


def _run_config(n: int, peer: bool, args) -> Dict:
    import numpy as np

    from kubetorch_tpu.train import checkpoint as ck
    from kubetorch_tpu.utils.procs import kill_process_tree

    rng = np.random.default_rng(0)
    elems = args.leaf_kb * 256
    service = f"bench-{n}-{'tree' if peer else 'star'}"
    topo = "tree" if peer else "star"
    procs = []
    with tempfile.TemporaryDirectory() as base:
        try:
            store_proc, store_url = _spawn_store(os.path.join(base, "store"))
            procs.append(store_proc)
            urls = []
            for i in range(n):
                p, u = _spawn_replica(i, base, store_url, service, peer,
                                      args)
                procs.append(p)
                urls.append(u)
            _wait_all_healthy(urls)

            # v1: full tree (every leaf is "the delta" — replicas start
            # from zeros)
            tree = {"layers": {f"l{i}": rng.standard_normal(elems).astype(
                np.float32) for i in range(args.leaves)}}
            out1 = ck.publish_rollout(service, tree, step=1,
                                      store_url=store_url)
            t_full = _wait_converged(urls, 1, out1["fingerprint"],
                                     timeout=args.timeout)
            st1 = _fleet_status(urls)
            b1 = {"origin": sum(r.get("bytes", {}).get("origin", 0)
                                for r in st1.values()),
                  "peer": sum(r.get("bytes", {}).get("peer", 0)
                              for r in st1.values())}

            # v2: a delta-frac push under open-loop load — the
            # zero-downtime claim
            n_delta = max(1, int(args.leaves * args.delta_frac))
            for i in range(n_delta):
                tree["layers"][f"l{i}"] = rng.standard_normal(elems).astype(
                    np.float32)
            load = _OpenLoopLoad(urls, qps=args.qps).start()
            try:
                out2 = ck.publish_rollout(service, tree, step=2,
                                          store_url=store_url)
                t_delta = _wait_converged(urls, 2, out2["fingerprint"],
                                          timeout=args.timeout)
                time.sleep(0.5)       # post-swap tail under load
            finally:
                load.stop()
            st2 = _fleet_status(urls)
            b2 = {"origin": sum(r.get("bytes", {}).get("origin", 0)
                                for r in st2.values()),
                  "peer": sum(r.get("bytes", {}).get("peer", 0)
                              for r in st2.values())}
            delta_bytes_pushed = out2["bytes"]
            return {
                "replicas": n,
                "topology": topo,
                "full": {"rollout_s": round(t_full, 3),
                         "origin_bytes": b1["origin"],
                         "peer_bytes": b1["peer"]},
                "delta": {"rollout_s": round(t_delta, 3),
                          "origin_bytes": b2["origin"] - b1["origin"],
                          "peer_bytes": b2["peer"] - b1["peer"],
                          "bytes_pushed": delta_bytes_pushed,
                          "leaves_changed": n_delta},
                "load": {"sent": load.sent, "dropped": load.dropped},
            }
        finally:
            for p in procs:
                kill_process_tree(p.pid)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--replicas", default="3,6,12",
                   help="comma-separated replica counts")
    p.add_argument("--leaves", type=int, default=24)
    p.add_argument("--leaf-kb", type=int, default=64)
    p.add_argument("--delta-frac", type=float, default=0.25)
    p.add_argument("--qps", type=float, default=40.0)
    p.add_argument("--step-ms", type=float, default=1.0)
    p.add_argument("--timeout", type=float, default=120.0)
    p.add_argument("--skip-star", action="store_true",
                   help="tree topology only (faster)")
    # internal: replica subprocess mode
    p.add_argument("--replica", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--service", default="", help=argparse.SUPPRESS)
    p.add_argument("--store", default="", help=argparse.SUPPRESS)
    p.add_argument("--peer", type=int, default=1, help=argparse.SUPPRESS)
    p.add_argument("--replica-id", default="", help=argparse.SUPPRESS)
    args = p.parse_args()

    if args.replica:
        run_replica(args)
        return 0

    counts = [int(x) for x in str(args.replicas).split(",") if x.strip()]
    results = []
    for n in counts:
        for peer in ([True] if args.skip_star else [True, False]):
            r = _run_config(n, peer, args)
            results.append(r)
            d = r["delta"]
            print(f"N={n:<3} {r['topology']:<5} "
                  f"full {r['full']['rollout_s']:6.2f}s  "
                  f"delta {d['rollout_s']:6.2f}s  "
                  f"origin {d['origin_bytes'] / 1e6:7.2f}MB  "
                  f"peer {d['peer_bytes'] / 1e6:7.2f}MB  "
                  f"dropped {r['load']['dropped']}/{r['load']['sent']}")

    tree = {r["replicas"]: r for r in results if r["topology"] == "tree"}
    star = {r["replicas"]: r for r in results if r["topology"] == "star"}
    acceptance: Dict[str, Optional[bool]] = {
        "zero_dropped": all(r["load"]["dropped"] == 0 for r in results),
    }
    if len(tree) >= 2:
        ns = sorted(tree)
        lo, hi = tree[ns[0]], tree[ns[-1]]
        growth = (hi["delta"]["origin_bytes"]
                  / max(lo["delta"]["origin_bytes"], 1))
        fleet_growth = ns[-1] / ns[0]
        # O(delta): origin egress must grow sublinearly in fleet size
        # (flat modulo the handful of fanout'd roots + fallbacks)
        acceptance["tree_origin_sublinear"] = growth < fleet_growth / 2
        acceptance["tree_origin_growth"] = round(growth, 2)
    if star and tree:
        common = sorted(set(tree) & set(star))
        if common:
            n = common[-1]
            acceptance["star_vs_tree_origin_ratio"] = round(
                star[n]["delta"]["origin_bytes"]
                / max(tree[n]["delta"]["origin_bytes"], 1), 2)
    out = {"bench": "rollout", "leaves": args.leaves,
           "leaf_kb": args.leaf_kb, "delta_frac": args.delta_frac,
           "qps": args.qps, "results": results, "acceptance": acceptance}
    print("\n" + json.dumps(out))
    return 0 if acceptance["zero_dropped"] else 1


if __name__ == "__main__":
    sys.exit(main())
