#!/usr/bin/env python
"""Serving front-door bench: open-loop traffic through the REAL router
(ISSUE 9 / ROADMAP item 3 — the first traffic-shaped benchmark, without
which "millions of users" is unfalsifiable).

Drives ``serving.router.Router`` — the actual production selection,
admission, affinity, and shedding code — over an in-process simulated
replica fleet, with BOTH policies on the SAME seeded arrival schedule:

- **rr**        the pre-ISSUE-9 baseline: blind round-robin, no admission
  control, no affinity (requests queue unboundedly at replica slots);
- **affinity**  the front door: continuous batching (slot-packed),
  session→replica affinity with consistent-hash cold placement, bounded
  admission queue, deadline/queue shedding.

Each simulated replica models what the engine bench already measures
per-pod: a slot-limited decode batch, prefill cost ∝ *uncached* prompt
tokens (an LRU per-replica prefix cache — ``serve/sessions.py``'s
residency), decode cost ∝ generated tokens. The numbers this bench owns
are the FLEET-path ones: TTFT p50/p99 under load, shed rate, affinity
hit rate, aggregate tokens/s. Device-side truths (per-token ms) are
inputs, not outputs — measured by bench.py / the TPU sweeps.

Defaults: 1200 open-loop sessions × 3 turns (3600 requests), 8 replicas
× 8 slots, with a mid-run arrival burst that exceeds fleet capacity so
admission control has something to prove. Run: ``make bench-serve`` or
``python scripts/bench_serve.py [--sessions 1200] [--replicas 8] ...``.
Prints a table plus a JSON blob (same convention as bench.py).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import sys
import time
from collections import OrderedDict
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# CPU-only (see Makefile PY_CPU)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from kubetorch_tpu import telemetry  # noqa: E402
from kubetorch_tpu.constants import SESSION_HEADER  # noqa: E402
from kubetorch_tpu.exceptions import (AdmissionShedError,  # noqa: E402
                                      DeadlineExceededError)
from kubetorch_tpu.resilience import DEADLINE_HEADER  # noqa: E402
from kubetorch_tpu.serving.router import Router  # noqa: E402


class SimReplica:
    """One serving pod: a slot-limited continuous-batching engine with an
    LRU prefix cache. Implements the transport surface the router
    dispatches through (``check_health`` / ``call_worker`` via
    :class:`SimPool`)."""

    def __init__(self, ip: str, slots: int, prefill_s_per_tok: float,
                 decode_s_per_tok: float, resident_cap: int = 256):
        self.ip = ip
        self.slots = slots
        self.prefill_s_per_tok = prefill_s_per_tok
        self.decode_s_per_tok = decode_s_per_tok
        self._slots = asyncio.Semaphore(slots)
        self.resident: "OrderedDict[str, int]" = OrderedDict()
        self.resident_cap = resident_cap
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.tokens = 0

    async def serve(self, session: Optional[str], prompt_len: int,
                    new_tokens: int) -> Dict[str, float]:
        async with self._slots:
            cached = self.resident.get(session, 0) if session else 0
            if cached:
                self.resident.move_to_end(session)
                self.prefix_hits += 1
            elif session:
                self.prefix_misses += 1
            suffix = max(prompt_len - cached, 1)
            await asyncio.sleep(suffix * self.prefill_s_per_tok
                                + self.decode_s_per_tok)
            ttft_at = time.monotonic()    # first token leaves the slot here
            await asyncio.sleep((new_tokens - 1) * self.decode_s_per_tok)
            if session:
                self.resident.pop(session, None)
                self.resident[session] = prompt_len
                while len(self.resident) > self.resident_cap:
                    self.resident.popitem(last=False)
            self.tokens += new_tokens
            return {"ttft_at": ttft_at, "tokens": new_tokens}


class SimPool:
    """The ``RemoteWorkerPool`` surface over the simulated fleet."""

    def __init__(self, replicas: Dict[str, SimReplica]):
        self.replicas = replicas
        self.health_probes = 0

    async def check_health(self, ip: str, timeout: float = 2.0) -> bool:
        self.health_probes += 1
        return ip in self.replicas

    async def call_worker(self, ip, fn_name, method, body, headers,
                          timeout=None, subtree=None, sel_ips=None):
        kw = body["kwargs"]
        return await self.replicas[ip].serve(
            headers.get(SESSION_HEADER), kw["prompt_len"], kw["new_tokens"])


def _percentile(values: List[float], q: float) -> float:
    if not values:
        return float("nan")
    vs = sorted(values)
    idx = min(int(q * (len(vs) - 1) + 0.5), len(vs) - 1)
    return vs[idx]


def _schedule(args) -> List[Dict]:
    """The seeded open-loop arrival plan, shared verbatim by both policy
    runs: every session's turn arrivals are fixed timestamps — completions
    never gate arrivals (open loop). A burst cohort's first turns land
    inside a short window to push offered load past fleet capacity."""
    rng = random.Random(args.seed)
    plan = []
    burst = int(args.sessions * args.burst_frac)
    for s in range(args.sessions):
        sid = f"sess-{s:05d}"
        if s < burst:
            t0 = args.burst_at + rng.random() * args.burst_window
        else:
            t0 = rng.random() * args.spread_s
        for turn in range(args.turns):
            # think-time variance decorrelates a cohort's follow-up turns
            # (real users don't reply in lockstep; without this the burst
            # cohort re-arrives as one wave every turn)
            plan.append({
                "session": sid,
                "at": t0 + turn * args.turn_gap_s * (0.7 + 0.6
                                                     * rng.random()),
                "prompt_len": args.header_tokens
                + (turn + 1) * args.turn_tokens,
                "new_tokens": args.new_tokens,
            })
    plan.sort(key=lambda r: r["at"])
    return plan


async def _run_policy(policy: str, plan: List[Dict], args,
                      on_complete=None) -> Dict:
    ips = [f"10.0.0.{i + 1}" for i in range(args.replicas)]
    fleet = {ip: SimReplica(ip, args.slots,
                            args.prefill_us_per_tok / 1e6,
                            args.decode_us_per_tok / 1e6,
                            resident_cap=args.resident_cap)
             for ip in ips}
    pool = SimPool(fleet)
    router = Router(fn_name="generate", slots_per_replica=args.slots,
                    queue_max=args.queue_max, health_ttl_s=5.0)
    rr_state = {"i": 0}
    ttfts: List[float] = []
    shed: Dict[str, int] = {}
    errors = 0

    async def local_call(method, a, kw, timeout):
        raise RuntimeError("bench client is not a replica")

    async def one(req: Dict, t_bench0: float) -> None:
        nonlocal errors
        arrival = t_bench0 + req["at"]
        delay = arrival - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        headers = {SESSION_HEADER: req["session"]}
        if args.deadline_s > 0:
            headers[DEADLINE_HEADER] = f"{time.time() + args.deadline_s:.6f}"
        kwargs = {"prompt_len": req["prompt_len"],
                  "new_tokens": req["new_tokens"]}
        try:
            if policy == "affinity":
                out = await router.dispatch(
                    pool=pool, ips=ips, my_ip="bench-client", method=None,
                    args=[], kwargs=kwargs, headers=headers, timeout=None,
                    local_call=local_call)
            else:
                # the pre-front-door baseline: rotate, no admission control
                ip = ips[rr_state["i"] % len(ips)]
                rr_state["i"] += 1
                out = await pool.call_worker(
                    ip, "generate", None, {"args": [], "kwargs": kwargs},
                    headers)
            ttfts.append(out["ttft_at"] - arrival)
            if on_complete is not None:
                # the flywheel tap (--flywheel): finished-request feedback
                # leaves the serving loop here, exactly where a real
                # engine's feedback_sink fires on slot retirement
                on_complete(req, out["ttft_at"] - arrival)
        except (AdmissionShedError, DeadlineExceededError) as e:
            reason = getattr(e, "reason", None) or "deadline_expired"
            shed[reason] = shed.get(reason, 0) + 1
        except Exception:  # noqa: BLE001 — count, don't kill the bench
            errors += 1

    t0 = time.monotonic()
    await asyncio.gather(*(one(r, t0) for r in plan))
    wall = time.monotonic() - t0
    hits = sum(r.prefix_hits for r in fleet.values())
    misses = sum(r.prefix_misses for r in fleet.values())
    total_tokens = sum(r.tokens for r in fleet.values())
    n_shed = sum(shed.values())
    return {
        "policy": policy,
        "requests": len(plan),
        "completed": len(ttfts),
        "shed": n_shed,
        "shed_by_reason": shed,
        "shed_rate": round(n_shed / len(plan), 4),
        "errors": errors,
        "prefix_hit_rate": round(hits / (hits + misses), 4)
        if hits + misses else 0.0,
        "ttft_p50_ms": round(_percentile(ttfts, 0.50) * 1000, 1),
        "ttft_p99_ms": round(_percentile(ttfts, 0.99) * 1000, 1),
        "tokens_per_s": round(total_tokens / wall, 1),
        "wall_s": round(wall, 2),
        "health_probes": pool.health_probes,
        "router": router.state_dict() if policy == "affinity" else None,
    }


# ---------------------------------------------------------------------------
# --regions: cross-region failover + spillover TTFT (ISSUE 13)
# ---------------------------------------------------------------------------


def _spawn_region(region: str, port: int, args) -> "subprocess.Popen":
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["KT_REGION"] = region
    env.pop("KT_CHAOS", None)
    return subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.federation.sim_region",
         "--port", str(port), "--region", region,
         "--replicas", str(args.replicas), "--slots", str(args.slots),
         "--prefill-us-per-tok", str(args.prefill_us_per_tok),
         "--decode-us-per-tok", str(args.decode_us_per_tok),
         "--queue-max", str(args.queue_max)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


async def _run_regions(plan: List[Dict], args) -> Dict:
    """Open-loop traffic through the REAL GeoFrontDoor over N subprocess
    CPU-proxy regions; region 0 (the client's local region) is SIGKILLed
    mid-run. Measures failover time (last pre-kill success in the dead
    region → first spilled success in a survivor), spillover TTFT, and
    the typed-vs-raw shed split (raw must be 0)."""
    import signal as signal_mod
    import subprocess  # noqa: F401  (type for _spawn_region)

    from kubetorch_tpu.federation import (GeoFrontDoor, HttpRegionTarget,
                                          RegionBook)
    from kubetorch_tpu.utils.procs import free_port, wait_for_port

    names = [f"region-{i}" for i in range(args.regions)]
    ports = [free_port() for _ in names]
    procs = {n: _spawn_region(n, p, args) for n, p in zip(names, ports)}
    for n, p in zip(names, ports):
        assert wait_for_port("127.0.0.1", p, timeout=30), f"{n} not up"
    door = GeoFrontDoor(
        [HttpRegionTarget(n, f"http://127.0.0.1:{p}")
         for n, p in zip(names, ports)],
        local_region=names[0],
        book=RegionBook(names, ttl_s=max(args.kill_at, 1.0)))

    ttft_pre: List[float] = []
    ttft_post: List[float] = []      # spillover: successes after the kill
    shed: Dict[str, int] = {}
    raw_errors = 0
    by_region: Dict[str, int] = {}
    marks = {"killed_at": None, "last_dead_ok": None, "first_spill_ok": None}

    async def one(req: Dict, t0: float) -> None:
        nonlocal raw_errors
        arrival = t0 + req["at"]
        delay = arrival - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        headers = {SESSION_HEADER: req["session"]}
        if args.deadline_s > 0:
            headers[DEADLINE_HEADER] = f"{time.time() + args.deadline_s:.6f}"
        try:
            out = await door.dispatch(
                {"prompt_len": req["prompt_len"],
                 "new_tokens": req["new_tokens"]}, headers)
        except (AdmissionShedError, DeadlineExceededError) as e:
            reason = getattr(e, "reason", None) or "deadline_expired"
            shed[reason] = shed.get(reason, 0) + 1
            return
        except Exception:  # noqa: BLE001 — the forbidden bucket
            raw_errors += 1
            return
        now = time.monotonic()
        region = out.get("region")
        by_region[region] = by_region.get(region, 0) + 1
        # client-observed TTFT: wall latency minus the decode tail the
        # region reports (service_s - ttft_s)
        ttft = (now - arrival) - (out["service_s"] - out["ttft_s"])
        if marks["killed_at"] is None:
            if region == names[0]:
                marks["last_dead_ok"] = now
            ttft_pre.append(ttft)
        else:
            if region != names[0] and marks["first_spill_ok"] is None:
                marks["first_spill_ok"] = now
            ttft_post.append(ttft)

    async def killer(t0: float) -> None:
        await asyncio.sleep(args.kill_at)
        marks["killed_at"] = time.monotonic()
        procs[names[0]].send_signal(signal_mod.SIGKILL)

    t0 = time.monotonic()
    try:
        await asyncio.gather(killer(t0), *(one(r, t0) for r in plan))
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
    wall = time.monotonic() - t0
    failover_s = None
    if marks["first_spill_ok"] is not None:
        anchor = marks["last_dead_ok"] or marks["killed_at"]
        failover_s = marks["first_spill_ok"] - max(anchor,
                                                   marks["killed_at"])
    n_shed = sum(shed.values())
    return {
        "regions": args.regions,
        "requests": len(plan),
        "completed": len(ttft_pre) + len(ttft_post),
        "by_region": by_region,
        "shed_by_reason": shed,
        "shed": n_shed,
        "raw_errors": raw_errors,
        "failover_s": round(failover_s, 3) if failover_s is not None
        else None,
        "ttft_pre_kill_p50_ms": round(_percentile(ttft_pre, 0.5) * 1000, 1),
        "ttft_spill_p50_ms": round(_percentile(ttft_post, 0.5) * 1000, 1),
        "ttft_spill_p99_ms": round(_percentile(ttft_post, 0.99) * 1000, 1),
        "wall_s": round(wall, 2),
        "door": door.state_dict(),
    }


def _regions_main(args) -> int:
    plan = _schedule(args)
    print(f"federation failover bench: {args.regions} subprocess regions x "
          f"{args.replicas} replicas x {args.slots} slots, "
          f"{len(plan)} open-loop requests, kill-region @ t="
          f"{args.kill_at}s (SIGKILL {'region-0'})")
    out = asyncio.run(_run_regions(plan, args))
    print(f"\ncompleted {out['completed']}/{out['requests']} "
          f"(by region: {out['by_region']}); typed shed {out['shed']} "
          f"({', '.join(f'{k}={v}' for k, v in sorted(out['shed_by_reason'].items())) or 'none'}); "
          f"raw errors reaching the client: {out['raw_errors']}")
    print(f"failover: {out['failover_s']}s from region death to the first "
          f"spilled success; spillover ttft p50 {out['ttft_spill_p50_ms']}ms "
          f"p99 {out['ttft_spill_p99_ms']}ms "
          f"(pre-kill p50 {out['ttft_pre_kill_p50_ms']}ms)")
    if out["raw_errors"]:
        print("FAIL: raw connection errors reached the client — the geo "
              "front door must shed typed only")
    blob = {"metric": "fed_failover_s", "value": out["failover_s"],
            "unit": "s", "detail": out}
    print("\n" + json.dumps(blob))
    return 1 if out["raw_errors"] else 0


# ---------------------------------------------------------------------------
# --scale-out: fleet cold-start burn-down (ISSUE 16)
# ---------------------------------------------------------------------------
#
# Two claims, one run:
#
# A/B  0→N replicas COLD (fresh interpreters, empty AOT cache: pay
#      import + weight pickle + XLA compile serially, the pre-ISSUE-16
#      baseline) vs WARM (pre-warmed template fork + shm weight attach +
#      persistent AOT executable cache). Reports per-arm p50/p99
#      time-to-first-token-served plus the per-phase anatomy
#      (import / weight_fetch|attach / compile_or_cache / first_token).
#
# egress  0→J joiners pull the SAME weights from one store through the
#      /route broadcast tree (content-aliased subkeys): origin egress
#      must stay ~1× the weight bytes however many replicas join —
#      joiner subprocesses serve /_kt/data to each other exactly like
#      pods do.


def run_joiner(args) -> None:
    """One joining replica (subprocess): serve the pod peer surface,
    pull the weights key over the broadcast tree, report bytes by
    source, keep serving so later joiners can fan out from us."""
    import threading

    from aiohttp import web

    from kubetorch_tpu.data_store import commands as dsc
    from kubetorch_tpu.data_store import netpool
    from kubetorch_tpu.data_store.peer_cache import cache_get

    def do_fetch() -> None:
        t0 = time.monotonic()
        out: Dict = {"idx": args.replica_id, "ok": False}
        try:
            fetcher = dsc._RoutedFetcher(args.store, args.key, True,
                                         content_alias=True)
            r = fetcher.fetch(f"{args.key}{dsc._INDEX_SUFFIX}", timeout=120,
                              expect_hash=args.index_hash or None)
            assert r.status_code == 200, f"index fetch {r.status_code}"
            index = json.loads(r.content)

            def one(item):
                path, meta = item
                rr = fetcher.fetch(f"{args.key}/{path}",
                                   expect_hash=meta.get("blake2b"))
                assert rr.status_code == 200, f"leaf {path} {rr.status_code}"
                return len(rr.content)

            nbytes = sum(netpool.map_concurrent(
                one, index["leaves"].items()))
            fetcher.complete()
            out.update(ok=True, seconds=round(time.monotonic() - t0, 3),
                       leaves=len(index["leaves"]), bytes=nbytes,
                       bytes_by_source=dict(fetcher.bytes_by_source))
        except BaseException as e:  # noqa: BLE001 — report, don't vanish
            out["error"] = f"{type(e).__name__}: {e}"
        tmp = f"{args.result}.tmp"
        with open(tmp, "w") as f:
            json.dump(out, f)
        os.replace(tmp, args.result)

    async def serve_cached(request):
        entry = await asyncio.get_event_loop().run_in_executor(
            None, cache_get, request.match_info["key"])
        if entry is None:
            return web.json_response({"error": "not cached"}, status=404)
        data, meta = entry
        return web.Response(body=data,
                            content_type="application/octet-stream",
                            headers={"X-KT-Meta": json.dumps(meta)})

    async def health(request):
        return web.json_response({"status": "ok"})

    async def on_startup(app):
        threading.Thread(target=do_fetch, daemon=True).start()

    app = web.Application(client_max_size=1 << 30)
    app.router.add_get("/health", health)
    app.router.add_get("/_kt/data/{key:.+}", serve_cached)
    app.on_startup.append(on_startup)
    web.run_app(app, host="127.0.0.1", port=args.port,
                print=lambda *_: None)


def _spawn_store(root: str) -> tuple:
    import subprocess

    from kubetorch_tpu.utils.procs import free_port, wait_for_port

    port = free_port()
    env = dict(os.environ)
    env.update({"KT_STORE_FSYNC": "0", "KT_SCRUB_INTERVAL_S": "0"})
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.data_store.store_server",
         "--host", "127.0.0.1", "--port", str(port), "--root", root],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    assert wait_for_port("127.0.0.1", port, timeout=30), "store not up"
    return proc, f"http://127.0.0.1:{port}"


def _phase_means(rows: List[Dict]) -> Dict[str, float]:
    sums: Dict[str, float] = {}
    for r in rows:
        for k, v in (r.get("phases") or {}).items():
            sums[k] = sums.get(k, 0.0) + v
    return {k: round(v / max(len(rows), 1), 3)
            for k, v in sorted(sums.items())}


def _collect_results(result_dir: str, names: List[str],
                     timeout: float) -> List[Dict]:
    deadline = time.monotonic() + timeout
    rows: List[Dict] = []
    pending = list(names)
    while pending and time.monotonic() < deadline:
        still = []
        for n in pending:
            path = os.path.join(result_dir, n)
            if os.path.exists(path):
                with open(path) as f:
                    rows.append(json.load(f))
            else:
                still.append(n)
        pending = still
        if pending:
            time.sleep(0.25)
    if pending:
        raise RuntimeError(f"replicas never reported: {pending}")
    return rows


def _make_weights(weights_path: str):
    """Driver-side model init: the tiny bench model, saved numpy-only so
    cold boots / the template load it without this process's jax state."""
    import jax

    from kubetorch_tpu.models.llama import LlamaConfig, llama_init
    from kubetorch_tpu.serving.warm_template import save_weights

    import jax.numpy as jnp
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attn_impl="xla", remat=False)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    save_weights(weights_path, params)
    import numpy as np
    params_np = jax.tree_util.tree_map(np.asarray, params)
    return params_np


def _cold_env() -> Dict[str, str]:
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu"})
    env.pop("KT_CHAOS", None)
    return env


def _run_cold_arm(spec: Dict, base: str, n: int, tag: str,
                  timeout: float) -> List[Dict]:
    """N fresh interpreters booting concurrently — the 0→N cold burst."""
    import subprocess

    spec_file = os.path.join(base, f"spec_{tag}.json")
    with open(spec_file, "w") as f:
        json.dump(spec, f)
    procs = [subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.serving.warm_template",
         "--cold", spec_file, str(i), str(time.time())],
        env=_cold_env(), stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL) for i in range(n)]
    try:
        return _collect_results(spec["result_dir"],
                                [f"cold_{i}.json" for i in range(n)],
                                timeout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def _run_warm_arm(spec: Dict, n: int, timeout: float) -> tuple:
    """Template fork burst: one pre-warmed template, N forked replicas
    attaching weights over shm and compiling through the seeded AOT
    cache."""
    from kubetorch_tpu.serving.warm_template import TemplateSupervisor

    t0 = time.monotonic()
    with TemplateSupervisor(spec) as sup:
        template_ready_s = time.monotonic() - t0
        for i in range(n):
            sup.fork(i)
        rows = _collect_results(spec["result_dir"],
                                [f"replica_{i}.json" for i in range(n)],
                                timeout)
    return rows, template_ready_s


def _scaleout_egress(params_np, args) -> Dict:
    """0→J joiners over the broadcast tree: origin egress vs weight
    bytes."""
    import subprocess
    import tempfile

    from kubetorch_tpu.data_store import commands as dsc
    from kubetorch_tpu.utils.procs import free_port, kill_process_tree

    key = "serve/scaleout/weights"
    procs = []
    with tempfile.TemporaryDirectory(prefix="kt-scaleout-") as base:
        try:
            store_proc, store_url = _spawn_store(os.path.join(base, "store"))
            procs.append(store_proc)
            pushed = dsc.put(key, params_np, store_url=store_url)
            weight_bytes = pushed["bytes"]
            results = []
            for i in range(args.joiners):
                port = free_port()
                result = os.path.join(base, f"join_{i}.json")
                results.append(result)
                env = _cold_env()
                env.update({
                    "POD_IP": "127.0.0.1",
                    "KT_SERVER_PORT": str(port),
                    "KT_DATA_CACHE_DIR": os.path.join(base, f"cache-{i}"),
                    "KT_PEER_WAIT_S": "60",
                })
                procs.append(subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__), "--joiner",
                     "--port", str(port), "--store", store_url,
                     "--key", key,
                     "--index-hash", pushed.get("index_blake2b") or "",
                     "--replica-id", str(i), "--result", result],
                    env=env, stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL))
            deadline = time.monotonic() + args.timeout
            rows: List[Dict] = []
            pending = list(results)
            while pending and time.monotonic() < deadline:
                still = []
                for path in pending:
                    if os.path.exists(path):
                        with open(path) as f:
                            rows.append(json.load(f))
                    else:
                        still.append(path)
                pending = still
                if pending:
                    time.sleep(0.25)
            if pending:
                raise RuntimeError(
                    f"joiners never finished: {len(pending)}/{args.joiners}")
            bad = [r for r in rows if not r.get("ok")]
            if bad:
                raise RuntimeError(f"joiner failed: {bad[0].get('error')}")
            by_source: Dict[str, int] = {}
            for r in rows:
                for src, b in (r.get("bytes_by_source") or {}).items():
                    by_source[src] = by_source.get(src, 0) + b
            origin = by_source.get("store", 0)
            return {
                "joiners": args.joiners,
                "weight_bytes": weight_bytes,
                "bytes_by_source": by_source,
                "origin_egress_x": round(origin / max(weight_bytes, 1), 2),
                "join_p50_s": round(_percentile(
                    [r["seconds"] for r in rows], 0.5), 2),
                "join_p99_s": round(_percentile(
                    [r["seconds"] for r in rows], 0.99), 2),
            }
        finally:
            for p in procs:
                kill_process_tree(p.pid)


def _scaleout_main(args) -> int:
    import tempfile

    print(f"fleet cold-start bench: 0->{args.n} replicas, cold "
          f"(fresh interpreter + empty AOT cache) vs warm (template fork "
          f"+ shm weights + AOT cache); egress: 0->{args.joiners} joiners "
          f"over the broadcast tree")
    with tempfile.TemporaryDirectory(prefix="kt-coldstart-") as base:
        weights = os.path.join(base, "weights.npy")
        params_np = _make_weights(weights)
        spec_base = {
            "weights": weights,
            "model": {"kind": "llama-tiny"},
            "engine": {"slots": 2, "max_len": 64,
                       "prefill_buckets": [8, 16, 32]},
            "probe_prompt": [1, 2, 3],
            "probe_tokens": 2,
            "chaos": "",
        }

        # arm 1: cold — every replica pays import + pickle + compile
        cold_spec = dict(spec_base,
                         result_dir=os.path.join(base, "cold"),
                         aot_root=os.path.join(base, "aot-cold"))
        cold = _run_cold_arm(cold_spec, base, args.n, "cold", args.timeout)

        # seed the persistent AOT cache once (the first-ever boot of this
        # model/mesh/bucket key — every later boot, pod, and fork hits it)
        warm_aot = os.path.join(base, "aot-warm")
        seed_spec = dict(spec_base,
                         result_dir=os.path.join(base, "seed"),
                         aot_root=warm_aot)
        t0 = time.monotonic()
        _run_cold_arm(seed_spec, base, 1, "seed", args.timeout)
        seed_s = time.monotonic() - t0

        # arm 2: warm — template fork + shm attach + AOT cache hits
        warm_spec = dict(spec_base,
                         result_dir=os.path.join(base, "warm"),
                         aot_root=warm_aot)
        warm, template_ready_s = _run_warm_arm(warm_spec, args.n,
                                               args.timeout)

        egress = (None if args.skip_egress
                  else _scaleout_egress(params_np, args))

    cold_t = [r["total_s"] for r in cold]
    warm_t = [r["total_s"] for r in warm]
    arms = {
        "cold": {"n": args.n,
                 "p50_s": round(_percentile(cold_t, 0.5), 2),
                 "p99_s": round(_percentile(cold_t, 0.99), 2),
                 "phases_mean_s": _phase_means(cold)},
        "warm": {"n": args.n,
                 "p50_s": round(_percentile(warm_t, 0.5), 2),
                 "p99_s": round(_percentile(warm_t, 0.99), 2),
                 "phases_mean_s": _phase_means(warm),
                 "aot": (warm[0].get("aot") or {}),
                 "template_ready_s": round(template_ready_s, 2),
                 "aot_seed_s": round(seed_s, 2)},
    }
    speedup = (arms["cold"]["p50_s"] / arms["warm"]["p50_s"]
               if arms["warm"]["p50_s"] else float("inf"))

    print(f"\n{'arm':<6} {'p50':>8} {'p99':>8}   phase anatomy (mean s)")
    for name in ("cold", "warm"):
        a = arms[name]
        anatomy = " ".join(f"{k}={v}" for k, v in a["phases_mean_s"].items())
        print(f"{name:<6} {a['p50_s']:>7.2f}s {a['p99_s']:>7.2f}s   "
              f"{anatomy}")
    print(f"\nwarm vs cold: p50 {speedup:.1f}x faster "
          f"(template ready in {arms['warm']['template_ready_s']}s, "
          f"one-time AOT seed {arms['warm']['aot_seed_s']}s, "
          f"fork-side AOT counts {arms['warm']['aot']})")
    acceptance = {"warm_speedup_x": round(speedup, 1),
                  "warm_speedup_ge_5x": speedup >= 5.0}
    if egress is not None:
        print(f"egress: {egress['joiners']} joiners pulled "
              f"{egress['weight_bytes'] / 1e6:.1f}MB weights with "
              f"{egress['origin_egress_x']}x origin egress "
              f"(by source: {egress['bytes_by_source']}; join p50 "
              f"{egress['join_p50_s']}s p99 {egress['join_p99_s']}s)")
        acceptance["origin_egress_x"] = egress["origin_egress_x"]
        acceptance["origin_egress_le_2x"] = egress["origin_egress_x"] <= 2.0
    out = {"metric": "cold_start_speedup_x", "value": round(speedup, 1),
           "unit": "x",
           "detail": {"arms": arms, "egress": egress,
                      "acceptance": acceptance}}
    print("\n" + json.dumps(out))
    return 0 if all(v for k, v in acceptance.items()
                    if isinstance(v, bool)) else 1


# ---------------------------------------------------------------------------
# --flywheel: feedback-to-weights-live + harvest/vacate impact (ISSUE 19)
# ---------------------------------------------------------------------------


def _flywheel_main(args) -> int:
    """Close the loop under load: the SAME open-loop arrival plan runs
    twice through the real router — once bare (baseline), once with the
    whole flywheel live against a real store subprocess (feedback sink →
    durable ledger → harvest trainer on a background thread → gated
    promotion). Reports:

    - **feedback-to-weights-live p50/p99** — ack of a feedback record to
      the PROMOTED manifest that contains its fold;
    - **serving impact** — TTFT p99 / shed-rate delta vs the bare arm
      (the harvester is supposed to be invisible: it trains in the
      trough and vacates when the burst eats the SLO headroom);
    - **vacate-inside-grace** — every vacate's flush must land inside
      the drain grace window; exit-coded, like the scale-out bench.
    """
    import collections
    import queue as _q
    import statistics
    import tempfile
    import threading

    import numpy as np

    from kubetorch_tpu.flywheel.harvester import Harvester, HarvestPolicy
    from kubetorch_tpu.flywheel.ledger import FeedbackLedger, LedgerCursor
    from kubetorch_tpu.flywheel.promoter import Promoter
    from kubetorch_tpu.train.checkpoint import Checkpointer
    from kubetorch_tpu.utils.procs import kill_process_tree

    service, replica = "bench-fly", "bench"
    plan = _schedule(args)
    print(f"flywheel bench: {len(plan)} requests open-loop, "
          f"{args.replicas} replicas x {args.slots} slots, burst "
          f"{args.burst_frac:.0%} @ t={args.burst_at}s; harvest SLO "
          f"{args.fly_slo_ms:.0f}ms, drain grace {args.fly_grace_s:.1f}s")

    baseline = asyncio.run(_run_policy("affinity", plan, args))

    with tempfile.TemporaryDirectory() as root:
        store_proc, url = _spawn_store(root)
        try:
            ledger = FeedbackLedger(service, replica, store_url=url)
            fb_q: "_q.Queue" = _q.Queue()
            ack_times: Dict[str, float] = {}
            recent = collections.deque(maxlen=32)
            serve_done = threading.Event()

            def sink_loop() -> None:
                # the durable half of the feedback sink: batch-drain the
                # queue so one quorum append acks many requests
                while True:
                    item = fb_q.get()
                    stop = item is None
                    batch = [] if stop else [item]
                    while True:
                        try:
                            nxt = fb_q.get_nowait()
                        except _q.Empty:
                            break
                        if nxt is None:
                            stop = True
                        else:
                            batch.append(nxt)
                    if batch:
                        hashes = ledger.append(batch)
                        now = time.monotonic()
                        for h in hashes:
                            ack_times.setdefault(h, now)
                    if stop:
                        return

            n_fb = {"i": 0}

            def on_complete(req: Dict, ttft_s: float) -> None:
                recent.append(ttft_s * 1000.0)
                n_fb["i"] += 1
                fb_q.put({"i": n_fb["i"], "session": req["session"],
                          "prompt_len": req["prompt_len"],
                          "new_tokens": req["new_tokens"],
                          "ttft_ms": round(ttft_s * 1000.0, 3)})

            def scrape() -> float:
                vals = list(recent)
                return statistics.median(vals) if vals else 0.0

            cursor = LedgerCursor(service, [replica], store_url=url)
            cursor.acquire()
            ckpt = Checkpointer(f"bench/{service}/ckpt", store_url=url,
                                every=1)
            state = {"w": np.zeros(64, dtype=np.float32)}
            fold = {"step": 0, "pending": []}

            def train_step():
                batch = cursor.poll(max_records=64)
                if not batch:
                    return None
                fold["step"] += 1
                w = state["w"] * np.float32(0.99)
                for rec in batch:
                    h = rec.get("hash") or ""
                    w = w + np.float32(int(h[:8] or "0", 16)
                                       / float(1 << 33))
                state["w"] = w
                cursor.commit_state(fold["step"])
                ckpt.save(state, fold["step"])
                fold["pending"].extend(r.get("hash") for r in batch)
                return fold["step"]

            class _Router:
                def set_canary(self, r, fraction=0.1):
                    pass

                def clear_canary(self):
                    pass

                def canary_verdict(self, **kw):
                    return "ok"

            promoter = Promoter(service, _Router(), store_url=url,
                                bake_s=0.05, min_requests=1, poll_s=0.01)
            harv = Harvester(HarvestPolicy(slo_ms=args.fly_slo_ms),
                             scrape, train_step,
                             lambda: ckpt.flush(timeout=args.fly_grace_s),
                             drain_grace_s=args.fly_grace_s, idle_s=0.05)
            cycles: List[Dict] = []
            live_lat: List[float] = []
            promotes = {"n": 0}

            def promote_pending() -> None:
                if not fold["pending"]:
                    return
                verdict = promoter.promote(
                    {k: np.copy(v) for k, v in state.items()},
                    fold["step"])
                if verdict == "promoted":
                    promotes["n"] += 1
                    now = time.monotonic()
                    for h in fold["pending"]:
                        if h in ack_times:
                            live_lat.append(now - ack_times[h])
                    fold["pending"].clear()

            def trainer_loop() -> None:
                dry = 0
                while dry < 2:
                    summary = harv.run_cycle(deadline_s=2.0)
                    cycles.append(summary)
                    promote_pending()
                    if summary["reason"] == "drained" and summary[
                            "steps"] == 0:
                        dry = dry + 1 if serve_done.is_set() else 0
                        time.sleep(0.1)
                    else:
                        dry = 0

            sink_t = threading.Thread(target=sink_loop, daemon=True)
            trainer_t = threading.Thread(target=trainer_loop, daemon=True)
            sink_t.start()
            trainer_t.start()
            flywheel = asyncio.run(_run_policy("affinity", plan, args,
                                               on_complete=on_complete))
            serve_done.set()
            fb_q.put(None)
            sink_t.join(timeout=60)
            trainer_t.join(timeout=120)
        finally:
            kill_process_tree(store_proc.pid)

    vacates = [c for c in cycles if c["vacate_s"] > 0]
    all_within = all(c["within_grace"] for c in vacates)
    lat_p50 = _percentile(live_lat, 0.50)
    lat_p99 = _percentile(live_lat, 0.99)
    p99_delta = flywheel["ttft_p99_ms"] - baseline["ttft_p99_ms"]
    shed_delta = flywheel["shed_rate"] - baseline["shed_rate"]

    print(f"\n{'arm':<12} {'shed%':>7} {'ttft p50':>10} {'ttft p99':>10} "
          f"{'tokens/s':>10}")
    for name, r in (("baseline", baseline), ("flywheel", flywheel)):
        print(f"{name:<12} {r['shed_rate'] * 100:>6.1f}% "
              f"{r['ttft_p50_ms']:>8.1f}ms {r['ttft_p99_ms']:>8.1f}ms "
              f"{r['tokens_per_s']:>10}")
    steps = sum(c["steps"] for c in cycles)
    print(f"\nfeedback-to-weights-live: p50 {lat_p50:.2f}s "
          f"p99 {lat_p99:.2f}s over {len(live_lat)} records "
          f"({promotes['n']} promotion(s), {steps} harvested step(s))")
    print(f"serving impact: ttft p99 {p99_delta:+.1f}ms, shed rate "
          f"{shed_delta * 100:+.2f}pp vs baseline")
    print(f"vacates: {len(vacates)}, max "
          f"{max((c['vacate_s'] for c in vacates), default=0.0):.3f}s vs "
          f"grace {args.fly_grace_s:.1f}s -> "
          f"{'all inside grace' if all_within else 'GRACE EXCEEDED'}")

    acceptance = {
        "promoted_at_least_once": promotes["n"] >= 1,
        "latency_measured": len(live_lat) > 0,
        "vacates_within_grace": all_within,
    }
    out = {"metric": "flywheel_feedback_to_live_p50_s",
           "value": round(lat_p50, 3), "unit": "s",
           "detail": {"p99_s": round(lat_p99, 3),
                      "records": len(live_lat),
                      "promotions": promotes["n"],
                      "harvested_steps": steps,
                      "cycles": {"count": len(cycles),
                                 "vacates": len(vacates),
                                 "max_vacate_s": round(max(
                                     (c["vacate_s"] for c in vacates),
                                     default=0.0), 4),
                                 "grace_s": args.fly_grace_s},
                      "ttft_p99_delta_ms": round(p99_delta, 1),
                      "shed_rate_delta": round(shed_delta, 4),
                      "baseline": baseline, "flywheel": flywheel,
                      "acceptance": acceptance}}
    print("\n" + json.dumps(out))
    return 0 if all(acceptance.values()) else 1


# ---------------------------------------------------------------------------
# --obs: fleet aggregator under load (ISSUE 20)
# ---------------------------------------------------------------------------
#
# Two claims, one run, exit-coded:
#
# merge   the controller-side FleetAggregator's merged p50/p99 for a stage
#         must match the single-scrape reference (raw bucket sums over the
#         same final exposition texts) within tolerance — the epoch
#         correction and union-edge merge must be invisible when pods
#         share a build and never restarted;
# alert   an injected latency breach (every pod's synthetic load turns
#         slower than the SLO at a known moment) must trip the
#         fast-window SloBurnAlert within ONE scrape round of the breach
#         becoming visible in a scrape.


def run_obs_pod(args) -> None:
    """One fleet pod for ``--obs``: the real registry behind a real
    ``/metrics`` endpoint, plus a seeded synthetic load loop observing
    ``kt_stage_seconds{stage="bench_obs"}`` — fast (well under the SLO)
    until ``--breach-at`` seconds in, then slow (over it). The breach
    flips a ``kt_bench_obs_breach`` gauge in the SAME loop iteration as
    the first slow observation, so the driver can pin exactly which
    scrape round first saw the breach."""
    import random as _random
    import threading

    from aiohttp import web

    rng = _random.Random(args.seed * 1000 + int(args.replica_id or 0))
    telemetry.build_info_metrics()       # kt_build_info on this scrape too
    breach_gauge = telemetry.REGISTRY.gauge(
        "kt_bench_obs_breach",
        "1 once this bench pod's injected latency breach is live")
    breach_gauge.set(0)
    slo_s = args.obs_slo_ms / 1000.0
    t0 = time.monotonic()

    def load() -> None:
        while True:
            if (args.breach_at > 0
                    and time.monotonic() - t0 >= args.breach_at):
                breach_gauge.set(1)
                lat = slo_s * (2.0 + rng.random())
            else:
                lat = slo_s * (0.1 + 0.4 * rng.random())
            telemetry.observe_stage("bench_obs", lat)
            time.sleep(0.002)

    async def metrics_route(request):
        return web.Response(text=telemetry.REGISTRY.render(),
                            content_type="text/plain")

    app = web.Application()
    app.router.add_get("/metrics", metrics_route)
    threading.Thread(target=load, daemon=True).start()
    web.run_app(app, host="127.0.0.1", port=args.port,
                print=lambda *_: None)


def _obs_main(args) -> int:
    import re as _re
    import subprocess

    import requests

    from kubetorch_tpu.controller.app import (_parse_histogram_buckets,
                                              _quantile_from_buckets)
    from kubetorch_tpu.exceptions import package_exception
    from kubetorch_tpu.obs import FleetAggregator
    from kubetorch_tpu.utils.procs import (free_port, kill_process_tree,
                                           wait_for_port)

    interval = args.obs_interval
    slo_s = args.obs_slo_ms / 1000.0
    # bench-scale windows: fast = 3 rounds, slow = 10 — same multi-window
    # shape as production (5m/1h), compressed so the run fits in seconds
    agg = FleetAggregator(slo_s=slo_s, target=0.99, burn_threshold=14.4,
                          fast_window_s=3 * interval,
                          slow_window_s=10 * interval)
    print(f"fleet aggregator bench: {args.obs_pods} subprocess pods, "
          f"scrape every {interval}s, SLO {args.obs_slo_ms:.0f}ms @ 99%, "
          f"latency breach injected at t={args.breach_at}s per pod")

    ports = [free_port() for _ in range(args.obs_pods)]
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--obs-pod",
         "--port", str(port), "--replica-id", str(i),
         "--breach-at", str(args.breach_at),
         "--obs-slo-ms", str(args.obs_slo_ms), "--seed", str(args.seed)],
        env=_cold_env(), stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL) for i, port in enumerate(ports)]
    texts: Dict[str, Optional[str]] = {}
    first_breach_round: Optional[int] = None
    first_alert_round: Optional[int] = None
    alert = None
    try:
        for port in ports:
            assert wait_for_port("127.0.0.1", port, timeout=30), \
                "obs pod never came up"
        for rnd in range(args.obs_rounds):
            round_texts: Dict[str, Optional[str]] = {}
            for i, port in enumerate(ports):
                try:
                    round_texts[f"pod-{i}"] = requests.get(
                        f"http://127.0.0.1:{port}/metrics", timeout=2).text
                except requests.RequestException:
                    round_texts[f"pod-{i}"] = None
                agg.ingest(f"pod-{i}", round_texts[f"pod-{i}"])
            raised = agg.tick()
            # keep each pod's LAST successful text: the reference must
            # cover exactly the history the aggregator folded in
            texts.update({k: v for k, v in round_texts.items() if v})
            if first_breach_round is None and any(
                    v and _re.search(r"^kt_bench_obs_breach(?:\{[^}]*\})?"
                                     r"\s+1(?:\.0)?\s*$", v, _re.M)
                    for v in round_texts.values()):
                first_breach_round = rnd
            fast = [a for a in raised
                    if a.window == "fast" and a.stage == "bench_obs"]
            if fast and first_alert_round is None:
                first_alert_round = rnd
                alert = fast[0]
            if first_alert_round is not None:
                break
            time.sleep(interval)
    finally:
        for proc in procs:
            kill_process_tree(proc.pid)

    per_pod = {}
    for pod, text in texts.items():
        raw = _parse_histogram_buckets(text, "kt_stage_seconds",
                                       'stage="bench_obs"')
        if raw:
            per_pod[pod] = raw
    ref: Dict[str, float] = {}
    for raw in per_pod.values():
        for le, count in raw.items():
            ref[le] = ref.get(le, 0.0) + count
    ref_p50 = _quantile_from_buckets(ref, 0.5)
    ref_p99 = _quantile_from_buckets(ref, 0.99)
    agg_p50 = agg.quantile("bench_obs", 0.5)
    agg_p99 = agg.quantile("bench_obs", 0.99)

    def _rel_err(a: Optional[float], b: Optional[float]) -> float:
        if not a or not b:
            return float("inf")
        return abs(a - b) / b

    status = agg.status()
    stage_row = status["stages"].get("bench_obs", {})
    print(f"\nmerged vs single-scrape reference "
          f"({len(per_pod)} pods, {stage_row.get('count', 0):.0f} obs): "
          f"p50 {1000 * (agg_p50 or 0):.1f}ms vs "
          f"{1000 * (ref_p50 or 0):.1f}ms, "
          f"p99 {1000 * (agg_p99 or 0):.1f}ms vs "
          f"{1000 * (ref_p99 or 0):.1f}ms")
    if first_alert_round is not None and alert is not None:
        rounds_late = (first_alert_round - first_breach_round
                       if first_breach_round is not None else None)
        print(f"breach first visible in scrape round {first_breach_round}; "
              f"fast-window alert in round {first_alert_round} "
              f"({rounds_late} round(s) later): {alert}")
    else:
        print("breach never tripped the fast-window alert "
              f"(breach round: {first_breach_round})")
    acceptance = {
        "merged_p50_matches_reference": _rel_err(agg_p50, ref_p50) <= 0.05,
        "merged_p99_matches_reference": _rel_err(agg_p99, ref_p99) <= 0.05,
        "alert_within_one_round": (
            first_alert_round is not None
            and first_breach_round is not None
            and first_alert_round <= first_breach_round + 1),
    }
    out = {
        "metric": "fleet_obs_alert_rounds",
        "value": (first_alert_round - first_breach_round
                  if first_alert_round is not None
                  and first_breach_round is not None else None),
        "unit": "rounds",
        "detail": {
            "pods": args.obs_pods,
            "scrape_interval_s": interval,
            "merged": {"p50_s": agg_p50, "p99_s": agg_p99},
            "reference": {"p50_s": ref_p50, "p99_s": ref_p99},
            "breach_round": first_breach_round,
            "alert_round": first_alert_round,
            "alert": package_exception(alert) if alert else None,
            "status": stage_row,
            "acceptance": acceptance,
        },
    }
    print("\n" + json.dumps(out))
    return 0 if all(acceptance.values()) else 1


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--regions", type=int, default=0,
                   help="N>0: cross-region failover mode — N subprocess "
                        "CPU-proxy regions behind the geo front door, "
                        "region-0 SIGKILLed at --kill-at (ISSUE 13)")
    p.add_argument("--kill-at", type=float, default=4.0,
                   help="seconds into the run to SIGKILL region-0")
    p.add_argument("--scale-out", action="store_true",
                   help="fleet cold-start burn-down: 0->N replicas cold "
                        "vs template-fork warm, plus broadcast-tree "
                        "joiner egress (ISSUE 16)")
    p.add_argument("--flywheel", action="store_true",
                   help="continuous-learning loop under load: feedback-"
                        "to-weights-live p50/p99 through a real store + "
                        "ledger + harvest trainer + gated promotion, and "
                        "the harvest/vacate impact on serving p99/shed "
                        "(ISSUE 19); exit-coded on vacate-inside-grace")
    p.add_argument("--obs", action="store_true",
                   help="fleet aggregator under load: subprocess pods "
                        "scraped into the real FleetAggregator — merged "
                        "p50/p99 vs single-scrape reference, and an "
                        "injected latency breach must trip the fast-"
                        "window SloBurnAlert within one scrape round "
                        "(ISSUE 20); exit-coded")
    p.add_argument("--obs-pods", type=int, default=4,
                   help="obs: subprocess pod count")
    p.add_argument("--obs-rounds", type=int, default=40,
                   help="obs: max scrape rounds before giving up")
    p.add_argument("--obs-interval", type=float, default=0.5,
                   help="obs: scrape interval (s)")
    p.add_argument("--obs-slo-ms", type=float, default=100.0,
                   help="obs: per-stage latency SLO (ms)")
    p.add_argument("--breach-at", type=float, default=4.0,
                   help="obs: seconds after pod start to turn its "
                        "synthetic load slower than the SLO")
    p.add_argument("--fly-slo-ms", type=float, default=400.0,
                   help="flywheel harvest policy queue-wait SLO (ms)")
    p.add_argument("--fly-grace-s", type=float, default=5.0,
                   help="flywheel vacate drain-grace window (s)")
    p.add_argument("--n", type=int, default=4,
                   help="scale-out A/B replica count per arm")
    p.add_argument("--joiners", type=int, default=16,
                   help="scale-out egress joiner count")
    p.add_argument("--skip-egress", action="store_true",
                   help="scale-out: A/B arms only")
    p.add_argument("--timeout", type=float, default=600.0,
                   help="scale-out per-phase wait budget")
    # internal: scale-out joiner / obs pod subprocess modes
    p.add_argument("--joiner", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--obs-pod", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--store", default="", help=argparse.SUPPRESS)
    p.add_argument("--key", default="", help=argparse.SUPPRESS)
    p.add_argument("--index-hash", default="", help=argparse.SUPPRESS)
    p.add_argument("--replica-id", default="", help=argparse.SUPPRESS)
    p.add_argument("--result", default="", help=argparse.SUPPRESS)
    p.add_argument("--sessions", type=int, default=1200)
    p.add_argument("--turns", type=int, default=3)
    p.add_argument("--replicas", type=int, default=8)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--queue-max", type=int, default=256)
    p.add_argument("--header-tokens", type=int, default=192,
                   help="shared conversation header (the prefix-cache win)")
    p.add_argument("--turn-tokens", type=int, default=48)
    p.add_argument("--new-tokens", type=int, default=24)
    p.add_argument("--prefill-us-per-tok", type=float, default=400.0)
    p.add_argument("--decode-us-per-tok", type=float, default=1500.0)
    p.add_argument("--resident-cap", type=int, default=256,
                   help="per-replica prefix-cache sessions (engine K/V cap)")
    p.add_argument("--spread-s", type=float, default=8.0,
                   help="window over which non-burst sessions start")
    p.add_argument("--turn-gap-s", type=float, default=2.5)
    p.add_argument("--burst-frac", type=float, default=0.5,
                   help="fraction of sessions arriving in the burst")
    p.add_argument("--burst-at", type=float, default=3.0)
    p.add_argument("--burst-window", type=float, default=0.4)
    p.add_argument("--deadline-s", type=float, default=1.5,
                   help="per-request X-KT-Deadline; 0 disables")
    p.add_argument("--seed", type=int, default=1234)
    args = p.parse_args()

    if args.joiner:
        run_joiner(args)
        return 0
    if args.obs_pod:
        run_obs_pod(args)
        return 0
    if args.obs:
        return _obs_main(args)
    if args.scale_out:
        return _scaleout_main(args)
    if args.flywheel:
        # lighter default schedule: every feedback batch and every
        # checkpoint crosses a real HTTP hop into the store subprocess
        if "--sessions" not in sys.argv:
            args.sessions = 300
        if "--turns" not in sys.argv:
            args.turns = 2
        return _flywheel_main(args)
    if args.regions > 0:
        # region mode defaults: a lighter schedule (every request crosses
        # a real HTTP hop into a subprocess) unless explicitly overridden
        if "--sessions" not in sys.argv:
            args.sessions = 240
        if "--turns" not in sys.argv:
            args.turns = 2
        if "--replicas" not in sys.argv:
            args.replicas = 4
        if "--spread-s" not in sys.argv:
            args.spread_s = 10.0
        return _regions_main(args)

    plan = _schedule(args)
    cap_rps = (args.replicas * args.slots
               / ((args.header_tokens + args.turn_tokens)
                  * args.prefill_us_per_tok / 1e6
                  + args.new_tokens * args.decode_us_per_tok / 1e6))
    print(f"serve front-door bench: {args.sessions} sessions x "
          f"{args.turns} turns = {len(plan)} requests, open-loop, "
          f"{args.replicas} replicas x {args.slots} slots "
          f"(~{cap_rps:.0f} rps cold capacity), burst "
          f"{args.burst_frac:.0%} @ t={args.burst_at}s")

    results = {}
    for policy in ("rr", "affinity"):
        results[policy] = asyncio.run(_run_policy(policy, plan, args))

    print(f"\n{'policy':<10} {'reqs':>6} {'shed%':>7} {'hit%':>6} "
          f"{'ttft p50':>10} {'ttft p99':>10} {'tokens/s':>10}")
    for policy in ("rr", "affinity"):
        r = results[policy]
        print(f"{policy:<10} {r['requests']:>6} "
              f"{r['shed_rate'] * 100:>6.1f}% "
              f"{r['prefix_hit_rate'] * 100:>5.1f}% "
              f"{r['ttft_p50_ms']:>8.1f}ms {r['ttft_p99_ms']:>8.1f}ms "
              f"{r['tokens_per_s']:>10}")
    rr, aff = results["rr"], results["affinity"]
    p50_win = (rr["ttft_p50_ms"] / aff["ttft_p50_ms"]
               if aff["ttft_p50_ms"] else float("nan"))
    shed_detail = ", ".join(
        f"{k}={v}" for k, v in sorted(aff["shed_by_reason"].items()))
    print(f"\naffinity vs round-robin: prefix hit rate "
          f"{rr['prefix_hit_rate']:.0%} -> {aff['prefix_hit_rate']:.0%}, "
          f"ttft p50 {p50_win:.2f}x better; admission shed "
          f"{aff['shed']}/{aff['requests']} ({shed_detail or 'none'}) "
          f"where rr queued unboundedly (p99 "
          f"{rr['ttft_p99_ms']:.0f}ms vs {aff['ttft_p99_ms']:.0f}ms)")
    probes_avoided = telemetry.serve_metrics()["probes_avoided"].value()
    print(f"health probes actually sent by the router: "
          f"{aff['health_probes']} for {aff['requests']} dispatches "
          f"({probes_avoided:.0f} avoided by the TTL cache — the old "
          f"per-call probe RTT)")

    out = {
        "metric": "serve_ttft_p99_ms",
        "value": aff["ttft_p99_ms"],
        "unit": "ms",
        "detail": {
            "requests": len(plan),
            "concurrent_sessions": args.sessions,
            "ttft_p50_win_x": round(p50_win, 2),
            "rr": rr,
            "affinity": aff,
        },
    }
    print("\n" + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
