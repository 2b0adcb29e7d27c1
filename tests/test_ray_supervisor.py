"""Ray distribution mode executed end-to-end (serving/ray_supervisor.py).

The supervisor's job is PROCESS MANAGEMENT: elect/honor the head, start the
GCS and wait for its port, join workers against it, run user code through a
single head-side ProcessWorker, refuse calls on workers, and tear the ray
processes down. All of that runs here against real pod-server subprocesses
(the LOCAL_IPS fake, as in test_distributed.py) and a minimal ``ray`` CLI
double (tests/assets/fake_ray/ray) that reproduces the contract the
supervisor drives: listener on the GCS port for ``start --head``,
connect-or-fail for ``start --address``, foreground ``--block`` semantics.
What it cannot prove: Ray's own scheduling inside user code — that needs
``ray`` in the image (reference CI runs real clusters; PARITY.md notes the
descope).
"""

import json
import os
import subprocess
import sys
import time

import pytest
import requests

from kubetorch_tpu.utils.procs import free_port, wait_for_port

pytestmark = [pytest.mark.level("minimal"), pytest.mark.slow]

ASSETS = os.path.join(os.path.dirname(__file__), "assets")
FAKE_RAY = os.path.join(ASSETS, "fake_ray")
GCS_PORT = 6379


def spawn_ray_pod(ip: str, port: int, ips: list, role: str = ""):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PATH": FAKE_RAY + os.pathsep + env.get("PATH", ""),
        "LOCAL_IPS": ",".join(ips),
        "POD_IP": ip,
        "POD_NAME": f"pod-{ip.split('.')[-1]}",
        "KT_PROJECT_ROOT": ASSETS,
        "KT_MODULE_NAME": "payloads",
        "KT_FILE_PATH": "payloads.py",
        "KT_CLS_OR_FN_NAME": "whoami",
        "KT_LAUNCH_ID": "launch-ray",
        "KT_SERVICE_NAME": "ray-svc",
        "KT_DISTRIBUTED_CONFIG": json.dumps({
            "distribution_type": "ray", "workers": len(ips),
            "procs_per_worker": 1}),
        "KT_SERVER_PORT": str(port),
    })
    if role:
        env["KT_RAY_ROLE"] = role
    return subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.serving.http_server",
         "--host", ip, "--port", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _teardown(procs):
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:
            p.kill()


def _wait_ready(ip, port, proc, timeout=60):
    """Pod port up AND /health green (ray head setup is async work)."""
    assert wait_for_port(ip, port, timeout=timeout), _tail(proc)
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            r = requests.get(f"http://{ip}:{port}/health", timeout=5)
            if r.status_code == 200:
                return
        except requests.ConnectionError:
            pass
        time.sleep(0.5)
    raise AssertionError(f"pod {ip} never became healthy: {_tail(proc)}")


def _tail(proc):
    proc.terminate()
    try:
        out = proc.communicate(timeout=5)[0]
    except subprocess.TimeoutExpired:
        proc.kill()
        out = ""
    return (out or "")[-2000:]


def test_ray_head_and_worker_lowest_ip_election():
    """Homogeneous pods (Deployment path): lowest IP becomes the head,
    starts the GCS, serves calls through its ProcessWorker; the worker
    joins the GCS and refuses user calls."""
    ips = ["127.0.0.2", "127.0.0.3"]
    port = free_port()
    procs = [spawn_ray_pod(ip, port, ips) for ip in ips]
    try:
        _wait_ready(ips[0], port, procs[0])
        # the head's GCS stand-in is live on the fixed ray port
        assert wait_for_port(ips[0], GCS_PORT, timeout=10)
        _wait_ready(ips[1], port, procs[1])

        # user code runs on the head only — one subprocess, not a fan-out
        r = requests.post(f"http://{ips[0]}:{port}/whoami",
                          json={"args": [], "kwargs": {}}, timeout=60)
        assert r.status_code == 200, r.text
        result = r.json()
        if isinstance(result, list):
            assert len(result) == 1
            result = result[0]
        # ExecutionSupervisor semantics on the head: a world of ONE pod
        assert result["pod_ips"] == ips[0]
        assert result["world_size"] == "1" and result["rank"] == "0"

        # the worker pod hosts ray processes only; calls are refused
        r = requests.post(f"http://{ips[1]}:{port}/whoami",
                          json={"args": [], "kwargs": {}}, timeout=60)
        assert r.status_code >= 400
        assert "head" in r.text.lower()
    finally:
        _teardown(procs)


def test_ray_kuberay_roles_and_gcs_probe():
    """KubeRay path (KT_RAY_ROLE): the designated head keeps the GCS even
    when it is NOT the lowest IP, and the worker finds it by probing the
    discovered set for the live GCS port (_find_gcs), not by rank."""
    head_ip, worker_ip = "127.0.0.5", "127.0.0.4"   # head deliberately higher
    ips = sorted([head_ip, worker_ip])
    port = free_port()
    head = spawn_ray_pod(head_ip, port, ips, role="head")
    worker = spawn_ray_pod(worker_ip, port, ips, role="worker")
    try:
        _wait_ready(head_ip, port, head)
        assert wait_for_port(head_ip, GCS_PORT, timeout=10)
        _wait_ready(worker_ip, port, worker)

        r = requests.post(f"http://{head_ip}:{port}/whoami",
                          json={"args": [], "kwargs": {}}, timeout=60)
        assert r.status_code == 200, r.text
        # the elected-by-IP candidate (lowest) must NOT have a GCS: role won
        assert not wait_for_port(worker_ip, GCS_PORT, timeout=1)
    finally:
        _teardown([head, worker])
