"""Framework env contracts end-to-end: the injected rank env must actually
bring up torch.distributed (the reference's pytorch mode, gloo on CPU)."""

import json
import os
import subprocess
import sys

import pytest
import requests

from kubetorch_tpu.utils.procs import free_port, kill_process_tree, wait_for_port

ASSETS = os.path.join(os.path.dirname(__file__), "assets")


@pytest.mark.level("minimal")
@pytest.mark.slow
def test_pytorch_gloo_allreduce_via_env_contract():
    """One pod × 2 rank subprocesses: dist.init_process_group('gloo') works
    purely from the env the fabric injects, and the allreduce sums ranks."""
    port = free_port()
    ip = "127.0.0.31"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "LOCAL_IPS": ip,
        "POD_IP": ip,
        "KT_PROJECT_ROOT": ASSETS,
        "KT_MODULE_NAME": "payloads",
        "KT_FILE_PATH": "payloads.py",
        "KT_CLS_OR_FN_NAME": "torch_allreduce",
        "KT_LAUNCH_ID": "t1",
        "KT_SERVICE_NAME": "t-torch",
        "KT_DISTRIBUTED_CONFIG": json.dumps(
            {"distribution_type": "pytorch", "workers": 1,
             "procs_per_worker": 2}),
        "KT_SERVER_PORT": str(port),
    })
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.serving.http_server",
         "--host", ip, "--port", str(port)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        assert wait_for_port(ip, port, timeout=30)
        r = requests.post(f"http://{ip}:{port}/torch_allreduce",
                          json={"args": [], "kwargs": {}}, timeout=120)
        assert r.status_code == 200, r.text[:300]
        results = r.json()
        assert len(results) == 2
        assert sorted(x["rank"] for x in results) == [0, 1]
        assert all(x["world"] == 2 for x in results)
        # allreduce of (rank+1) over 2 ranks = 1 + 2
        assert all(x["sum"] == 3.0 for x in results)
    finally:
        kill_process_tree(proc.pid)
