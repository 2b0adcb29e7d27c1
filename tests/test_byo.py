"""BYO compute e2e (reference: tests/test_byo_compute.py / SURVEY §3.5 —
``kubetorch server start`` on user-owned pods + ``Compute(selector=...)``).

The user starts the pod runtime themselves; it registers over the controller
WS and idles ("waiting"). A later ``kt.fn(...).to(kt.Compute(selector=...))``
registers the workload WITHOUT a manifest, the controller pushes the callable
metadata to the already-connected pod, derives a routable service_url from
the registration (no manifest ever declared one), and calls flow.
"""

import os
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.level("minimal")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "assets"))

import kubetorch_tpu as kt
from kubetorch_tpu.client import controller_client, shutdown_local_controller
from kubetorch_tpu.config import reset_config

import payloads  # tests/assets

from kubetorch_tpu.utils.procs import (free_port, kill_process_tree,
                                       wait_for_port)


@pytest.fixture(scope="module", autouse=True)
def local_stack():
    from kubetorch_tpu.client import _read_running_local

    prior_user = os.environ.get("KT_USERNAME")
    preexisting_daemon = _read_running_local() is not None
    reset_config()
    os.environ["KT_USERNAME"] = "t-byo"
    reset_config()
    yield
    try:
        for w in controller_client().list_workloads():
            if w["name"].startswith("t-byo"):
                controller_client().delete_workload(w["namespace"], w["name"])
    except Exception:
        pass
    if not preexisting_daemon:
        shutdown_local_controller()
    if prior_user is None:
        os.environ.pop("KT_USERNAME", None)
    else:
        os.environ["KT_USERNAME"] = prior_user
    reset_config()


@pytest.fixture
def byo_pod():
    """A user-owned pod: ``kt server start --workload ...`` as a subprocess."""
    cc = controller_client()          # auto-starts the local daemon
    port = free_port()
    name = "t-byo-summer"             # must equal the fn's derived service name
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "KT_CONTROLLER_WS_URL":
            cc.base_url.replace("http", "ws", 1) + "/controller/ws/pods",
        "KT_NAMESPACE": "default",
        # deliberately NOT setting KT_SERVER_PORT: `--port` alone must make
        # the WS registration advertise the right port
        "POD_IP": "127.0.0.1",
        "LOCAL_IPS": "127.0.0.1",
        "POD_NAME": "byo-pod-0",
    })
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.cli", "server", "start",
         "--port", str(port), "--workload", name],
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        assert wait_for_port("127.0.0.1", port, timeout=60)
        _wait_for_registration(cc, name)
        yield name, port
    finally:
        # also covers failures BEFORE yield — a fixture that dies waiting
        # must not leak its pod subprocess into later tests
        kill_process_tree(proc.pid)


def _wait_for_registration(cc, name, timeout=30):
    """Block until the pod's WS registration lands — a .to() that races it
    reaches zero pods and derives no service URL."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            if cc.get_workload("default", name).get("connected_pods"):
                return
        except Exception:
            pass
        time.sleep(0.5)
    raise AssertionError(f"BYO pod {name!r} never registered over WS")


@pytest.mark.slow
def test_byo_selector_deploy_and_call(byo_pod):
    name, port = byo_pod
    cc = controller_client()

    f = kt.fn(payloads.summer)
    assert f.name == name, "pod must be registered under the fn's service name"
    f.to(kt.Compute(selector={"app": "byo-test"}))

    # no manifest: the controller derived the URL from the pod registration
    record = cc.get_workload("default", name)
    assert record["selector"] == {"app": "byo-test"}
    assert record["manifest"] is None
    assert record["service_url"] == f"http://127.0.0.1:{port}"

    assert f(2, 3) == 5
    assert f(10, -4) == 6


@pytest.mark.slow
def test_byo_hot_reload(byo_pod):
    """Second .to() on the same BYO pod swaps the callable without restart."""
    name, _ = byo_pod
    f = kt.fn(payloads.summer)
    f.to(kt.Compute(selector={"app": "byo-test"}))
    assert f(1, 1) == 2

    g = kt.fn(payloads.whoami, name=name)
    g.to(kt.Compute(selector={"app": "byo-test"}))
    out = g()
    assert out["world_size"] == "1" and out["rank"] == "0"
