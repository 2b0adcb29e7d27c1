"""Client-side controller access (reference ``globals.py``).

``ControllerClient`` speaks the controller REST/WS protocol. When no
``api_url`` is configured, a local controller (with the subprocess-pod
backend) is auto-started once per client process — the zero-infra dev loop:
``kt.fn(f).to(kt.Compute(cpus=1))`` works on a bare machine with no cluster,
exactly like the reference's port-forward path makes a remote cluster feel
local (reference ``globals.py:123-366``).
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
from typing import Any, Dict, List, Optional

import requests as _requests

from .config import config
from .exceptions import ControllerRequestError
from .resilience import (RETRYABLE_STATUSES, connection_never_established,
                         controller_policy, retry_after_seconds)
from .utils.procs import free_port, kill_process_tree, wait_for_port

_IDEMPOTENT_VERBS = ("GET", "HEAD", "DELETE")


class ControllerClient:
    def __init__(self, base_url: str):
        self.base_url = base_url.rstrip("/")
        self._session = _requests.Session()

    # -- raw ------------------------------------------------------------------

    def _request(self, method: str, path: str, timeout: float = 120.0,
                 **kwargs) -> Any:
        """One controller call under the control-plane retry policy:
        idempotent verbs retry transient failures (connection errors,
        timeouts, 502/503/504 with Retry-After honored); POSTs retry only
        when the connection was never established — the controller may have
        acted on an established one. A dead *local daemon* is additionally
        re-resolved once per call (its durable state revives under a fresh
        daemon); user-configured URLs are never silently redirected."""
        from . import telemetry

        policy = controller_policy()
        idempotent = method in _IDEMPOTENT_VERBS
        recovered = [False]
        # control-plane hops join the active trace too: a deploy or
        # workload lookup mid-call shows up on the same waterfall, and the
        # controller's own downstream requests can keep propagating it
        if telemetry.current_header() is not None:
            hdrs = dict(kwargs.get("headers") or {})
            telemetry.inject(hdrs)
            kwargs["headers"] = hdrs

        def _attempt(info):
            url = f"{self.base_url}{path}"
            t = timeout if info.timeout is None else min(timeout, info.timeout)
            try:
                return self._session.request(method, url, timeout=t, **kwargs)
            except _requests.ConnectionError as e:
                if not recovered[0]:
                    recovered[0] = True
                    new_url = _recover_daemon(self.base_url)
                    if new_url is not None:
                        self.base_url = new_url
                        return self._session.request(
                            method, f"{self.base_url}{path}", timeout=t,
                            **kwargs)
                raise e

        def _retryable(e: BaseException) -> bool:
            if connection_never_established(e):
                return True
            return idempotent and isinstance(
                e, (_requests.ConnectionError, _requests.Timeout))

        def _resp_retry(resp):
            if not idempotent or resp.status_code not in RETRYABLE_STATUSES:
                return None
            ra = retry_after_seconds(resp)
            return ra if ra is not None else True

        try:
            with telemetry.span("controller.request", method=method,
                                path=path) as sp:
                resp = policy.run(_attempt, retryable_exc=_retryable,
                                  response_retry_delay=_resp_retry)
                sp.set_attr("status", resp.status_code)
        except _requests.RequestException as e:
            raise ControllerRequestError(
                f"Controller unreachable at {self.base_url}{path}: {e}")
        if resp.status_code >= 400:
            raise ControllerRequestError(
                f"{method} {path} → {resp.status_code}: {resp.text[:500]}",
                status_code=resp.status_code)
        return resp.json() if resp.content else None

    # -- API ------------------------------------------------------------------

    def deploy(self, namespace: str, name: str, manifest: Dict,
               metadata: Dict, launch_id: str,
               inactivity_ttl: Optional[int] = None,
               expected_pods: Optional[int] = None,
               autoscaling: Optional[Dict] = None,
               scheduling: Optional[Dict] = None,
               service_url: Optional[str] = None,
               timeout: float = 900.0) -> Dict:
        return self._request("POST", "/controller/deploy", timeout=timeout, json={
            "namespace": namespace, "name": name, "manifest": manifest,
            "metadata": metadata, "launch_id": launch_id,
            "inactivity_ttl": inactivity_ttl, "expected_pods": expected_pods,
            "autoscaling": autoscaling, "scheduling": scheduling,
            "service_url": service_url,
        })

    def apply(self, namespace: str, name: str, manifest: Dict,
              env: Optional[Dict] = None) -> Dict:
        return self._request("POST", "/controller/apply", json={
            "namespace": namespace, "name": name, "manifest": manifest,
            "env": env or {}})

    def register_workload(self, namespace: str, name: str, metadata: Dict,
                          selector: Optional[Dict] = None,
                          service_url: Optional[str] = None,
                          launch_id: Optional[str] = None) -> Dict:
        return self._request("POST", "/controller/workload", json={
            "namespace": namespace, "name": name, "metadata": metadata,
            "selector": selector, "service_url": service_url,
            "launch_id": launch_id})

    def get_workload(self, namespace: str, name: str) -> Dict:
        return self._request("GET", f"/controller/workload/{namespace}/{name}")

    def delete_workload(self, namespace: str, name: str) -> Dict:
        return self._request("DELETE", f"/controller/workload/{namespace}/{name}")

    def list_workloads(self, namespace: Optional[str] = None) -> List[Dict]:
        params = {"namespace": namespace} if namespace else {}
        return self._request("GET", "/controller/workloads",
                             params=params)["workloads"]

    def check_ready(self, namespace: str, name: str) -> Dict:
        return self._request("GET", f"/controller/check-ready/{namespace}/{name}")

    def queue_status(self) -> Dict:
        """Scheduler snapshot (ISSUE 8): tiers + queue order, the capacity
        book, and the recent preemption ledger (``kt queue status``)."""
        return self._request("GET", "/controller/queue")

    # -- config objects (Secret / PVC / ConfigMap) ----------------------------

    def get_object(self, kind: str, namespace: str, name: str) -> Optional[Dict]:
        try:
            return self._request(
                "GET", f"/controller/object/{kind}/{namespace}/{name}")["object"]
        except ControllerRequestError as e:
            if e.status_code == 404:
                return None
            raise

    def delete_object(self, kind: str, namespace: str, name: str) -> Dict:
        return self._request(
            "DELETE", f"/controller/object/{kind}/{namespace}/{name}")

    def storage_classes(self) -> List[Dict]:
        return self._request(
            "GET", "/controller/storage-classes")["storage_classes"]

    def prom_query(self, query: str) -> Dict:
        """PromQL against the cluster metrics stack, via the controller
        (reference pod/resource-scope metric queries)."""
        return self._request("GET", "/controller/metrics/query",
                             params={"query": query})

    def cluster_config(self) -> Dict:
        try:
            return self._request("GET", "/controller/cluster-config",
                                 timeout=5.0) or {}
        except ControllerRequestError:
            return {}

    def logs(self, service: Optional[str] = None, namespace: str = "default",
             request_id: Optional[str] = None, offset: int = 0) -> Dict:
        params: Dict[str, Any] = {"namespace": namespace, "offset": offset}
        if service:
            params["service"] = service
        if request_id:
            params["request_id"] = request_id
        return self._request("GET", "/controller/logs", params=params)

    def events(self, service: Optional[str] = None) -> List[Dict]:
        params = {"service": service} if service else {}
        return self._request("GET", "/controller/events",
                             params=params)["events"]

    def version(self) -> str:
        return self._request("GET", "/controller/version", timeout=5.0)["version"]


# ---------------------------------------------------------------------------
# Local controller lifecycle
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_client: Optional[ControllerClient] = None
# URL of the daemon this process discovered/spawned (as opposed to a
# user-configured api_url): only these are safe to silently re-resolve when
# they stop answering — see _recover_daemon.
_daemon_url: Optional[str] = None


def _clear_client_singleton() -> None:
    global _client, _daemon_url
    with _lock:
        _client = None
        _daemon_url = None


# reset_config() must also drop the derived client singleton, or a stale
# client would silently survive a config swap
from .config import on_reset as _on_reset  # noqa: E402

_on_reset(_clear_client_singleton)


def _state_file() -> str:
    return os.path.join(config().config_dir, "local-controller.json")


def _read_running_local() -> Optional[Dict]:
    """The persisted local-controller daemon, if it still answers AND was
    built from the sources currently on disk. A daemon running stale code
    (package edited since it started) is stopped and forgotten so the caller
    spawns a fresh one — the local analog of the reference's
    client↔controller version-mismatch check."""
    import json

    from .utils import code_fingerprint

    try:
        with open(_state_file()) as f:
            state = json.load(f)
    except (OSError, ValueError):
        return None
    try:
        r = _requests.get(f"{state['url']}/controller/version", timeout=2)
        if r.status_code == 200:
            try:
                remote_fp = r.json().get("code_fingerprint")
            except ValueError:
                remote_fp = None
            if remote_fp == code_fingerprint():
                return state
            # Stale code, but the daemon may be running someone's workloads
            # (another venv/checkout alternating with this one, or a long
            # training service). Killing it would tear all of them down, so
            # refuse and reuse unless explicitly overridden — the user can
            # run `kt controller stop` (records persist and revive, but
            # in-flight work on the pods dies).
            if os.environ.get("KT_CONTROLLER_REPLACE", "") != "always":
                try:
                    listed = _requests.get(
                        f"{state['url']}/controller/workloads",
                        timeout=5).json().get("workloads", [])
                    # persisted records with explicitly zero live pods (e.g.
                    # restored after a daemon restart) are safe to hand over
                    # — the replacement daemon revives them from the same
                    # state dir. A missing pod_count (older daemon code that
                    # predates the field) must count as active: unknown is
                    # not safe-to-kill.
                    active = [w for w in listed if w.get("pod_count", 1)]
                except (_requests.RequestException, ValueError):
                    active = None
                if active or active is None:
                    # a failed probe also lands here: never kill a daemon
                    # whose workloads we could not enumerate
                    import warnings
                    n = len(active) if active else "unknown"
                    warnings.warn(
                        f"Local controller pid {state['pid']} runs stale code "
                        f"but hosts {n} active workload(s); reusing "
                        "it. Run `kt controller stop` to replace it (or set "
                        "KT_CONTROLLER_REPLACE=always).")
                    return state
            if _kill_daemon_process(state):
                try:
                    os.unlink(_state_file())
                except OSError:
                    pass
                return None
            # kill failed: reusing the stale daemon beats orphaning a live
            # controller (state file must survive so `kt controller stop`
            # can still find it) or spawning a duplicate next to it
            import warnings
            warnings.warn(
                f"Local controller pid {state['pid']} runs stale code but "
                "could not be stopped; reusing it. Run `kt controller stop`.")
            return state
    except _requests.RequestException:
        pass
    return None


def _kill_daemon_process(state: Dict) -> bool:
    """Verify-and-kill the persisted daemon; True when it is provably gone.

    Never kill a reused PID: confirm the process is actually our controller
    before signalling it."""
    import psutil

    try:
        proc = psutil.Process(state["pid"])
        if not any("kubetorch_tpu.controller" in part
                   for part in proc.cmdline()):
            return True          # PID reused: our daemon already died
        kill_process_tree(state["pid"])
        try:
            # kill_process_tree returns right after the SIGKILL escalation;
            # give the kernel a moment to reap. Zombie == dead for us.
            psutil.wait_procs([proc], timeout=3)
            return (not proc.is_running()
                    or proc.status() == psutil.STATUS_ZOMBIE)
        except psutil.NoSuchProcess:
            return True
    except psutil.NoSuchProcess:
        return True
    except Exception:
        return False


def controller_client() -> ControllerClient:
    """Singleton (reference ``globals.py:902``): configured api_url, else a
    persistent local-controller daemon shared across CLI invocations and
    sessions — deploy in one process, `kt list` in the next. The daemon
    outlives clients (like the in-cluster controller does); stop it with
    ``kt controller stop`` or :func:`shutdown_local_controller`."""
    global _client
    with _lock:
        if _client is not None:
            return _client
        api = config().api_url
        if api:
            _client = ControllerClient(api)
            return _client
        # an existing local daemon wins (no kubectl probe stall for local
        # users); else a kubeconfig'd cluster running our controller →
        # port-forward (reference globals.py:123-366); else spawn the daemon
        state = _read_running_local()
        if state is None:
            pf_url = _try_cluster_port_forward()
            if pf_url is not None:
                config().api_url = pf_url
                _client = ControllerClient(pf_url)
                return _client
            state = _spawn_local_daemon()
        global _daemon_url
        _daemon_url = state["url"]
        config().api_url = state["url"]
        _client = ControllerClient(state["url"])
        return _client


def _recover_daemon(dead_url: str) -> Optional[str]:
    """Called on a connection error to ``dead_url``. When that URL is the
    local daemon this process resolved (never a user-configured one),
    re-resolve — respawning the daemon if needed, which restores its durable
    workload state — and return the replacement URL."""
    global _client, _daemon_url
    with _lock:
        if dead_url != _daemon_url:
            return None
        if config().api_url == dead_url:
            config().api_url = None
        _client = None
        _daemon_url = None
    new_client = controller_client()
    return new_client.base_url if new_client.base_url != dead_url else None


def _try_cluster_port_forward() -> Optional[str]:
    """Port-forward to an in-cluster controller when one exists.

    Opt-out with KT_LOCAL_MODE=1. Cheap negative path: no kubectl → None.
    """
    if config().local_mode:
        return None
    from .utils.kubectl import resolve_kubectl

    kubectl = resolve_kubectl()
    if kubectl is None:
        return None
    try:
        # short timeout: a hung API server (stale kubeconfig, VPN down) must
        # not stall first use; the local daemon covers the fallback
        probe = subprocess.run(
            [kubectl, "get", "svc", "kubetorch-controller",
             "-n", config().install_namespace, "-o", "name"],
            capture_output=True, timeout=3)
        if probe.returncode != 0:
            return None
        from .provisioning.port_forward import ensure_port_forward
        handle = ensure_port_forward(
            service="kubetorch-controller",
            namespace=config().install_namespace, remote_port=8080)
        return handle.url
    except Exception:
        return None


def _spawn_local_daemon() -> Dict:
    """Spawn the daemon under an exclusive file lock so two first-use
    processes can't race to create (and leak) duplicate controllers."""
    import fcntl
    import json

    os.makedirs(config().config_dir, exist_ok=True)
    lock_path = os.path.join(config().config_dir, "local-controller.lock")
    with open(lock_path, "w") as lock_f:
        fcntl.flock(lock_f, fcntl.LOCK_EX)
        try:
            # another process may have won the race while we waited
            state = _read_running_local()
            if state is not None:
                return state
            return _spawn_local_daemon_locked()
        finally:
            fcntl.flock(lock_f, fcntl.LOCK_UN)


def _spawn_local_daemon_locked() -> Dict:
    import json

    port = free_port()
    env = dict(os.environ)
    # The daemon must not inherit pod identity or wiring: when a pod's
    # worker runs client code (user driver imported remotely) and ends up
    # respawning the daemon, the pod's service name / module pointers /
    # store URL would otherwise contaminate the daemon's env — and
    # LocalBackend seeds every future pod's env from it.
    from .constants import POD_IDENTITY_ENV
    for key in POD_IDENTITY_ENV:
        env.pop(key, None)
    # the controller never owns the chip (its local backend decides which
    # pod does); nothing it imports may open a jax backend on the device
    env["JAX_PLATFORMS"] = "cpu"
    # the subprocess must find this package regardless of the user's cwd
    pkg_parent = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = pkg_parent + os.pathsep + env.get("PYTHONPATH", "")
    with open(os.path.join(config().config_dir, "local-controller.log"),
              "ab") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "kubetorch_tpu.controller.app",
             "--host", "127.0.0.1", "--port", str(port), "--backend", "local"],
            env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True)
    if not wait_for_port("127.0.0.1", port, timeout=30):
        kill_process_tree(proc.pid)
        raise ControllerRequestError("Local controller failed to start")
    state = {"url": f"http://127.0.0.1:{port}", "pid": proc.pid}
    with open(_state_file(), "w") as f:
        json.dump(state, f)
    return state


def shutdown_local_controller() -> None:
    """Stop the local daemon and all its pods (used by tests and
    ``kt controller stop``)."""
    global _client, _daemon_url
    with _lock:
        _client = None
        _daemon_url = None
        state = None
        try:
            import json
            with open(_state_file()) as f:
                state = json.load(f)
        except (OSError, ValueError):
            pass
        if state:
            # only forget the state file once the daemon is provably gone,
            # or a failed stop would orphan a live controller forever
            daemon_gone = _kill_daemon_process(state)
            if daemon_gone:
                try:
                    os.unlink(_state_file())
                except OSError:
                    pass
            else:
                import warnings
                warnings.warn(
                    f"Local controller pid {state['pid']} could not be "
                    f"confirmed stopped; keeping {_state_file()}")
        if config().api_url and "127.0.0.1" in (config().api_url or ""):
            config().api_url = None
