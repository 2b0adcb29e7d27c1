"""Module: the deployable wrapper around a user callable.

Reference (``resources/callables/module.py``): ``.to(compute)`` is the
product's core verb — extract pointers, sync code, assemble metadata, launch
through the controller, wait for health — and a second ``.to()`` with the
same name is the 1-2s hot-reload loop (SURVEY §3.1/§3.4).
"""

from __future__ import annotations

import os
import time
import uuid
from typing import Any, Dict, Optional

from .. import telemetry
from ..client import controller_client
from ..config import config
from ..constants import READY_WAIT_CAP_S
from ..exceptions import ServiceHealthError, ServiceTimeoutError
from ..serving.http_client import HTTPClient
from ..utils.naming import service_name_for
from .compute import Compute
from .pointers import Pointers, extract_pointers

# a /ready that stayed open this long was held by the pod (or by a pod too
# busy to answer): an answer at once takes milliseconds
_HELD_FROM_S = 0.1


def extract_call_config(kwargs: Dict[str, Any],
                        **seeds: Any) -> Dict[str, Any]:
    """Pop TYPED per-call config objects (kt.MetricsConfig /
    kt.LoggingConfig / kt.DebugConfig) out of a remote call's kwargs —
    keyed by TYPE, not name, so they work on any proxy (Fn, Cls methods,
    actors) without reserving kwarg names: a plain dict named ``metrics``
    still reaches the remote function. To send one of these types TO the
    remote function (pickle serialization), pass it positionally.

    ``seeds`` are configs already captured by a proxy's named params (Fn's
    ``metrics=``/``logging=``/``debugger=``); a second config of the same
    type is ambiguous and raises — never silently dropped."""
    from ..config import DebugConfig, LoggingConfig, MetricsConfig

    slot_for = {MetricsConfig: "metrics", LoggingConfig: "logging",
                DebugConfig: "debugger"}
    out: Dict[str, Any] = {"metrics": None, "logging": None, "debugger": None}
    out.update({k: v for k, v in seeds.items() if v is not None})
    for key in list(kwargs):
        for cfg_type, slot in slot_for.items():
            if isinstance(kwargs[key], cfg_type):
                if out[slot] is not None:
                    raise ValueError(
                        f"two {cfg_type.__name__} objects in one call "
                        f"(kwarg {key!r}) — pass exactly one")
                out[slot] = kwargs.pop(key)
                break
    return out


class Module:
    callable_type = "fn"

    def __init__(self, pointers: Pointers, name: Optional[str] = None,
                 init_args: Optional[Dict] = None):
        self.pointers = pointers
        self.name = service_name_for(pointers.cls_or_fn_name,
                                     username=config().username, name=name)
        self._explicit_name = name is not None
        self.init_args = init_args
        self.compute: Optional[Compute] = None
        self.service_url: Optional[str] = None
        self.launch_id: Optional[str] = None
        self._client: Optional[HTTPClient] = None

    # -- deploy ---------------------------------------------------------------

    def to(self, compute: Compute, name: Optional[str] = None,
           sync_code: bool = True) -> "Module":
        """Deploy (or hot-reload) this callable onto the given compute."""
        if name:
            self.name = service_name_for(self.pointers.cls_or_fn_name,
                                         username=config().username, name=name)
            self._explicit_name = True
        # Self-deploy guard: a pod worker importing the user's module runs
        # its top level — an unguarded driver script would re-deploy THIS
        # service from inside its own pod and then health-wait on itself
        # forever (the warmup can't finish while the import is blocked).
        # Deploying a DIFFERENT service from a pod is legitimate (nested
        # pipelines); deploying yourself never is. Same discipline torch
        # multiprocessing demands: guard driver code with
        # ``if __name__ == "__main__":``. Matching uses what the POD knows:
        # the recomputed name alone fails open whenever the in-pod username
        # differs from the deployer's (config().username feeds the name),
        # so the module pointers this pod was deployed FROM count too —
        # unless the caller chose a different explicit name, which is the
        # legitimate "replica of my own class" pattern.
        if os.environ.get("POD_NAME") and os.environ.get("KT_SERVICE_NAME"):
            same_name = os.environ.get("KT_SERVICE_NAME") == self.name
            same_callable = (
                not self._explicit_name
                and os.environ.get("KT_CLS_OR_FN_NAME")
                == self.pointers.cls_or_fn_name
                and os.environ.get("KT_MODULE_NAME")
                == self.pointers.module_name)
            if same_name or same_callable:
                raise RuntimeError(
                    f"refusing to deploy service {self.name!r} from inside "
                    f"pod {os.environ['POD_NAME']!r} of service "
                    f"{os.environ['KT_SERVICE_NAME']!r} — this almost always "
                    "means the module's top-level driver code ran on import; "
                    "guard it with `if __name__ == \"__main__\":`")
        self.compute = compute
        launch_id = uuid.uuid4().hex

        # one span for the deploy, a child for each of its waits; the pod's
        # own boot phases come back in the last /ready (ISSUE 26), so the
        # caller's ring alone says where a deploy's seconds went
        with telemetry.span("client.deploy", service=self.name) as sp:
            if sync_code:
                with telemetry.span("deploy.sync_code"):
                    self._sync_code()
            with telemetry.span("deploy.launch"):
                result = compute._launch(self.name, self._metadata(),
                                         launch_id)
            self.launch_id = result.get("launch_id", launch_id)
            self.service_url = result.get("service_url")
            compute._check_service_ready(self.name)
            boot = self._wait_for_http_health()
            for phase, seconds in (boot or {}).items():
                if phase == "ready_for_s":
                    # how long the service had been ready when the poll
                    # noticed: what the back-off cost this deploy
                    sp.set_attr("poll_slack_s", seconds)
                else:
                    sp.set_attr("boot." + phase, seconds)
        return self

    async def to_async(self, compute: Compute, **kwargs) -> "Module":
        import asyncio
        return await asyncio.to_thread(self.to, compute, **kwargs)

    def _metadata(self) -> Dict[str, Any]:
        meta: Dict[str, Any] = {
            "KT_PROJECT_ROOT": self._remote_root(),
            "KT_MODULE_NAME": self.pointers.module_name,
            "KT_FILE_PATH": self.pointers.file_path,
            "KT_CLS_OR_FN_NAME": self.pointers.cls_or_fn_name,
            "KT_CALLABLE_TYPE": self.callable_type,
            "KT_SERVICE_NAME": self.name,
        }
        if self.init_args:
            meta["KT_INIT_ARGS"] = self.init_args
        if self.compute and self.compute.distributed is not None:
            meta["KT_DISTRIBUTED_CONFIG"] = self.compute.distributed.to_dict()
        if self.compute:
            meta["KT_DOCKERFILE"] = self.compute.image.dockerfile()
        ser_cfg = config().serialization
        if ser_cfg and ser_cfg != "json":
            meta["KT_ALLOWED_SERIALIZATION"] = f"json,msgpack,none,{ser_cfg}"
        return meta

    def _remote_root(self) -> str:
        """Where the pod finds the synced project tree. Local backend pods
        share this filesystem, so the local root is directly importable; real
        pods pull from the data store to /kt/app."""
        if config().api_url and "127.0.0.1" in config().api_url:
            return self.pointers.project_root
        if config().local_mode or not config().api_url:
            return self.pointers.project_root
        return "/kt/app"

    def _sync_code(self) -> None:
        """Ship the working dir to the data store (reference SURVEY §3.1
        RSYNC step). No-op when pods share our filesystem (local backend) or
        no data store is configured."""
        store = config().data_store_url
        if not store:
            return
        from ..data_store.sync import push_tree
        push_tree(store, f"__code__/{self.name}", self.pointers.project_root)

    # -- health ---------------------------------------------------------------

    def _wait_for_http_health(self, timeout: Optional[float] = None
                              ) -> Optional[Dict[str, Any]]:
        """Poll /ready?launch_id until the deployed launch answers
        (reference ``_wait_for_http_health`` :1424). Returns the pod's
        ``boot`` record when it sent one."""
        if self.service_url is None:
            record = controller_client().get_workload(
                self.compute.namespace, self.name)
            self.service_url = record.get("service_url")
        if self.service_url is None:
            if self._scaled_to_zero():
                return None
            raise ServiceHealthError(f"No service URL for {self.name!r}")
        client = self._http_client()
        deadline = time.monotonic() + (timeout or
                                       (self.compute.launch_timeout
                                        if self.compute else 900))
        delay = 0.2
        with telemetry.span("deploy.wait_ready", polls=0, last_delay_s=0.0,
                            held_polls=0, held_s=0.0) as sp:
            polls = held_polls = 0
            held_s = 0.0
            while (left := deadline - time.monotonic()) > 0:
                polls += 1
                sp.set_attr("polls", polls)
                # the pod waits, not this loop: it holds the request while
                # the launch is loading and answers when it is warm
                wait = min(READY_WAIT_CAP_S, left)
                asked = time.monotonic()
                body = client.ready_body(self.launch_id, wait=wait)
                took = time.monotonic() - asked
                if took >= _HELD_FROM_S:
                    held_polls += 1
                    held_s += took
                    sp.set_attr("held_polls", held_polls)
                    sp.set_attr("held_s", round(held_s, 3))
                if body is not None:
                    return body.get("boot")
                if self._scaled_to_zero():
                    # an autoscaled service with no pods is
                    # healthy-by-design: launch completed, then the idle
                    # window elapsed; the first call cold-starts it through
                    # the controller proxy
                    return None
                if took >= wait:
                    # held for all of ``wait``: still loading, ask again
                    continue
                # "not yet" sooner than asked for: a pod that does not hold
                # (an older one, a proxy that drops ``wait``), a launch
                # that cannot become ready, or nothing listening yet
                time.sleep(delay)
                sp.set_attr("last_delay_s", delay)
                delay = min(delay * 2, 3.0)
        raise ServiceTimeoutError(
            f"Service {self.name!r} at {self.service_url} never became ready "
            f"for launch {self.launch_id}")

    def _scaled_to_zero(self) -> bool:
        """True only for DELIBERATE zero-pod states — the autoscaler reaped
        an idle service, or the deploy asked for initial_scale=0. Pods that
        crashed at boot leave neither marker, so a broken deploy still
        surfaces as the health-wait timeout it is."""
        if self.compute is None or self.compute.autoscaling is None:
            return False
        try:
            record = controller_client().get_workload(
                self.compute.namespace, self.name)
        except Exception:
            return False
        if record.get("pod_ips"):
            return False
        return (bool(record.get("scaled_to_zero"))
                or record.get("expected_pods") == 0)

    @property
    def is_deployed(self) -> bool:
        """True once this module has a route to the service: a pod URL, or a
        completed launch whose calls go through the controller proxy (an
        ``initial_scale=0`` / scaled-to-zero service never has a pod URL —
        the proxy cold-starts it on first call). launch_id is only set after
        ``_launch`` returns, so a deploy that raised mid-flight still reads
        as not deployed."""
        return self.service_url is not None or self.launch_id is not None

    def _http_client(self) -> HTTPClient:
        from ..config import config as _config
        from ..constants import DEFAULT_SERVER_PORT
        ns = self.compute.namespace if self.compute else "default"
        # the controller-proxy route doubles as the cold-start activator
        # for scaled-to-zero services (nothing listens at service_url —
        # which may itself be None after a scale-to-zero: then the proxy IS
        # the base URL)
        proxy = (f"{_config().api_url}/{ns}/{self.name}:"
                 f"{DEFAULT_SERVER_PORT}" if _config().api_url else None)
        base = self.service_url or proxy
        if base is None:
            raise ServiceHealthError(
                f"No service URL for {self.name!r} and no controller "
                "configured to route through")
        if self._client is None or self._client.base_url != base.rstrip("/"):
            self._client = HTTPClient(base, proxy_url=proxy,
                                      service=self.name)
        return self._client

    # -- lifecycle ------------------------------------------------------------

    @classmethod
    def from_name(cls, name: str, namespace: Optional[str] = None) -> "Module":
        """Reattach to a deployed service (reference ``from_name`` :338)."""
        record = controller_client().get_workload(
            namespace or config().namespace, name)
        meta = record.get("metadata", {})
        pointers = Pointers(
            project_root=meta.get("KT_PROJECT_ROOT", ""),
            module_name=meta.get("KT_MODULE_NAME", ""),
            file_path=meta.get("KT_FILE_PATH", ""),
            cls_or_fn_name=meta.get("KT_CLS_OR_FN_NAME", ""),
        )
        mod = cls.__new__(cls)
        Module.__init__(mod, pointers, name=name)
        mod.name = name
        mod.service_url = record.get("service_url")
        mod.launch_id = record.get("launch_id")
        return mod

    def teardown(self) -> None:
        controller_client().delete_workload(
            self.compute.namespace if self.compute else config().namespace,
            self.name)
        self.service_url = None
        self.launch_id = None
        self._client = None

    # -- pod ops (reference compute.py:2400-2493) ------------------------------

    @property
    def namespace(self) -> str:
        return self.compute.namespace if self.compute else config().namespace

    def pod_ips(self) -> list:
        """Live pod addresses of this service, from the controller."""
        record = controller_client().get_workload(self.namespace, self.name)
        return record.get("pod_ips") or []

    def _pod_exec_targets(self, node) -> list:
        """Resolve ``node`` to (ip, base_url, headers) per target pod.
        ``node``: None/"all" → every pod; int → pod index; str ip; list of
        either. Local-backend pods are directly reachable; otherwise the
        exec rides the controller proxy with pod-targeted routing."""
        ips = self.pod_ips()
        if not ips:
            raise ServiceHealthError(f"{self.name!r} has no running pods")
        if node in (None, "all"):
            chosen = ips
        else:
            nodes = node if isinstance(node, list) else [node]
            chosen = [ips[n] if isinstance(n, int) else n for n in nodes]
            unknown = [ip for ip in chosen if ip not in ips]
            if unknown:
                raise ValueError(f"not pods of {self.name!r}: {unknown}")
        from ..constants import DEFAULT_SERVER_PORT, server_port
        out = []
        for ip in chosen:
            if config().api_url and "127.0.0.1" not in config().api_url:
                base = (f"{config().api_url}/{self.namespace}/"
                        f"{self.name}:{DEFAULT_SERVER_PORT}")
                out.append((ip, base, {"X-KT-Pod-IP": ip}))
            else:
                out.append((ip, f"http://{ip}:{server_port()}", {}))
        return out

    def run_bash(self, commands, node=None, timeout: float = 600) -> list:
        """Run shell command(s) on pod(s); returns ``[(rc, stdout, stderr)]``
        per target pod (reference ``run_bash`` compute.py:2478; transport is
        the pod server's ``/_kt/exec`` instead of ``kubectl exec``, so it
        works identically on the local backend and through the controller
        proxy)."""
        import requests as _requests

        cmds = commands if isinstance(commands, list) else [commands]
        results = []
        for ip, base, headers in self._pod_exec_targets(node):
            for cmd in cmds:
                r = _requests.post(f"{base}/_kt/exec",
                                   json={"cmd": cmd, "timeout": timeout},
                                   headers=headers, timeout=timeout + 30)
                r.raise_for_status()
                body = r.json()
                results.append((body["rc"], body["stdout"], body["stderr"]))
        return results

    def pip_install(self, reqs, node=None,
                    override_remote_version: bool = False) -> None:
        """Pip-install packages onto the pod(s) (reference ``pip_install``
        compute.py:2423): skips packages already importable remotely unless
        ``override_remote_version`` pins the local version."""
        reqs = [reqs] if isinstance(reqs, str) else reqs
        for req in reqs:
            target = req
            mod_name = req.split("[")[0].replace("-", "_")
            if not override_remote_version:
                probe = self.run_bash(
                    f"python3 -c \"import importlib.util,sys; "
                    f"sys.exit(0 if importlib.util.find_spec('{mod_name}') "
                    f"else 1)\"", node=node)
                if all(rc == 0 for rc, _, _ in probe):
                    continue
            else:
                try:
                    from importlib.metadata import version as _v
                    target = f"{req}=={_v(mod_name)}"
                except Exception:
                    pass
            self.run_bash(f"python3 -m pip install {target}", node=node)

    def ssh(self, pod_name: Optional[str] = None) -> None:
        """Interactive shell into a pod (reference ``ssh`` compute.py:2400).
        Cluster mode execs via kubectl; on the local backend pods are host
        subprocesses, so this opens a shell in the service's synced root."""
        import subprocess

        from ..utils.kubectl import resolve_kubectl

        local = not config().api_url or "127.0.0.1" in config().api_url
        kubectl = None if local else resolve_kubectl()
        if kubectl:
            pod = pod_name or f"{self.name}-0"
            subprocess.run([kubectl, "exec", "-it", pod,
                            "-n", self.namespace, "--", "/bin/bash"],
                           check=True)
            return
        root = self.pointers.project_root or os.getcwd()
        subprocess.run(["/bin/bash"], cwd=root,
                       env={**os.environ, "KT_SERVICE_NAME": self.name})


def module_factory(obj: Any, name: Optional[str] = None,
                   init_args: Optional[Dict] = None,
                   cls_type: type = Module) -> Module:
    pointers = extract_pointers(obj)
    return cls_type(pointers, name=name, init_args=init_args)
