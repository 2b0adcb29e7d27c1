"""Controller backends: how manifests become running pods.

``LocalBackend`` — pods are host subprocesses bound to per-service loopback
alias IPs (127.x.y.z all route to lo on Linux), sharing one port like real
pods do across nodes. This is the kind/minikube-free local story and what the
test suite drives end-to-end.

``KubernetesBackend`` — ``kubectl apply`` of the manifest built by
``provisioning`` (Deployment / JobSet with ``google.com/tpu`` resources).
Gated on kubectl credentials; in-cluster it uses the service-account token.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from typing import Dict, List, Optional

from ..utils.procs import (kill_process_tree, signal_process_tree,
                           wait_for_port)


class PodHandle:
    def __init__(self, name: str, ip: str, process: subprocess.Popen):
        self.name = name
        self.ip = ip
        self.process = process


# manifest kinds that are config objects, not runnable workloads
OBJECT_KINDS = {"Secret", "PersistentVolumeClaim", "ConfigMap"}


def _manifest_kind(manifest: Dict) -> str:
    kind = manifest.get("kind", "Deployment")
    if kind == "Service" and "knative" in manifest.get("apiVersion", ""):
        return "KnativeService"
    return kind


def _pod_specs(manifest: Dict) -> List[Dict]:
    """Locate the pod spec(s) inside a workload manifest (reference
    ``navigate_path``-style kind polymorphism, compute/utils.py:18-54)."""
    kind = _manifest_kind(manifest)
    spec = manifest.get("spec", {})
    if kind == "JobSet":
        return [job.get("template", {}).get("spec", {})
                   .get("template", {}).get("spec", {})
                for job in spec.get("replicatedJobs", [])]
    if kind == "RayCluster":
        head = [spec.get("headGroupSpec", {}).get("template", {})
                    .get("spec", {})]
        workers = [g.get("template", {}).get("spec", {})
                   for g in spec.get("workerGroupSpecs", [])]
        return head + workers
    # Deployment and Knative Service share spec.template.spec
    return [spec.get("template", {}).get("spec", {})]


def requests_tpu(manifest: Dict) -> bool:
    """Does any container of this workload ask for ``google.com/tpu``? That
    request is what ``Compute(tpu=...)`` becomes, and it is the local
    backend's rule for which pod owns the chip."""
    return any("google.com/tpu" in (c.get("resources", {}).get("limits") or {})
               for spec in _pod_specs(manifest)
               for c in spec.get("containers", []))


def default_local_volume_dir(namespace: str, name: str) -> str:
    """Host directory backing a local-mode PVC under the DEFAULT layout
    (``config_dir/volumes``) — the contract client-side ``Volume.ssh``
    resolves against. ``LocalBackend.__init__`` defaults ``volumes_dir`` to
    the same root; a backend constructed with a custom ``volumes_dir`` is
    test-only and unreachable from a remote client anyway."""
    from ..config import config
    return os.path.join(config().config_dir, "volumes", f"{namespace}__{name}")


def controller_wiring(controller_url: str) -> Dict[str, str]:
    """Env vars every pod needs to register with the controller and stream
    logs, derived from the controller's base URL."""
    return {
        "KT_CONTROLLER_WS_URL":
            controller_url.replace("http", "ws", 1) + "/controller/ws/pods",
        "KT_LOG_SINK_URL": controller_url + "/controller/logs",
    }


# libc resolved at import time: the preexec hook runs between fork and exec
# in a multithreaded parent, where `import ctypes`/CDLL could deadlock on
# locks held by other threads at fork time. Only the pre-bound prctl call
# may run there.
try:
    import ctypes as _ctypes
    import signal as _signal

    _LIBC = _ctypes.CDLL("libc.so.6", use_errno=True)
    _LIBC.prctl  # resolve the symbol now
except Exception:
    _LIBC = None
_PR_SET_PDEATHSIG = 1


def _die_with_parent():
    """PR_SET_PDEATHSIG: local pods are children of the controller daemon; if
    the daemon is SIGKILLed (no cleanup runs), orphaned pods would squat the
    per-service IP:port and wedge every revival after restart. Linux-only."""
    _LIBC.prctl(_PR_SET_PDEATHSIG, _signal.SIGTERM)


class LocalBackend:
    """Run 'pods' as subprocesses on loopback alias IPs."""

    def __init__(self, controller_url: str, server_port: int = 32300,
                 store_url: Optional[str] = None,
                 secrets_dir: Optional[str] = None,
                 volumes_dir: Optional[str] = None):
        from ..config import config
        self.controller_url = controller_url
        self.server_port = server_port
        self.store_url = store_url
        self.services: Dict[str, List[PodHandle]] = {}
        self.objects: Dict[str, Dict] = {}   # "Kind/ns/name" → manifest
        self.kinds: Dict[str, str] = {}      # "ns/name" → applied kind
        self._ip_block = 0
        # secret VALUES live only here, as 0600 files under a 0700 dir —
        # never in the manifest, the workload record, or persisted controller
        # state (the k8s backend's analog is a real K8s Secret object)
        self.secrets_dir = secrets_dir or os.path.join(config().config_dir,
                                                       "secrets")
        # local Volume analog: PVCs map to host directories; pods learn the
        # mapping via KT_VOLUME_* env (a subprocess can't bind-mount). The
        # default MUST match default_local_volume_dir — client-side
        # Volume.ssh resolves through that contract
        self.volumes_dir = volumes_dir or os.path.join(config().config_dir,
                                                       "volumes")
        # each pod's stdout/stderr (the rank's included): what a pod wrote
        # before its log capture came up — a rank that could not open the
        # chip — is only ever here
        self.logs_dir = os.path.join(config().config_dir, "logs")

    # -- config objects -------------------------------------------------------

    def get_object(self, kind: str, namespace: str, name: str) -> Optional[Dict]:
        return self.objects.get(f"{kind}/{namespace}/{name}")

    def delete_object(self, kind: str, namespace: str, name: str) -> bool:
        existed = self.objects.pop(f"{kind}/{namespace}/{name}", None) is not None
        if self.kinds.get(f"{namespace}/{name}") == kind:
            self.kinds.pop(f"{namespace}/{name}", None)
        aux = {"Secret": self._secret_dir,
               "PersistentVolumeClaim": self._volume_dir}.get(kind)
        if aux is not None:
            path = aux(namespace, name)
            if os.path.isdir(path):
                shutil.rmtree(path, ignore_errors=True)
                existed = True
        return existed

    def storage_classes(self) -> List[Dict]:
        return [{"name": "local-dir", "default": True,
                 "provisioner": "kubetorch.com/local-dir"}]

    # -- volume store ---------------------------------------------------------

    def _volume_dir(self, namespace: str, name: str) -> str:
        return os.path.join(self.volumes_dir, f"{namespace}__{name}")

    @staticmethod
    def _container_env(manifest: Dict) -> Dict[str, str]:
        """Plain ``{name, value}`` container env from the manifest — the
        kubelet-analog for ``Compute(env={...})``: the K8s backend gets
        these injected by the kubelet, so subprocess pods must see them
        too or user env silently works only on real clusters."""
        env: Dict[str, str] = {}
        for spec in _pod_specs(manifest):
            for container in spec.get("containers", []):
                for entry in container.get("env", []):
                    if entry.get("name") and "value" in entry:
                        env[entry["name"]] = str(entry["value"])
        return env

    def _volume_env(self, namespace: str, manifest: Dict) -> Dict[str, str]:
        """Resolve PVC claims in the pod template to host directories:
        ``KT_VOLUME_<NAME>`` points at the backing dir (and is created on
        first use, the local 'provisioner')."""
        env: Dict[str, str] = {}
        for spec in _pod_specs(manifest):
            for vol in spec.get("volumes", []):
                claim = (vol.get("persistentVolumeClaim") or {}).get("claimName")
                if not claim:
                    continue
                vdir = self._volume_dir(namespace, claim)
                os.makedirs(vdir, exist_ok=True)
                env["KT_VOLUME_" + claim.upper().replace("-", "_")] = vdir
        return env

    # -- secret store ---------------------------------------------------------

    def _secret_dir(self, namespace: str, name: str) -> str:
        return os.path.join(self.secrets_dir, f"{namespace}__{name}")

    def _store_secret(self, namespace: str, name: str, manifest: Dict) -> List[str]:
        data = manifest.get("stringData", {}) or {}
        sdir = self._secret_dir(namespace, name)
        # replace, don't merge: a re-save after credential rotation must not
        # keep injecting keys the new Secret no longer carries
        shutil.rmtree(sdir, ignore_errors=True)
        os.makedirs(sdir, mode=0o700, exist_ok=True)
        os.chmod(sdir, 0o700)
        for key, value in data.items():
            path = os.path.join(sdir, key)
            fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
            with os.fdopen(fd, "w") as f:
                f.write(str(value))
        return sorted(data)

    def _secret_env(self, namespace: str, manifest: Dict) -> Dict[str, str]:
        """Resolve ``envFrom`` secretRefs in the pod template against the
        local secret files — the subprocess-pod analog of kubelet injecting a
        K8s Secret. File-type secrets surface as a PATH (local pods share the
        host filesystem), not as env payload."""
        env: Dict[str, str] = {}
        secret_names = set()
        for spec in _pod_specs(manifest):
            for container in spec.get("containers", []):
                # per-key delivery (the canonical path): valueFrom refs
                for entry in container.get("env", []):
                    key_ref = ((entry.get("valueFrom") or {})
                               .get("secretKeyRef") or {})
                    if key_ref.get("name") and key_ref.get("key"):
                        secret_names.add(key_ref["name"])
                        path = os.path.join(
                            self._secret_dir(namespace, key_ref["name"]),
                            key_ref["key"])
                        if os.path.exists(path):
                            with open(path) as f:
                                env[entry["name"]] = f.read()
                # blanket envFrom (name-only refs): every non-dunder key
                for ref in container.get("envFrom", []):
                    sname = (ref.get("secretRef") or {}).get("name")
                    if not sname:
                        continue
                    secret_names.add(sname)
                    sdir = self._secret_dir(namespace, sname)
                    if not os.path.isdir(sdir):
                        continue
                    for key in os.listdir(sdir):
                        if key.startswith("__"):
                            continue
                        with open(os.path.join(sdir, key)) as f:
                            env[key] = f.read()
                # file-mount payloads surface as a PATH (the volume-mount
                # analog; local pods share the host filesystem)
                for vol in spec.get("volumes", []):
                    sname = (vol.get("secret") or {}).get("secretName")
                    if sname:
                        secret_names.add(sname)
        for sname in secret_names:
            fpath = os.path.join(self._secret_dir(namespace, sname),
                                 "__file__")
            if os.path.exists(fpath):
                # env key carries the BASE secret's name: the payload rides
                # a companion <name>-file object (Secret.save's split)
                base = sname[:-5] if sname.endswith("-file") else sname
                env["KT_SECRET_FILE_" + base.upper().replace("-", "_")] = fpath
        return env

    def _next_ips(self, service_key: str, n: int) -> List[str]:
        existing = [h.ip for h in self.services.get(service_key, [])]
        if len(existing) >= n:
            return existing[:n]
        if existing:
            # grow within the service's block so live pods keep their
            # addresses — an autoscale-up must never restart busy pods
            block = int(existing[0].split(".")[2])
            top = max(int(ip.split(".")[3]) for ip in existing)
            return existing + [f"127.77.{block}.{top + i + 1}"
                               for i in range(n - len(existing))]
        self._ip_block += 1
        block = self._ip_block
        return [f"127.77.{block}.{i + 1}" for i in range(n)]

    def apply(self, namespace: str, name: str, manifest: Dict,
              env: Dict[str, str]) -> Dict:
        key = f"{namespace}/{name}"
        kind = manifest.get("kind", "Deployment")
        self.kinds[key] = kind
        if kind in OBJECT_KINDS:
            # store config objects instead of spawning pods for them
            if kind == "Secret":
                # values go to 0600 files; memory keeps key NAMES only
                keys = self._store_secret(namespace, name, manifest)
                manifest = {**{k: v for k, v in manifest.items()
                               if k not in ("stringData", "data")},
                            "keys": keys}
            elif kind == "PersistentVolumeClaim":
                os.makedirs(self._volume_dir(namespace, name), exist_ok=True)
            self.objects[f"{kind}/{key}"] = manifest
            return {"kind": kind, "stored": True}
        if kind == "RayCluster":
            # head + workers; the KubeRay group structure maps to N local
            # subprocess pods like any other workload
            replicas = 1 + sum(
                int(g.get("replicas", 0)) for g in
                manifest.get("spec", {}).get("workerGroupSpecs", []))
        else:
            replicas = int(manifest.get("spec", {}).get("replicas", 1))
        ips = self._next_ips(key, replicas)

        # slot-indexed reconciliation: pod i owns ips[i]; dead or surplus
        # slots are respawned/reaped individually so a crashed pod is
        # actually replaced rather than shadowed by a survivor's address.
        existing = {h.ip: h for h in self.services.get(key, [])}
        for ip, h in list(existing.items()):
            if h.process.poll() is not None or ip not in ips[:replicas]:
                if h.process.poll() is None:
                    kill_process_tree(h.process.pid)
                existing.pop(ip)

        pod_env = dict(os.environ)
        # Who owns the chip: the pod whose Compute names a TPU. A chip
        # belongs to one process, so every other pod is held to the CPU
        # explicitly — left to jax's own search, any pod whose code imports
        # jax would race for the device (and, on a machine without one, land
        # on the CPU without an error). "tpu" first makes a failed TPU init
        # raise instead of falling back; the rank checks it before loading
        # (serving/process_worker.require_accelerator). An explicit
        # Compute(env=...) still wins through the container-env overlay.
        pod_env["JAX_PLATFORMS"] = "tpu,cpu" if requests_tpu(manifest) else "cpu"
        # Never inherit ANOTHER pod's identity/wiring: if this controller was
        # itself started from a pod environment (unguarded user driver code
        # importing kt inside a worker), os.environ carries that pod's
        # service name, module pointers, and store URL — the overlay below
        # must start from a clean slate or stale values (a dead store URL
        # especially) poison every pod this backend ever spawns.
        from ..constants import POD_IDENTITY_ENV
        for stale in POD_IDENTITY_ENV:
            pod_env.pop(stale, None)
        pod_env.update(self._container_env(manifest))
        pod_env.update(self._secret_env(namespace, manifest))
        pod_env.update(self._volume_env(namespace, manifest))
        pod_env.update(env)
        pod_env.update({
            "LOCAL_IPS": ",".join(ips[:replicas]),
            "KT_SERVER_PORT": str(self.server_port),
            **controller_wiring(self.controller_url),
            "KT_NAMESPACE": namespace,
            "KT_SERVICE_NAME": name,
        })
        if self.store_url:
            # the POD_IDENTITY_ENV scrub above already dropped any stale
            # inherited value, so setdefault resolves cleanly: an explicit
            # per-service overlay (the ``env`` dict) wins, the backend's own
            # store is the default
            pod_env.setdefault("KT_DATA_STORE_URL", self.store_url)

        handles = []
        os.makedirs(self.logs_dir, exist_ok=True)
        for i, ip in enumerate(ips[:replicas]):
            if ip in existing:
                handles.append(existing[ip])
                continue
            p_env = dict(pod_env)
            p_env["POD_IP"] = ip
            p_env["POD_NAME"] = f"{name}-{i}"
            with open(os.path.join(self.logs_dir,
                                   f"{namespace}__{name}-{i}.log"), "ab") as log:
                proc = subprocess.Popen(
                    [sys.executable, "-m",
                     "kubetorch_tpu.serving.http_server",
                     "--host", ip, "--port", str(self.server_port)],
                    env=p_env, stdout=log, stderr=subprocess.STDOUT,
                    preexec_fn=_die_with_parent if _LIBC is not None else None)
            handles.append(PodHandle(f"{name}-{i}", ip, proc))
        self.services[key] = handles
        for h in handles:
            wait_for_port(h.ip, self.server_port, timeout=30)
        # replicas=0 (scale-to-zero) leaves no pods and no URL; the
        # controller proxy cold-starts on the next request
        return {"service_url": (f"http://{handles[0].ip}:{self.server_port}"
                                if handles else None),
                "pod_ips": [h.ip for h in handles]}

    def delete(self, namespace: str, name: str,
               kind: Optional[str] = None) -> bool:
        key = f"{namespace}/{name}"
        handles = self.services.pop(key, [])
        for h in handles:
            if h.process.poll() is None:
                kill_process_tree(h.process.pid)
        # Only sweep the config object the deleted WORKLOAD itself was —
        # an independent Secret/PVC that merely shares a name with a deleted
        # service must keep its stored values. The controller passes the
        # record's manifest kind (durable, so correct even after a restart);
        # the in-memory kinds map is a fallback for direct backend use. A
        # name-only delete with no known kind removes pods only — never a
        # config object. delete_object owns the aux-dir cleanup per kind.
        kind = kind or self.kinds.get(key)
        if self.kinds.get(key) == kind:
            self.kinds.pop(key, None)
        removed_obj = (kind in OBJECT_KINDS
                       and self.delete_object(kind, namespace, name))
        return bool(handles) or removed_obj

    def pod_ips(self, namespace: str, name: str) -> List[str]:
        return [h.ip for h in self.services.get(f"{namespace}/{name}", [])
                if h.process.poll() is None]

    def signal_pods(self, namespace: str, name: str, sig: int,
                    grace_s: float = 0.0) -> int:
        """Deliver ``sig`` to every pod's whole process tree — the local
        analog of the kubelet's preemption SIGTERM reaching each container
        (rank workers flip their cooperative drain flag and flush a
        committed checkpoint; see ``serving/elastic.py``). No SIGKILL
        escalation here: the scheduler owns the grace window, and its
        eviction (apply replicas=0 → slot reconciliation) is the backstop
        for pods that ignore the signal. Returns pods signaled."""
        signaled = 0
        for h in self.services.get(f"{namespace}/{name}", []):
            if h.process.poll() is None:
                if signal_process_tree(h.process.pid, sig):
                    signaled += 1
        return signaled

    def shutdown(self) -> None:
        for key in list(self.services):
            ns, name = key.split("/", 1)
            self.delete(ns, name)
        store_proc = getattr(self, "_store_proc", None)
        if store_proc is not None and store_proc.poll() is None:
            kill_process_tree(store_proc.pid)


def _event_epoch(item: Dict) -> float:
    """Event time as epoch seconds; 0.0 when the item carries none (then
    the watcher treats it as fresh). K8s events stamp ``lastTimestamp``
    (or ``eventTime`` for the events.k8s.io shape) in RFC3339 Z form."""
    from datetime import datetime, timezone
    raw = (item.get("lastTimestamp") or item.get("eventTime")
           or item.get("firstTimestamp"))
    if not raw:
        return 0.0
    try:
        return datetime.fromisoformat(
            str(raw).replace("Z", "+00:00")).astimezone(
                timezone.utc).timestamp()
    except ValueError:
        return 0.0


class KubernetesBackend:
    """kubectl-applied manifests. Requires cluster credentials (or a kubectl
    shim — the test suite drives this path end-to-end with a recording fake,
    ``tests/assets/fake_kubectl.py``).

    Reference analog: the closed-source controller's K8s apply path
    (``provisioning/service_manager.py:387-673``). Beyond applying the
    workload manifest itself, a deploy also needs routable Services: a
    ClusterIP Service fronting the pods and a headless Service for rank
    discovery (reference ``createHeadlessService`` in the workload CRD).
    Knative creates its own route, so only the headless Service is added
    there."""

    # kubectl resource names per manifest kind, for deletes
    _KIND_RESOURCES = {
        "Deployment": "deployment",
        "JobSet": "jobsets.jobset.x-k8s.io",
        "KnativeService": "services.serving.knative.dev",
        "RayCluster": "rayclusters.ray.io",
        "Secret": "secret",
        "PersistentVolumeClaim": "pvc",
        "ConfigMap": "configmap",
    }

    def __init__(self, kubectl: Optional[str] = None):
        from ..exceptions import KubernetesCredentialsError
        from ..utils.kubectl import resolve_kubectl
        self.kubectl = resolve_kubectl(kubectl)
        if self.kubectl is None:
            raise KubernetesCredentialsError(
                "kubectl not found; KubernetesBackend unavailable")
        self.kinds: Dict[str, str] = {}  # "ns/name" -> applied manifest kind

    @staticmethod
    def available() -> bool:
        from ..utils.kubectl import resolve_kubectl
        kubectl = resolve_kubectl()
        if kubectl is None:
            return False
        try:
            return subprocess.run(
                [kubectl, "auth", "can-i", "create", "deployments"],
                capture_output=True, timeout=10).returncode == 0
        except Exception:
            return False

    def _run(self, *args: str, input_data: Optional[str] = None) -> str:
        try:
            res = subprocess.run([self.kubectl, *args], capture_output=True,
                                 text=True, input=input_data, timeout=120)
        except subprocess.TimeoutExpired as e:
            raise RuntimeError(f"kubectl {' '.join(args)} timed out "
                               f"after {e.timeout:.0f}s") from e
        if res.returncode != 0:
            raise RuntimeError(f"kubectl {' '.join(args)} failed: {res.stderr}")
        return res.stdout

    _manifest_kind = staticmethod(_manifest_kind)
    _pod_specs = staticmethod(_pod_specs)

    def _inject_env(self, manifest: Dict, env: Dict[str, str]) -> None:
        """Merge workload metadata env + in-cluster wiring into every
        container, without overriding explicitly-set manifest values. Pods
        need KT_CONTROLLER_WS_URL / KT_LOG_SINK_URL to register and stream
        logs — LocalBackend passes these through the subprocess environment;
        here they ride the manifest."""
        cluster_url = os.environ.get(
            "KT_CLUSTER_CONTROLLER_URL",
            "http://kubetorch-controller.kubetorch.svc.cluster.local:8080")
        wired = {
            **controller_wiring(cluster_url),
            # bootstrap pods pull the framework tree from here; also the
            # pod-side data plane (kt.put/get, code sync)
            "KT_DATA_STORE_URL": os.environ.get(
                "KT_DATA_STORE_URL",
                "http://kubetorch-data-store.kubetorch.svc.cluster.local:8873"),
            **env,
        }
        for pod_spec in self._pod_specs(manifest):
            for container in pod_spec.get("containers", []):
                have = {e["name"] for e in container.setdefault("env", [])}
                container["env"].extend(
                    {"name": k, "value": v} for k, v in sorted(wired.items())
                    if k not in have)

    def apply(self, namespace: str, name: str, manifest: Dict,
              env: Dict[str, str]) -> Dict:
        kind = self._manifest_kind(manifest)
        if kind not in OBJECT_KINDS:
            self._inject_env(manifest, env)
        self._run("apply", "-n", namespace, "-f", "-",
                  input_data=json.dumps(manifest))
        self.kinds[f"{namespace}/{name}"] = kind
        if kind in OBJECT_KINDS:
            return {"kind": kind, "stored": True}

        from ..provisioning.manifests import build_service_manifest
        if kind != "KnativeService":  # Knative provisions its own route
            self._run("apply", "-n", namespace, "-f", "-",
                      input_data=json.dumps(
                          build_service_manifest(name, namespace)))
        self._run("apply", "-n", namespace, "-f", "-",
                  input_data=json.dumps(
                      build_service_manifest(name, namespace, headless=True)))
        # best-effort: pods are usually still Pending right after apply, and
        # a transient kubectl failure must not fail a deploy that succeeded
        try:
            pod_ips = self.pod_ips(namespace, name)
        except RuntimeError:
            pod_ips = []
        return {"service_url":
                f"http://{name}.{namespace}.svc.cluster.local:32300",
                "pod_ips": pod_ips}

    def delete(self, namespace: str, name: str,
               kind: Optional[str] = None) -> bool:
        key = f"{namespace}/{name}"
        kind = kind or self.kinds.get(key)
        if self.kinds.get(key) == kind:
            self.kinds.pop(key, None)
        # Unknown kind (controller restarted AND no durable record): sweep
        # only WORKLOAD kinds. Config objects are never destroyed on a
        # name-only delete — an independent Secret/PVC may share the name,
        # and their deletion routes through delete_object explicitly. A
        # Secret/PVC deployed AS a workload always has a durable record
        # whose manifest kind the controller passes in.
        resources = ([self._KIND_RESOURCES.get(kind, kind.lower())] if kind
                     else [r for k, r in self._KIND_RESOURCES.items()
                           if k not in OBJECT_KINDS])
        if kind not in OBJECT_KINDS:
            resources += [f"service/{name}", f"service/{name}-headless"]
        ok = True
        for resource in resources:
            args = (resource.split("/") if "/" in resource
                    else [resource, name])
            try:
                self._run("delete", *args, "-n", namespace,
                          "--ignore-not-found")
            except RuntimeError as e:
                # a cluster without the JobSet/Knative CRDs answers the
                # sweep with "the server doesn't have a resource type" even
                # under --ignore-not-found; that must not abort the sweep
                # or the remaining kinds leak
                msg = str(e).lower()
                if ("doesn't have a resource type" in msg
                        or "could not find the requested resource" in msg
                        or "not found" in msg):
                    continue
                ok = False
        return ok

    def pod_ips(self, namespace: str, name: str) -> List[str]:
        out = self._run("get", "pods", "-n", namespace, "-l",
                        f"kubetorch.com/service={name}", "-o",
                        "jsonpath={.items[*].status.podIP}")
        return [ip for ip in out.split() if ip]

    def signal_pods(self, namespace: str, name: str, sig: int,
                    grace_s: float = 0.0) -> int:
        """Graceful pod termination via the kubelet's own contract:
        ``kubectl delete pods --grace-period=N --wait=false`` delivers
        SIGTERM now and SIGKILL after the grace window — exactly the
        sequence the scheduler's drain path expects. ``sig`` is accepted
        for interface parity but K8s only speaks TERM-then-KILL."""
        ips = self.pod_ips(namespace, name)
        if not ips:
            return 0
        self._run("delete", "pods", "-n", namespace, "-l",
                  f"kubetorch.com/service={name}",
                  f"--grace-period={max(1, int(grace_s or 30))}",
                  "--wait=false", "--ignore-not-found")
        return len(ips)

    def pod_events(self, namespace: str) -> List[Dict]:
        """Recent Pod events in the namespace, normalized to
        ``{uid, count, pod, type, reason, message}``.

        Reference analog: the controller-side event watcher
        (``charts/kubetorch/values.yaml`` eventWatcher) feeding the live
        event stream ``.to()`` shows while waiting
        (``python_client/kubetorch/serving/http_client.py:576``). The
        controller's ``_k8s_events_loop`` polls this and routes events to
        workloads by pod-name prefix."""
        try:
            # server-side kind filter: a busy namespace carries thousands of
            # non-Pod events the 2s poll would otherwise fetch+parse+discard
            out = self._run("get", "events", "-n", namespace,
                            "--field-selector", "involvedObject.kind=Pod",
                            "-o", "json")
            items = json.loads(out).get("items", [])
        except (RuntimeError, ValueError):
            return []
        events: List[Dict] = []
        for it in items:
            obj = it.get("involvedObject", {})
            if obj.get("kind") != "Pod":
                continue
            events.append({
                "uid": (it.get("metadata", {}).get("uid")
                        or f"{obj.get('name')}/{it.get('reason')}"),
                "count": int(it.get("count") or 1),
                "pod": obj.get("name", ""),
                "type": it.get("type", "Normal"),
                "reason": it.get("reason", ""),
                "message": (it.get("message") or "").strip(),
                "ts": _event_epoch(it),
            })
        return events

    # -- config objects -------------------------------------------------------

    def get_object(self, kind: str, namespace: str, name: str) -> Optional[Dict]:
        resource = self._KIND_RESOURCES.get(kind, kind.lower())
        try:
            out = self._run("get", resource, name, "-n", namespace,
                            "-o", "json")
        except RuntimeError as e:
            if "not found" in str(e).lower():
                return None
            raise
        return json.loads(out)

    def delete_object(self, kind: str, namespace: str, name: str) -> bool:
        resource = self._KIND_RESOURCES.get(kind, kind.lower())
        existed = self.get_object(kind, namespace, name) is not None
        # --wait=false: an in-use PVC blocks on the pvc-protection finalizer
        # until kubectl's timeout; the CLIENT owns the Terminating poll
        # (Volume.delete wait=), the controller thread must return promptly
        self._run("delete", resource, name, "-n", namespace,
                  "--ignore-not-found", "--wait=false")
        self.kinds.pop(f"{namespace}/{name}", None)
        return existed

    def storage_classes(self) -> List[Dict]:
        items = json.loads(self._run("get", "storageclass", "-o",
                                     "json")).get("items", [])
        default_anno = "storageclass.kubernetes.io/is-default-class"
        return [{"name": it["metadata"]["name"],
                 "default": it["metadata"].get("annotations", {})
                                          .get(default_anno) == "true",
                 "provisioner": it.get("provisioner")}
                for it in items]

    def shutdown(self) -> None:
        pass
