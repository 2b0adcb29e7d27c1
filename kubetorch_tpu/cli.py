"""``kt`` CLI (reference ``cli.py``, 2933 LoC, typer → click here).

Command surface parity (reference line refs in SURVEY §2.10): check, config,
deploy, call, describe, list, apply, run, debug, ssh, teardown, logs,
put/get/ls/rm, secrets, volumes, workload, port-forward, server start.
Run as ``python -m kubetorch_tpu.cli`` (or install the ``kt`` entry point).
"""

from __future__ import annotations

import json
import os
import sys
from typing import Optional

import click

from .config import config as kt_config, reset_config


@click.group()
def cli():
    """kubetorch-tpu: TPU-native compute dispatch."""


# -- check -------------------------------------------------------------------


@cli.command()
def check():
    """Doctor: verify client, controller, backend, and TPU visibility."""
    cfg = kt_config()
    click.echo(f"config file      : {cfg.config_dir}/config")
    click.echo(f"namespace        : {cfg.namespace}")
    click.echo(f"api_url          : {cfg.api_url or '(local controller)'}")
    try:
        from .client import controller_client
        client = controller_client()
        click.echo(f"controller       : OK ({client.base_url}, "
                   f"v{client.version()})")
    except Exception as e:
        click.echo(f"controller       : UNREACHABLE ({e})")
    try:
        from .controller.backends import KubernetesBackend
        k8s = KubernetesBackend.available()
        click.echo(f"kubernetes       : {'available' if k8s else 'not configured'}")
    except Exception:
        click.echo("kubernetes       : not configured")
    try:
        from .client import controller_client
        store = controller_client().cluster_config().get("data_store_url")
        if store:
            import requests as _requests
            r = _requests.get(f"{store}/health", timeout=3)
            click.echo(f"data store       : "
                       f"{'OK' if r.status_code == 200 else r.status_code} "
                       f"({store})")
        else:
            click.echo("data store       : not configured")
    except Exception as e:
        click.echo(f"data store       : UNREACHABLE ({e})")
    from .native import available as native_available, blobd_available
    click.echo(f"native runtime   : "
               f"lib={'OK' if native_available() else 'not built'}  "
               f"blobd={'OK' if blobd_available() else 'not built'} "
               f"(make -C kubetorch_tpu/native)")
    # accelerator probe in a SUBPROCESS with a hard timeout: the CLI itself
    # stays off jax, and a chip held by a running rank can hang backend init
    # — a doctor that hangs diagnoses nothing
    import subprocess as _subprocess
    import sys as _sys
    try:
        probe = _subprocess.run(
            [_sys.executable, "-c",
             "import jax; print([str(d) for d in jax.devices()])"],
            capture_output=True, text=True, timeout=30)
        if probe.returncode == 0:
            click.echo(f"accelerators     : {probe.stdout.strip()}")
        else:
            err_lines = probe.stderr.strip().splitlines()
            reason = (err_lines[-1][:120] if err_lines
                      else f"probe exited rc={probe.returncode}")
            click.echo(f"accelerators     : ERROR ({reason})")
    except _subprocess.TimeoutExpired:
        click.echo("accelerators     : TIMEOUT after 30s (TPU held by "
                   "another process or unavailable; CPU work unaffected)")


# -- config ------------------------------------------------------------------


@cli.group("config")
def config_group():
    """Get/set client configuration."""


@config_group.command("get")
@click.argument("key", required=False)
def config_get(key):
    cfg = kt_config()
    if key:
        click.echo(cfg.get(key))
    else:
        from dataclasses import fields
        for f in fields(cfg):
            if f.name != "extra":
                click.echo(f"{f.name}: {getattr(cfg, f.name)}")


@config_group.command("set")
@click.argument("key")
@click.argument("value")
def config_set(key, value):
    cfg = kt_config()
    cfg.set(key, value)
    cfg.save()
    click.echo(f"{key} = {value}")


# -- cluster install ----------------------------------------------------------


@cli.command()
@click.option("--skip", multiple=True,
              help="Skip manifests whose filename contains this substring "
                   "(e.g. --skip loki --skip kueue).")
def install(skip):
    """Install the control plane + observability stack (deploy/*.yaml)."""
    from .provisioning.installer import install_stack
    for fname, kind, name in install_stack(skip=skip):
        click.echo(f"applied {kind}/{name}  ({fname})")


# -- deploy ------------------------------------------------------------------


@cli.command()
@click.argument("target")
def deploy(target):
    """Deploy all @kt.compute-decorated callables in a python file."""
    os.environ["KT_CLI_DEPLOY_MODE"] = "1"
    reset_config()
    from .resources.decorators import clear_registry, collected_modules

    clear_registry()
    import importlib.util
    spec = importlib.util.spec_from_file_location("__kt_deploy__", target)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["__kt_deploy__"] = mod
    spec.loader.exec_module(mod)
    partials = collected_modules()
    if not partials:
        click.echo("No @kt.compute-decorated callables found.")
        return
    for pm in partials:
        module, compute = pm.build()
        click.echo(f"Deploying {module.name} ...")
        module.to(compute)
        click.echo(f"  → {module.service_url}")


# -- call --------------------------------------------------------------------


@cli.command()
@click.argument("service")
@click.argument("method", required=False)
@click.option("--args", "args_json", default="[]", help="JSON args list")
@click.option("--kwargs", "kwargs_json", default="{}", help="JSON kwargs")
@click.option("--namespace", default=None)
def call(service, method, args_json, kwargs_json, namespace):
    """Invoke a deployed service: kt call my-svc [method] --args '[1,2]'."""
    from .client import controller_client
    from .serving.http_client import HTTPClient

    record = controller_client().get_workload(
        namespace or kt_config().namespace, service)
    url = record.get("service_url")
    fn_name = record.get("metadata", {}).get("KT_CLS_OR_FN_NAME", service)
    out = HTTPClient(url).call_method(
        fn_name, method=method, args=tuple(json.loads(args_json)),
        kwargs=json.loads(kwargs_json))
    click.echo(json.dumps(out, default=str))


# -- list / describe / teardown / workload ------------------------------------


@cli.command("list")
@click.option("--namespace", default=None)
def list_cmd(namespace):
    """List deployed workloads."""
    from .client import controller_client
    rows = controller_client().list_workloads(namespace)
    if not rows:
        click.echo("(no workloads)")
        return
    for w in rows:
        click.echo(f"{w['namespace']:12} {w['name']:32} "
                   f"{w.get('service_url') or '-'}")


@cli.command()
@click.argument("service")
@click.option("--namespace", default=None)
def describe(service, namespace):
    """Full workload record incl. connected pods."""
    from .client import controller_client
    record = controller_client().get_workload(
        namespace or kt_config().namespace, service)
    click.echo(json.dumps(record, indent=2, default=str))


@cli.command()
@click.argument("service", required=False)
@click.option("--all", "all_", is_flag=True, help="tear down every workload")
@click.option("--prefix", default=None, help="tear down by name prefix")
@click.option("--namespace", default=None)
@click.option("--all-namespaces", "all_ns", is_flag=True,
              help="bulk ops span every namespace (default: configured ns)")
def teardown(service, all_, prefix, namespace, all_ns):
    """Delete workload(s) and their pods."""
    if not (service or all_ or prefix):
        # validate before touching the controller — a bare `kt teardown`
        # must not spawn a local daemon just to print usage
        raise click.UsageError("pass SERVICE, --all, or --prefix")
    if service and all_ns:
        raise click.UsageError(
            "--all-namespaces only applies to bulk ops (--all/--prefix); "
            "for one service pass --namespace")
    from .client import controller_client
    client = controller_client()
    ns = namespace or kt_config().namespace
    if service:
        client.delete_workload(ns, service)
        click.echo(f"deleted {service}")
        return
    # bulk ops scope to the resolved namespace unless --all-namespaces —
    # explicit over implicit for a destructive command
    scope = None if all_ns else ns
    for w in client.list_workloads(scope):
        if all_ or (prefix and w["name"].startswith(prefix)):
            client.delete_workload(w["namespace"], w["name"])
            click.echo(f"deleted {w['name']}")


@cli.command()
@click.argument("manifest_file")
@click.option("--namespace", default=None)
@click.option("--name", default=None)
def apply(manifest_file, namespace, name):
    """Apply a BYO manifest through the controller."""
    import yaml
    from .client import controller_client
    with open(manifest_file) as f:
        manifest = yaml.safe_load(f)
    out = controller_client().apply(
        namespace or kt_config().namespace,
        name or manifest.get("metadata", {}).get("name", "unnamed"), manifest)
    click.echo(json.dumps(out))


# -- run (App) ---------------------------------------------------------------


@cli.command()
@click.argument("command", nargs=-1, required=True)
@click.option("--name", default=None)
@click.option("--port", type=int, default=None)
@click.option("--cpus", default=None)
@click.option("--tpu", default=None)
def run(command, name, port, cpus, tpu):
    """Run an arbitrary server process: kt run python serve.py --port 8000."""
    import shlex

    from .resources.app import app as app_factory
    from .resources.compute import Compute

    a = app_factory(shlex.join(command), name=name, port=port)
    a.to(Compute(cpus=cpus, tpu=tpu))
    click.echo(f"{a.name} → {a.service_url}")


# -- trace -------------------------------------------------------------------


@cli.command("trace")
@click.argument("query")
@click.option("--service", default=None,
              help="Resolve the pod URL for this deployed service via the "
                   "controller (default when --url is not given).")
@click.option("--url", default=None,
              help="Query this server's /debug/traces directly (a pod or "
                   "store URL) — no controller needed.")
@click.option("--namespace", default=None)
@click.option("--json", "as_json", is_flag=True,
              help="Raw span dicts instead of the waterfall view.")
def trace_cmd(query, service, url, namespace, as_json):
    """Waterfall view of one request's trace: ``kt trace <request_id>``
    (or a trace id). Reads the serving pod's ``/debug/traces`` flight
    recorder, which includes rank-worker and store-fetch spans shipped
    back across the process boundary."""
    from . import telemetry

    if url is None:
        if service is None:
            raise click.UsageError("pass --service (resolved via the "
                                   "controller) or --url <pod url>")
        from .client import controller_client
        record = controller_client().get_workload(
            namespace or kt_config().namespace, service)
        url = record.get("service_url")
        if not url:
            raise click.ClickException(f"service {service!r} has no URL")
    import requests as _requests
    try:
        r = _requests.get(f"{url.rstrip('/')}/debug/traces",
                          params={"q": query}, timeout=10)
    except _requests.RequestException as e:
        # dead pod: its trace ring died with it, but the flight recorder's
        # spool survives — point at the black box instead of shrugging
        from .exceptions import PodUnreachableError
        spool = kt_config().obs_spool
        hint = (f"kt blackbox {spool}" if spool
                else "set KT_OBS_SPOOL to arm the flight recorder for "
                     "next time")
        err = PodUnreachableError(
            f"{type(e).__name__}: cannot reach {url} — the pod is dead, "
            f"restarting, or partitioned; its in-memory trace ring is "
            f"gone. Last recorded interval: {hint}",
            url=url, spool_hint=spool or None)
        raise click.ClickException(str(err))
    if r.status_code != 200:
        raise click.ClickException(
            f"/debug/traces → {r.status_code}: {r.text[:200]}")
    body = r.json()
    spans = body.get("spans", [])
    if as_json:
        click.echo(json.dumps(spans, indent=2, default=str))
        return
    if not spans:
        state = ("" if body.get("enabled", True)
                 else " (tracing is DISABLED on that server: KT_TRACE=0)")
        click.echo(f"no spans for {query!r}{state} — the ring keeps the "
                   f"last {body.get('ring_size', 0)}+ spans per process")
        return
    click.echo(telemetry.format_waterfall(spans))


# -- logs --------------------------------------------------------------------


@cli.command()
@click.argument("service")
@click.option("--namespace", default=None)
@click.option("--follow", "-f", is_flag=True)
def logs(service, namespace, follow):
    """Show (and follow) service logs from the controller buffer."""
    import time as _t
    from .client import controller_client
    client = controller_client()
    ns = namespace or kt_config().namespace
    offset = 0
    while True:
        out = client.logs(service=service, namespace=ns, offset=offset)
        for e in out.get("entries", []):
            click.echo(f"[{e.get('pod', '?')}] {e['line']}")
        offset = out.get("offset", offset)
        if not follow:
            break
        _t.sleep(1)


# -- data store ---------------------------------------------------------------


@cli.command()
@click.argument("key")
@click.argument("src")
def put(key, src):
    """Upload a file/dir to the data store."""
    from .data_store import commands as ds
    click.echo(json.dumps(ds.put(key, src)))


@cli.command()
@click.argument("key")
@click.argument("dest", required=False)
def get(key, dest):
    """Download a key from the data store."""
    from .data_store import commands as ds
    out = ds.get(key, dest=dest)
    click.echo(str(out) if not isinstance(out, bytes) else f"{len(out)} bytes")


@cli.command()
@click.argument("prefix", required=False, default="")
def ls(prefix):
    from .data_store import commands as ds
    for k in ds.ls(prefix):
        click.echo(f"{k.get('kind', '?'):5} {k['key']}")


@cli.command()
@click.argument("key")
def rm(key):
    from .data_store import commands as ds
    click.echo("deleted" if ds.rm(key) else "not found")


# -- secrets / volumes --------------------------------------------------------


@cli.group()
def secrets():
    """Manage secrets."""


@secrets.command("create")
@click.argument("provider")
@click.option("--name", default=None)
def secrets_create(provider, name):
    from .resources.secret import Secret
    s = Secret.from_provider(provider, name=name)
    s.save()
    click.echo(f"created {s.name} ({sorted(s.values)})")


@secrets.command("providers")
def secrets_providers():
    from .resources.secret import PROVIDERS
    for p in sorted(PROVIDERS):
        click.echo(p)


@secrets.command("delete")
@click.argument("name")
def secrets_delete(name):
    from .resources.secret import Secret
    result = Secret(name).delete()
    click.echo("deleted" if result.get("existed") else "not found")


@cli.group()
def volumes():
    """Manage volumes."""


@volumes.command("create")
@click.argument("name")
@click.option("--size", default="10Gi")
@click.option("--storage-class", default=None)
@click.option("--access-mode", default="ReadWriteOnce")
def volumes_create(name, size, storage_class, access_mode):
    from .resources.volume import Volume
    Volume(name, size=size, storage_class=storage_class,
           access_mode=access_mode).create()
    click.echo(f"created {name} ({size})")


@volumes.command("delete")
@click.argument("name")
@click.option("--no-wait", is_flag=True, default=False)
def volumes_delete(name, no_wait):
    from .resources.volume import Volume
    result = Volume(name).delete(wait=not no_wait)
    click.echo("deleted" if result.get("existed") else "not found")


@volumes.command("ssh")
@click.argument("name")
@click.option("--image", default="alpine:latest")
def volumes_ssh(name, image):
    """Interactive scratch pod (or local shell) with the volume mounted."""
    from .resources.volume import Volume
    Volume.from_name(name).ssh(image=image)


@volumes.command("storage-classes")
def volumes_storage_classes():
    from .resources.volume import Volume
    for c in Volume.storage_classes():
        default = " (default)" if c.get("default") else ""
        click.echo(f"{c['name']}{default}  {c.get('provisioner', '')}")


# -- debug / ssh / events -----------------------------------------------------


@cli.command()
@click.argument("service")
@click.option("--port", type=int, default=5678)
@click.option("--token", default=None,
              help="One-shot session token printed by the call that armed "
                   "the breakpoint.")
def debug(service, port, token):
    """Attach to a remote pdb session armed by a call with debugger=."""
    import socket
    from .client import controller_client
    record = controller_client().get_workload(kt_config().namespace, service)
    host = record["service_url"].split("//")[1].split(":")[0]
    click.echo(f"connecting to {host}:{port} ... (Ctrl-D to detach)")
    sock = socket.create_connection((host, port))
    if token:
        sock.sendall(token.encode() + b"\n")
    import threading

    def pump_out():
        while True:
            data = sock.recv(4096)
            if not data:
                break
            sys.stdout.write(data.decode(errors="replace"))
            sys.stdout.flush()

    t = threading.Thread(target=pump_out, daemon=True)
    t.start()
    try:
        for line in sys.stdin:
            sock.sendall(line.encode())
    except KeyboardInterrupt:
        pass
    sock.close()


@cli.command()
@click.argument("service")
@click.option("--namespace", default=None)
def events(service, namespace):
    """Controller events for a service."""
    from .client import controller_client
    for e in controller_client().events(service):
        click.echo(f"{e['ts']:.0f} {e['service']}: {e['message']}")


@cli.command()
@click.argument("service")
@click.option("--namespace", default=None)
@click.option("--command", "-c", default="/bin/bash")
def ssh(service, namespace, command):
    """Shell into a service pod (kubectl exec; reference cli.py:1757)."""
    import subprocess as sp

    from .utils.kubectl import resolve_kubectl

    kubectl = resolve_kubectl()
    if kubectl is None:
        raise click.ClickException(
            "kubectl not found — ssh requires a Kubernetes cluster "
            "(local-backend pods are host subprocesses; see `kt describe`)")
    ns = namespace or kt_config().namespace
    out = sp.run([kubectl, "get", "pods", "-n", ns, "-l",
                  f"kubetorch.com/service={service}", "-o",
                  "jsonpath={.items[0].metadata.name}"],
                 capture_output=True, text=True)
    pod = out.stdout.strip()
    if not pod:
        raise click.ClickException(f"no pods found for service {service!r}")
    # sh -c so multi-word commands work: kt ssh svc -c "python -V"
    sp.run([kubectl, "exec", "-it", "-n", ns, pod, "--", "sh", "-c", command])


@cli.command("port-forward")
@click.argument("service", required=False, default="kubetorch-controller")
@click.option("--namespace", default=None)
@click.option("--port", type=int, default=8080)
def port_forward_cmd(service, namespace, port):
    """Port-forward to a cluster service (reference cli.py:1259)."""
    from .provisioning.port_forward import ensure_port_forward

    ns = namespace or ("kubetorch" if service == "kubetorch-controller"
                       else kt_config().namespace)
    try:
        handle = ensure_port_forward(service=service, namespace=ns,
                                     remote_port=port)
    except RuntimeError as e:
        raise click.ClickException(str(e))
    click.echo(f"{service} → {handle.url}  (Ctrl-C to stop)")
    try:
        handle.proc.wait()
    except KeyboardInterrupt:
        handle.close()


@cli.command()
def dashboard():
    """Cluster overview: workloads, pods, recent events (reference :812)."""
    from .client import controller_client

    client = controller_client()
    workloads = client.list_workloads()
    click.echo(f"=== workloads ({len(workloads)}) ===")
    for w in workloads:
        record = client.get_workload(w["namespace"], w["name"])
        pods = record.get("connected_pods", [])
        click.echo(f"{w['namespace']:10} {w['name']:28} pods={len(pods)} "
                   f"{w.get('service_url') or '-'}")
    events = client.events()
    click.echo(f"=== events (last {min(len(events), 10)}) ===")
    for e in events[-10:]:
        click.echo(f"{e['ts']:.0f} {e['service']}: {e['message']}")


@cli.command()
@click.option("--cpus", default="2")
@click.option("--tpu", default=None)
@click.option("--port", type=int, default=8888)
def notebook(cpus, tpu, port):
    """Remote Jupyter on managed compute (reference cli.py:2181) — deployed
    as a kt App; requires jupyter in the image."""
    from .resources.app import app as app_factory
    from .resources.compute import Compute
    from .resources.image import Image

    image = Image().pip_install(["jupyterlab"])
    nb = app_factory(
        f"jupyter lab --ip 0.0.0.0 --port {port} --no-browser --allow-root",
        name="kt-notebook", port=port)
    nb.to(Compute(cpus=cpus, tpu=tpu, image=image))
    click.echo(f"notebook service: {nb.service_url} (token in `kt logs kt-notebook`)")


# -- server ------------------------------------------------------------------


@cli.group()
def server():
    """Pod-side server management."""


@server.command("start")
@click.option("--port", type=int, default=None)
@click.option("--workload", default=None,
              help="BYO: register under this workload name")
def server_start(port, workload):
    """Start the pod runtime (BYO compute bootstrap, reference cli.py:2846)."""
    from .constants import server_port as parse_port
    if workload:
        os.environ.setdefault("KT_SERVICE_NAME", workload)
    # http_server.main advertises the bound port via KT_SERVER_PORT itself.
    # `is not None`: an explicit --port 0 means bind-ephemeral, not default.
    from .serving.http_server import main as server_main
    server_main(["--port", str(port if port is not None else parse_port())])


@cli.command("serve", context_settings={"ignore_unknown_options": True})
@click.argument("args", nargs=-1, type=click.UNPROCESSED)
def serve_cmd(args):
    """OpenAI-compatible server for a HF checkpoint (vLLM-style UX):

    \b
      kt serve --ckpt /path/to/llama --port 8000 --int8 --decode-block 32

    All flags pass through to ``kubetorch_tpu.serve.openai_api`` (run it
    with --help for the full list: slots, max-len, auto-prefix,
    prefill-chunk, ...).

    \b
      kt serve status [--service NAME | --url URL] [--json]

    shows the serving front door (ISSUE 9): admission/shed counters,
    affinity hit rate, replica batch depth, and engine occupancy."""
    if args and args[0] == "status":
        _serve_status(list(args[1:]))
        return
    from .serve.openai_api import main as serve_main
    serve_main(list(args))


def _serve_status(argv):
    """``kt serve status``: one pod's ``/health`` router block +
    ``/metrics`` serve/engine series, rendered for the operator."""
    import argparse

    import requests as _requests

    p = argparse.ArgumentParser(prog="kt serve status")
    p.add_argument("--service", default=None,
                   help="Resolve the service URL via the controller.")
    p.add_argument("--url", default=None,
                   help="Query this pod/service URL directly.")
    p.add_argument("--namespace", default=None)
    p.add_argument("--json", dest="as_json", action="store_true")
    ns = p.parse_args(argv)
    url = ns.url
    if url is None:
        if ns.service is None:
            raise click.UsageError("pass --service (resolved via the "
                                   "controller) or --url <pod url>")
        from .client import controller_client
        record = controller_client().get_workload(
            ns.namespace or kt_config().namespace, ns.service)
        url = record.get("service_url")
        if not url:
            raise click.ClickException(f"service {ns.service!r} has no URL")
    url = url.rstrip("/")
    try:
        # one-shot probes by design (like `kt store status`): a status
        # command that retried would hide the flakiness it exists to show
        health = _requests.get(f"{url}/health", timeout=5).json()
        text = _requests.get(f"{url}/metrics", timeout=5).text
    except _requests.RequestException as e:
        raise click.ClickException(f"cannot reach {url}: {e}")

    def metric_lines(prefix):
        out = {}
        for line in text.splitlines():
            if line.startswith(prefix) and not line.startswith("#"):
                try:
                    out[line.rsplit(" ", 1)[0]] = float(line.split()[-1])
                except (ValueError, IndexError):
                    continue
        return out

    serve_series = {k: v for name in
                    ("kt_serve_", "kt_user_engine_", "kt_user_session")
                    for k, v in metric_lines(name).items()}
    router = health.get("router") or {}
    if ns.as_json:
        click.echo(json.dumps({"url": url, "router": router,
                               "metrics": serve_series},
                              indent=2, default=str))
        return
    click.echo(f"pod {health.get('pod', '?')}  "
               f"supervisor_healthy={health.get('supervisor_healthy')}")
    if router:
        click.echo(
            f"front door: capacity={router.get('capacity')} "
            f"active={router.get('active')} "
            f"queued={router.get('queued')}/{router.get('queue_max')} "
            f"sessions={router.get('sessions')} "
            f"affinity-hit-rate={router.get('affinity_hit_rate', 0):.1%} "
            f"est-wait={router.get('estimated_wait_s')}s")
        inflight = router.get("inflight") or {}
        for ip, n in sorted(inflight.items()):
            click.echo(f"  {ip:<20} inflight={n}")
    else:
        click.echo("front door: (not a load_balanced service — no router)")
    if serve_series:
        click.echo("series:")
        for k, v in sorted(serve_series.items()):
            click.echo(f"  {k} {v:g}")


# -- store -------------------------------------------------------------------


@cli.group()
def store():
    """Data-store server management."""


@store.command("start")
@click.option("--port", type=int, default=8873)
@click.option("--root", default="./kt-store")
@click.option("--nodes", default=None,
              help="Comma-separated ring member URLs (incl. this node); "
                   "default KT_STORE_NODES.")
@click.option("--self-url", default=None,
              help="This node's URL within --nodes; default "
                   "KT_STORE_SELF_URL.")
def store_start(port, root, nodes, self_url):
    from .data_store.store_server import main as store_main
    args = ["--port", str(port), "--root", root]
    if nodes:
        args += ["--nodes", nodes]
    if self_url:
        args += ["--self-url", self_url]
    store_main(args)


@store.command("status")
@click.option("--url", default=None,
              help="Any ring member (default: the configured store / "
                   "KT_STORE_NODES).")
@click.option("--json", "as_json", is_flag=True, help="Raw JSON per node.")
def store_status(url, as_json):
    """Ring health: membership + epoch, per-node capacity, scrub and
    replication state — rendered from each member's ``/ring`` and
    ``/scrub/status``."""
    import requests as _requests

    from .data_store import ring as ring_mod

    seed = url or ring_mod.resolve_origin(None)
    rg = ring_mod.ring_for(seed)
    if rg.size > 1:
        rg.refresh()
    nodes = rg.nodes
    rows, raw = [], {}
    for base in nodes:
        info: dict = {"url": base, "alive": False}
        try:
            # one-shot probes by design: a status command that retried
            # would hide exactly the flakiness it exists to show
            r = _requests.get(f"{base}/ring", timeout=5)
            r.raise_for_status()
            view = r.json()
            s = _requests.get(f"{base}/scrub/status", timeout=5).json()
            cap = view.get("capacity") or {}
            info.update({
                "alive": True,
                "epoch": view.get("epoch"),
                "members": len(view.get("nodes") or []),
                "used_gb": round((cap.get("used_bytes") or 0) / 1e9, 2),
                "free_gb": round((cap.get("free_bytes") or 0) / 1e9, 2),
                "under_replicated": s.get("under_replicated"),
                "re_replicated": s.get("re_replicated"),
                "quarantine": s.get("quarantine_files"),
                "down": sorted((view.get("down") or {})),
            })
            raw[base] = {"ring": view, "scrub": s}
        except _requests.RequestException as e:
            info["error"] = str(e)[:120]
            raw[base] = {"error": str(e)}
        rows.append(info)
    if as_json:
        click.echo(json.dumps(raw, indent=2, default=str))
        return
    head = (f"ring: {len(nodes)} node(s)"
            f"{'' if rg.epoch is None else f', epoch {rg.epoch}'}"
            f" · R={ring_mod.replication_factor()}"
            f" W={ring_mod.write_quorum()}"
            f" · node TTL {ring_mod.node_ttl_s():g}s")
    click.echo(head)
    for row in rows:
        if not row["alive"]:
            click.echo(f"  {row['url']:<28} DEAD  ({row.get('error', '?')})")
            continue
        down = f"  down={','.join(row['down'])}" if row["down"] else ""
        click.echo(
            f"  {row['url']:<28} ok    epoch={row['epoch']}"
            f" used={row['used_gb']}G free={row['free_gb']}G"
            f" under-repl={row['under_replicated']}"
            f" re-repl={row['re_replicated']}"
            f" quarantine={row['quarantine']}{down}")


@cli.group()
def rollout():
    """Live weight rollout management (ISSUE 11)."""


@rollout.command("status")
@click.option("--service", default=None,
              help="Service name: reads its rollout manifest from the "
                   "store and resolves replica URLs via the controller.")
@click.option("--url", "urls", multiple=True,
              help="Query these pod URLs directly (repeatable).")
@click.option("--store-url", default=None,
              help="Any store ring member (default: the configured store).")
@click.option("--namespace", default=None)
@click.option("--json", "as_json", is_flag=True)
def rollout_status(service, urls, store_url, namespace, as_json):
    """Fleet rollout view: the current manifest (version/phase/canary/
    fingerprint from the quorum ``put_json`` path), each replica's applied
    version + fingerprint, and bytes moved by source — rendered from the
    store manifest plus each pod's ``/rollout/status`` and the
    ``kt_rollout_*`` series on its ``/metrics``."""
    import requests as _requests

    from .data_store import commands as ds

    manifest = None
    if service:
        # key shape owned by serve/rollout.py (manifest_key) — inlined here
        # so a status command never imports the jax-heavy serve package
        manifest = ds.get_json(f"rollout/{service}/manifest",
                               store_url=store_url, quorum=True)
    replica_urls = list(urls)
    if service and not replica_urls:
        try:
            from .client import controller_client
            record = controller_client().get_workload(
                namespace or kt_config().namespace, service)
            for pod in record.get("connected_pods", []) or []:
                ip = pod.get("ip") if isinstance(pod, dict) else pod
                if ip:
                    from .constants import server_port
                    replica_urls.append(f"http://{ip}:{server_port()}")
        except Exception:
            pass                      # store-only view is still useful
    replicas, raw = [], {}
    for base in replica_urls:
        base = base.rstrip("/")
        row = {"url": base, "alive": False}
        try:
            # one-shot probes by design (like `kt store status`): a status
            # command that retried would hide the flakiness it shows
            st = _requests.get(f"{base}/rollout/status", timeout=5).json()
            text = _requests.get(f"{base}/metrics", timeout=5).text
            series = {}
            for line in text.splitlines():
                if line.startswith("kt_rollout_") and not line.startswith("#"):
                    try:
                        series[line.rsplit(" ", 1)[0]] = float(
                            line.split()[-1])
                    except (ValueError, IndexError):
                        continue
            row.update({"alive": True,
                        "rollouts": st.get("rollouts", []),
                        "series": series})
        except (_requests.RequestException, ValueError) as e:
            row["error"] = str(e)[:120]
        replicas.append(row)
        raw[base] = row
    if as_json:
        click.echo(json.dumps({"manifest": manifest, "replicas": raw},
                              indent=2, default=str))
        return
    if manifest:
        fp = manifest.get("fingerprint") or "?"
        click.echo(
            f"manifest: v{manifest.get('version')} "
            f"phase={manifest.get('phase')} step={manifest.get('step')} "
            f"key={manifest.get('key')}")
        click.echo(f"  fingerprint {fp}"
                   + (f"  canary={manifest['canary']}"
                      if manifest.get("canary") else "")
                   + (f"  reason={manifest['reason']}"
                      if manifest.get("reason") else ""))
    elif service:
        click.echo(f"no rollout manifest published for {service!r}")
    for row in replicas:
        if not row["alive"]:
            click.echo(f"  {row['url']:<28} DEAD  ({row.get('error', '?')})")
            continue
        entries = row.get("rollouts") or []
        if not entries:
            click.echo(f"  {row['url']:<28} (no in-process rollout)")
        for st in entries:
            b = st.get("bytes") or {}
            match = (manifest is not None
                     and st.get("fingerprint") == manifest.get("fingerprint"))
            click.echo(
                f"  {row['url']:<28} v{st.get('version')} "
                f"phase={st.get('phase')} "
                f"{'swapping ' if st.get('swapping') else ''}"
                f"origin={b.get('origin', 0)}B peer={b.get('peer', 0)}B "
                f"rollbacks={st.get('rollbacks', 0)}"
                f"{'  IN-SYNC' if match else ''}"
                + (f"  err={st['last_error']}" if st.get("last_error")
                   else ""))


@cli.group()
def flywheel():
    """Continuous-learning flywheel: ledger, harvest, gated promotion
    (ISSUE 19)."""


@flywheel.command("status")
@click.option("--service", required=True)
@click.option("--replica", "replicas", multiple=True,
              help="Serving replica ids feeding the ledger (repeatable; "
                   "default: replica-0).")
@click.option("--store-url", default=None,
              help="Any store ring member (default: the configured store).")
@click.option("--json", "as_json", is_flag=True)
def flywheel_status_cmd(service, replicas, store_url, as_json):
    """One freshness snapshot of the whole loop — ledger heads, cursor,
    trainer lease, rollout manifest, eval baseline, and the per-stage
    ``kt_flywheel_lag_seconds`` (collect/train/publish/promote) that a
    stalled stage shows up in first."""
    from .flywheel.promoter import flywheel_status

    out = flywheel_status(service, list(replicas) or ["replica-0"],
                          store_url=store_url)
    if as_json:
        click.echo(json.dumps(out, indent=2, default=str))
        return
    click.echo(f"flywheel: {service}")
    for replica, head in sorted(out["replicas"].items()):
        if head:
            click.echo(f"  ledger {replica:<12} seq={head.get('seq')} "
                       f"records={head.get('records', '?')}")
        else:
            click.echo(f"  ledger {replica:<12} (no appends yet)")
    cursor = out.get("cursor")
    click.echo(f"  cursor step={cursor.get('step')}" if cursor
               else "  cursor (never committed)")
    lease = out.get("lease")
    if lease:
        click.echo(f"  trainer lease epoch={lease.get('epoch')} "
                   f"owner={lease.get('owner', '?')}")
    manifest = out.get("manifest")
    if manifest:
        click.echo(f"  manifest v{manifest.get('version')} "
                   f"phase={manifest.get('phase')} "
                   f"step={manifest.get('step')} "
                   f"fingerprint={manifest.get('fingerprint')}")
    else:
        click.echo("  manifest (nothing published)")
    base = out.get("eval_baseline")
    if base:
        click.echo(f"  eval baseline loss={base.get('loss'):.6g} "
                   f"step={base.get('step')}")
    lag_bits = []
    for stage in ("collect", "train", "publish", "promote"):
        lag = out["lag_seconds"].get(stage)
        lag_bits.append(f"{stage}={'-' if lag is None else f'{lag:.1f}s'}")
    click.echo("  lag " + "  ".join(lag_bits))


@cli.group()
def queue():
    """Scheduler queue management (priorities & preemption)."""


@queue.command("status")
@click.option("--json", "as_json", is_flag=True, help="Raw scheduler state.")
def queue_status(as_json):
    """Tiers, queue depth/order, the capacity book, and recent
    preemptions — the controller scheduler's ``/controller/queue`` view."""
    from .client import controller_client

    snap = controller_client().queue_status()
    if as_json:
        click.echo(json.dumps(snap, indent=2, default=str))
        return
    cap = snap.get("capacity") or {}
    click.echo(f"policy: {snap.get('policy')}"
               f"  ·  capacity book: "
               f"{'limited' if cap.get('limited') else 'unlimited'}")
    for cls, row in sorted((cap.get("classes") or {}).items()):
        total = row.get("capacity")
        click.echo(f"  {cls:<8} used={row.get('used', 0)}"
                   f" free={'∞' if row.get('free') is None else row['free']}"
                   f"{'' if total is None else f' of {total}'}")
    allocs = cap.get("allocations") or {}
    if allocs:
        click.echo(f"running ({len(allocs)}):")
        for key, a in sorted(allocs.items()):
            click.echo(f"  {key:<36} {a.get('device_class')}×{a.get('width')}"
                       f"  tier={a.get('tier')} prio={a.get('priority')}")
    q = snap.get("queue") or []
    click.echo(f"queue ({len(q)}):" if q else "queue: empty")
    for e in q:
        flag = " (preempted, resume pending)" if e.get("preempted") else ""
        click.echo(f"  #{e.get('position')} {e.get('key'):<30} "
                   f"tier={e.get('tier')} prio={e.get('priority')} "
                   f"{e.get('device_class')}×{e.get('width')} "
                   f"waited={e.get('waiting_s')}s{flag}")
    ledger = snap.get("ledger") or []
    if ledger:
        click.echo(f"recent preemptions ({len(ledger)}):")
        for led in ledger:
            click.echo(f"  {led.get('victim'):<30} by {led.get('preemptor')}"
                       f"  phase={led.get('phase')}"
                       f" grace={led.get('grace_s')}s")


@cli.group()
def fleet():
    """Planet-scale federation management (ISSUE 13)."""


@fleet.command("status")
@click.option("--url", default=None,
              help="A federation coordinator's base URL (default "
                   "KT_FED_URL; without one, regions are probed directly "
                   "from the KT_FED_REGIONS/KT_FED_STORES topology).")
@click.option("--json", "as_json", is_flag=True, help="Raw JSON.")
def fleet_status_cmd(url, as_json):
    """Per-region health (Alive/Unreachable/Dead), capacity books, queue
    depth, cross-region replication lag, and the global placement map —
    the federation's ``kt store status``/``kt queue status`` sibling."""
    from .federation import fleet_status

    try:
        snap = fleet_status(fed_url=url)
    except Exception as e:  # noqa: BLE001 — a doctor command reports, not dies
        raise click.ClickException(f"fleet status failed: {e}")
    if as_json:
        click.echo(json.dumps(snap, indent=2, default=str))
        return
    regions = snap.get("regions") or {}
    src = snap.get("source") or ("coordinator" if snap.get("leases")
                                 is not None else "probe")
    head = f"federation: {len(regions)} region(s) · source={src}"
    if snap.get("heartbeat_s") is not None:
        head += (f" · heartbeat {snap['heartbeat_s']:g}s"
                 f" · region TTL {snap.get('region_ttl_s'):g}s")
    click.echo(head)
    for name, info in sorted(regions.items()):
        state = info.get("state", "Alive")
        flag = {"Alive": "ok  ", "Unreachable": "UNRCH",
                "Dead": "DEAD "}.get(state, state[:5])
        down = (f" down={info['down_for_s']}s"
                if info.get("down_for_s") is not None else "")
        qd = info.get("queue_depth")
        lag = info.get("xregion_lag_s")
        store = info.get("store") or {}
        cap = info.get("capacity") or {}
        cap_str = " ".join(
            f"{cls}:{row.get('used', 0)}/"
            f"{'∞' if row.get('capacity') is None else row['capacity']}"
            for cls, row in sorted(cap.items())) if cap else ""
        parts = [f"  {name:<16} {flag}{down}"]
        if qd is not None:
            parts.append(f"queue={qd}")
        if cap_str:
            parts.append(cap_str)
        if store:
            parts.append(f"store={store.get('alive')}/"
                         f"{store.get('nodes')} alive"
                         + (f" epoch={store['epoch']}"
                            if store.get("epoch") is not None else ""))
        if lag is not None:
            parts.append(f"xregion-lag={lag}s")
        if info.get("error"):
            parts.append(f"({info['error']})")
        click.echo(" ".join(parts))
    placements = snap.get("placements")
    if placements:
        click.echo(f"placements ({len(placements)}):")
        for w, p in sorted(placements.items()):
            extra = (f" migrations={p['migrations']}"
                     if p.get("migrations") else "")
            frm = (f" (from {p['migrated_from']})"
                   if p.get("migrated_from") else "")
            click.echo(f"  {w:<36} region={p.get('region')}"
                       f" epoch={p.get('epoch')}{extra}{frm}")
    elif placements is not None:
        click.echo("placements: none")
    else:
        click.echo("placements: unknown (probe mode — point --url/"
                   "KT_FED_URL at a coordinator)")


@cli.group()
def hbm():
    """Training-step HBM tooling (ISSUE 12)."""


@hbm.command("audit")
@click.option("--model", default="tiny",
              type=click.Choice(["tiny", "1b", "8b"]),
              help="Llama preset to audit")
@click.option("--batch", type=int, default=8)
@click.option("--seq", type=int, default=128)
@click.option("--accum", "accum_steps", type=int, default=1,
              help="gradient-accumulation microbatches")
@click.option("--remat-policy", default=None,
              type=click.Choice(["none", "dots", "nothing_saveable"]),
              help="named jax.checkpoint policy for the layer stack")
@click.option("--overlap/--no-overlap", "overlap_grads", default=False,
              help="overlapped per-microbatch grad reduction (needs --mesh)")
@click.option("--mesh", "mesh_spec", default=None,
              help='mesh axes, e.g. "fsdp=8" or "data=2,fsdp=2,tensor=2"')
@click.option("--no-donate", is_flag=True,
              help="audit the donation-off worst case")
@click.option("--host-devices", type=int, default=None,
              help="force N virtual CPU devices (sets XLA_FLAGS; lets a "
                   "1-core box audit an 8-way mesh)")
@click.option("--json", "as_json", is_flag=True)
def hbm_audit(model, batch, seq, accum_steps, remat_policy, overlap_grads,
              mesh_spec, no_donate, host_devices, as_json):
    """Report live-buffer HBM per train step (params/opt/activations from
    the compiled program's memory analysis) and flag undonated buffers —
    the numbers that decide accum vs remat vs smaller batch
    (docs/operations.md "Step-time anatomy"). No weights are materialized:
    auditing an 8B config on a laptop is fine."""
    import sys as _sys

    if host_devices:
        if "jax" in _sys.modules:
            raise click.ClickException(
                "--host-devices must be set before jax initializes; run "
                "`kt hbm audit` in a fresh process")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{host_devices}").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    axes = None
    if mesh_spec:
        try:
            axes = {k.strip(): int(v) for k, _, v in
                    (part.partition("=") for part in mesh_spec.split(","))}
        except ValueError:
            raise click.ClickException(
                f'bad --mesh {mesh_spec!r}; expected "axis=N[,axis=N...]"')
    from .train.hbm_audit import audit_llama, format_audit

    report = audit_llama(model, batch=batch, seq=seq, mesh_axes=axes,
                         accum_steps=accum_steps,
                         overlap_grads=overlap_grads,
                         remat_policy=remat_policy, donate=not no_donate)
    if as_json:
        click.echo(json.dumps(report, indent=2))
    else:
        click.echo(format_audit(report))


@cli.group()
def controller():
    """Controller management."""


@controller.command("start")
@click.option("--port", type=int, default=8080)
@click.option("--backend", type=click.Choice(["local", "kubernetes"]),
              default="local")
def controller_start(port, backend):
    from .controller.app import main as controller_main
    controller_main(["--port", str(port), "--backend", backend])


@controller.command("stop")
def controller_stop():
    """Stop the local controller daemon and all its pods."""
    from .client import shutdown_local_controller
    shutdown_local_controller()
    click.echo("local controller stopped")


@cli.group()
def chaos():
    """Fault-injection tooling (the KT_CHAOS grammar)."""


@chaos.command("verbs")
@click.option("--json", "as_json", is_flag=True)
def chaos_verbs(as_json):
    """List the chaos-verb registry: every verb the KT_CHAOS grammar
    accepts, with its scope, consumer, grammar, and an example token.
    docs/resilience.md's grammar table is generated from the same
    registry, so this list and the docs cannot drift apart."""
    from .chaos import registry_as_dicts

    verbs = registry_as_dicts()
    if as_json:
        click.echo(json.dumps(verbs, indent=2))
        return
    w = max(len(v["name"]) for v in verbs)
    for v in verbs:
        flags = "  [process-fatal]" if v["process_fatal"] else ""
        methods = (f" ({'/'.join(v['methods'])} only)"
                   if v["methods"] else "")
        click.echo(f"{v['name']:<{w}}  [{v['scope']}] "
                   f"{v['summary']}{methods}{flags}")
        click.echo(f"{'':<{w}}  grammar: {v['grammar']}   "
                   f"e.g. {v['example']}")


@cli.group()
def obs():
    """Fleet flight recorder & SLO burn rollups (ISSUE 20)."""


@obs.command("top")
@click.option("--url", default=None,
              help="Controller base URL (default: the configured / local "
                   "controller).")
@click.option("--json", "as_json", is_flag=True, help="Raw JSON.")
def obs_top(url, as_json):
    """Live fleet dashboard: merged per-stage latency histograms across
    every pod, SLO error-budget burn rates (fast 5m / slow 1h windows),
    and any standing burn alerts — rendered from the controller's
    ``/fleet/status`` rollup."""
    import requests as _requests

    if url is None:
        from .client import controller_client
        url = controller_client().base_url
    try:
        # single-shot dashboard probe by design: a top that retried would
        # smooth over exactly the instability it exists to surface
        r = _requests.get(f"{url.rstrip('/')}/fleet/status", timeout=5)
        r.raise_for_status()
    except _requests.RequestException as e:
        raise click.ClickException(f"cannot reach controller {url}: {e}")
    snap = r.json()
    if as_json:
        click.echo(json.dumps(snap, indent=2, default=str))
        return
    slo = snap.get("slo") or {}
    pods = snap.get("pods") or {}
    up = sum(1 for s in pods.values() if s.get("up"))
    click.echo(f"fleet: {up} pod(s) up, {len(pods) - up} down · "
               f"SLO {slo.get('slo_s')}s @ {slo.get('target')} · "
               f"burn pages at x{slo.get('burn_threshold')}")
    stages = snap.get("stages") or {}
    if not stages:
        click.echo("no stage samples yet (is the scrape loop running "
                   "against live pods?)")
    else:
        click.echo(f"{'stage':<22} {'count':>8} {'p50':>9} {'p99':>9} "
                   f"{'bad%':>6} {'burn-5m':>8} {'burn-1h':>8}")
        for stage, row in sorted(stages.items()):
            burn = row.get("burn") or {}

            def _fmt(x, spec=".3f"):
                return "-" if x is None else format(x, spec)

            click.echo(
                f"{stage:<22} {int(row.get('count') or 0):>8} "
                f"{_fmt(row.get('p50')):>9} {_fmt(row.get('p99')):>9} "
                f"{_fmt(100.0 * (row.get('bad_frac') or 0.0), '.2f'):>6} "
                f"{_fmt(burn.get('fast'), '.2f'):>8} "
                f"{_fmt(burn.get('slow'), '.2f'):>8}")
    alerts = snap.get("alerts") or []
    if alerts:
        click.echo(f"ALERTS ({len(alerts)}):")
        for a in alerts:
            click.echo(f"  ! {a.get('message', a)}")


@cli.command("blackbox")
@click.argument("spool")
@click.option("--width", type=int, default=40,
              help="Waterfall bar width in characters.")
@click.option("--json", "as_json", is_flag=True, help="Raw JSON.")
def blackbox_cmd(spool, width, as_json):
    """Crash forensics: reconstruct a dead process's last telemetry
    interval from its flight-recorder spool — final metric snapshot,
    metric movement over the last record, and the in-flight span
    waterfall at the moment of death. SPOOL is a spool root
    (``KT_OBS_SPOOL``) or a single ``<name>-<pid>`` spool directory."""
    from pathlib import Path as _Path

    from .obs import format_blackbox, reconstruct, spool_dirs

    root = _Path(spool)
    dirs = spool_dirs(root)
    if not dirs and list(root.glob("segment-*.jsonl")):
        dirs = [root]
    if not dirs:
        raise click.ClickException(
            f"no flight-recorder spools under {spool!r} (expected "
            f"<name>-<pid>/segment-*.jsonl; is KT_OBS_SPOOL armed?)")
    recons = [reconstruct(d) for d in dirs]
    if as_json:
        click.echo(json.dumps(recons, indent=2, default=str))
        return
    bad = 0
    for i, recon in enumerate(recons):
        if i:
            click.echo("")
        click.echo(format_blackbox(recon, width=width))
        bad += 1 if recon.get("errors") else 0
    if bad:
        raise click.ClickException(
            f"{bad} spool(s) failed hash-chain/sequence verification")


@cli.group()
def soak():
    """Seeded whole-stack chaos soak with invariant checking (ISSUE 15)."""


@soak.command("run")
@click.option("--seed", type=int, default=0,
              help="schedule seed (same seed → byte-identical schedule)")
@click.option("--duration", type=float, default=60.0,
              help="approximate run seconds; divided by the op interval "
                   "to get the op-indexed schedule length")
@click.option("--profile", default="all",
              type=click.Choice(["store", "train", "serve", "federation",
                                 "all", "pipeline", "flywheel"]))
@click.option("--shrink/--no-shrink", "do_shrink", default=True,
              help="on violation, ddmin the schedule to a minimal repro")
@click.option("--out", default=None,
              help="replay-file path (default: <base-dir>/repro.json)")
@click.option("--base-dir", default=None,
              help="work dir for fleet roots + history (default: a fresh "
                   "temp dir, kept on violation)")
@click.option("--json", "as_json", is_flag=True)
def soak_run(seed, duration, profile, do_shrink, out, base_dir, as_json):
    """Generate a seeded fault schedule, conduct it against a real
    subprocess fleet, check the Jepsen-style invariants over the recorded
    history, and (on violation) shrink to a minimal replayable repro.
    Exit 0 green, 1 on any violation."""
    import tempfile

    from .config import config
    from .soak import generate
    from .soak.conductor import run_soak, shrink_violation, write_replay

    cfg = config()
    interval = cfg.soak_op_interval_s
    n_ops = max(8, int(duration / max(interval, 0.01)))
    sched = generate(seed, profile, n_ops,
                     store_nodes=cfg.soak_store_nodes)
    base_dir = base_dir or tempfile.mkdtemp(prefix="kt-soak-")
    os.makedirs(base_dir, exist_ok=True)
    history_path = os.path.join(base_dir, "history.jsonl")
    log = (lambda m: None) if as_json else \
        (lambda m: click.echo(m, err=True))
    res = run_soak(sched, base_dir, op_interval_s=interval,
                   settle_timeout_s=cfg.soak_settle_timeout_s,
                   history_path=history_path, log=log)
    report = res.to_dict()
    if not res.ok:
        repro = sched
        if do_shrink:
            repro = shrink_violation(
                sched, base_dir, res.violations[0].invariant,
                op_interval_s=interval,
                settle_timeout_s=cfg.soak_settle_timeout_s, log=log)
        out = out or os.path.join(base_dir, "repro.json")
        write_replay(repro, out, res.violations)
        report["replay"] = out
        report["replay_events"] = len(repro.events)
    if as_json:
        click.echo(json.dumps(report, indent=2))
    elif res.ok:
        click.echo(f"soak OK: seed={seed} profile={profile} "
                   f"ops={res.ops} events={res.events_fired} "
                   f"({res.duration_s:.1f}s)")
    else:
        for v in res.violations:
            click.echo(f"VIOLATION [{v.invariant}] {v.detail}", err=True)
        click.echo(f"replay file: {report['replay']} "
                   f"({report['replay_events']} event(s)) — refire with "
                   f"`kt soak replay {report['replay']}`", err=True)
    sys.exit(0 if res.ok else 1)


@soak.command("replay")
@click.argument("replay_file")
@click.option("--base-dir", default=None)
@click.option("--json", "as_json", is_flag=True)
def soak_replay(replay_file, base_dir, as_json):
    """Refire a (shrunk) replay file deterministically: same seed, same
    boot chaos, same op stream, only the recorded events. Exit 1 if the
    violation reproduces (it is a repro — that is the expected verdict)."""
    import tempfile

    from .config import config
    from .soak.conductor import load_replay, run_soak

    cfg = config()
    sched = load_replay(replay_file)
    base_dir = base_dir or tempfile.mkdtemp(prefix="kt-soak-replay-")
    log = (lambda m: None) if as_json else \
        (lambda m: click.echo(m, err=True))
    res = run_soak(sched, base_dir, op_interval_s=cfg.soak_op_interval_s,
                   settle_timeout_s=cfg.soak_settle_timeout_s,
                   events_override=sched.events, log=log)
    if as_json:
        click.echo(json.dumps(res.to_dict(), indent=2))
    elif res.ok:
        click.echo("replay did NOT reproduce any violation")
    else:
        for v in res.violations:
            click.echo(f"VIOLATION [{v.invariant}] {v.detail}")
    sys.exit(0 if res.ok else 1)


def main():
    from .exceptions import KubetorchError

    try:
        cli(standalone_mode=False)
    except click.ClickException as e:
        e.show()
        sys.exit(e.exit_code)
    except click.exceptions.Abort:
        sys.exit(130)
    except KubetorchError as e:
        click.echo(f"error: {e}", err=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
