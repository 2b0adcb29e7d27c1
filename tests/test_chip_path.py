"""What keeps a run from passing without the chip (ISSUE 21), CPU-only:
the local backend's device rule, the compile-cache helper, the rank's
accelerator check, the kernels' interpret rule, the no-TPU exits of
``bench.py`` and ``chip_smoke.py``, and the smoke's labelled rehearsal."""

import asyncio
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ASSETS = os.path.join(os.path.dirname(__file__), "assets")


# -- who owns the chip -------------------------------------------------------

class _FakeProc:
    pid = 4242

    def poll(self):
        return None


@pytest.fixture()
def spawned(monkeypatch, tmp_path):
    """Capture what the local backend / client would spawn, without
    spawning: argv → env."""
    from kubetorch_tpu import client
    from kubetorch_tpu.config import reset_config
    from kubetorch_tpu.controller import backends

    calls = []

    def popen(argv, env=None, **kw):
        calls.append((argv, env))
        return _FakeProc()
    monkeypatch.setenv("KT_CONFIG_DIR", str(tmp_path))
    reset_config()
    monkeypatch.setattr(backends.subprocess, "Popen", popen)
    monkeypatch.setattr(backends, "wait_for_port", lambda *a, **k: True)
    monkeypatch.setattr(client.subprocess, "Popen", popen)
    monkeypatch.setattr(client, "wait_for_port", lambda *a, **k: True)
    yield calls
    reset_config()


def _pod_env(spawned, compute, inherited="cpu"):
    from kubetorch_tpu.controller.backends import LocalBackend
    os.environ["JAX_PLATFORMS"] = inherited
    backend = LocalBackend(controller_url="http://127.0.0.1:1")
    backend.apply("default", "svc", compute.manifest("svc", env={}), env={})
    return spawned[-1][1]


def test_local_backend_device_rule(spawned, monkeypatch):
    import kubetorch_tpu as kt
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # a TPU Compute keeps the accelerator, whatever the daemon inherited:
    # tpu first, so a failed TPU init raises instead of falling back
    env = _pod_env(spawned, kt.Compute(tpu="v5e-1"))
    assert env["JAX_PLATFORMS"] == "tpu,cpu"
    # every other pod is held to the CPU explicitly — never left to jax's
    # own search (the seed popped the variable)
    for inherited in ("cpu", "tpu,cpu", ""):
        env = _pod_env(spawned, kt.Compute(cpus=1), inherited=inherited)
        assert env["JAX_PLATFORMS"] == "cpu"
    # an explicit Compute(env=...) wins, both ways
    env = _pod_env(spawned, kt.Compute(cpus=1, env={"JAX_PLATFORMS": "tpu"}))
    assert env["JAX_PLATFORMS"] == "tpu"
    env = _pod_env(spawned, kt.Compute(tpu="v5e-4",
                                       env={"JAX_PLATFORMS": "cpu"}))
    assert env["JAX_PLATFORMS"] == "cpu"
    # pods write where a failure can be read afterwards, not to /dev/null
    from kubetorch_tpu.config import config
    assert os.path.exists(os.path.join(config().config_dir, "logs",
                                       "default__svc-0.log"))


def test_controller_daemon_is_held_to_the_cpu(spawned, monkeypatch):
    from kubetorch_tpu import client
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    client._spawn_local_daemon_locked()
    argv, env = spawned[-1]
    assert "kubetorch_tpu.controller.app" in argv
    assert env["JAX_PLATFORMS"] == "cpu"


def test_requests_tpu_reads_the_manifest():
    import kubetorch_tpu as kt
    from kubetorch_tpu.controller.backends import requests_tpu
    assert requests_tpu(kt.Compute(tpu="v5e-1").manifest("s", env={}))
    assert requests_tpu(kt.Compute(tpu="v5e-4").distribute("jax")
                        .manifest("s", env={}))
    assert not requests_tpu(kt.Compute(cpus=2).manifest("s", env={}))
    assert not requests_tpu(kt.Compute(gpus=1).manifest("s", env={}))


# -- the rank: compile cache and accelerator check ---------------------------

def _call_in_rank(fn_name, args, monkeypatch):
    """One call through the in-process pod server → a real spawned rank."""
    from aiohttp.test_utils import TestClient, TestServer

    from kubetorch_tpu.serving.env_contract import METADATA_KEYS
    from kubetorch_tpu.serving.http_server import ServerState, create_app

    for k in METADATA_KEYS:
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("KT_PROJECT_ROOT", ASSETS)
    monkeypatch.setenv("KT_MODULE_NAME", "payloads")
    monkeypatch.setenv("KT_FILE_PATH", "payloads.py")
    monkeypatch.setenv("KT_CLS_OR_FN_NAME", fn_name)
    monkeypatch.setenv("KT_LAUNCH_ID", "l1")

    async def go():
        client = TestClient(TestServer(create_app(ServerState())))
        await client.start_server()
        try:
            r = await client.post(f"/{fn_name}",
                                  json={"args": args, "kwargs": {}})
            return r.status, await r.json()
        finally:
            await client.close()
    return asyncio.run(go())


def test_same_launch_reload_keeps_the_loading_ranks(monkeypatch):
    """A pod booted BY a launch (env carries its id and metadata) gets that
    launch's reload pushed once its websocket connects. Seen on the chip:
    the reload tore the warming rank pool down and loaded the whole model a
    second time. Same launch, same config, no code or image change → the
    ranks stay; a new launch id still reloads."""
    from aiohttp.test_utils import TestClient, TestServer

    from kubetorch_tpu.serving.env_contract import METADATA_KEYS
    from kubetorch_tpu.serving.http_server import ServerState, create_app

    for k in METADATA_KEYS:
        monkeypatch.delenv(k, raising=False)
    meta = {"KT_PROJECT_ROOT": ASSETS, "KT_MODULE_NAME": "payloads",
            "KT_FILE_PATH": "payloads.py", "KT_CLS_OR_FN_NAME": "whoami"}
    for k, v in meta.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv("KT_LAUNCH_ID", "l1")

    async def go():
        state = ServerState()
        client = TestClient(TestServer(create_app(state)))
        await client.start_server()           # boots + prewarms from env
        try:
            async def pid():
                r = await client.post("/whoami", json={"args": [],
                                                       "kwargs": {}})
                assert r.status == 200, await r.text()
                return (await r.json())["pid"]
            first = await pid()
            await state.reload({**meta, "KT_LAUNCH_ID": "l1"}, "l1")
            assert await pid() == first       # same launch: ranks kept
            await state.reload({**meta, "KT_LAUNCH_ID": "l2"}, "l2")
            assert state.launch_id == "l2"
            assert await pid() != first       # a real reload respawns
        finally:
            await client.close()
    asyncio.run(go())


def test_every_rank_gets_the_in_checkout_compile_cache(monkeypatch):
    """Not only ``.distribute("jax")`` ranks: a plain fn's rank (FrameworkEnv)
    has the cache placed before it can import jax."""
    from kubetorch_tpu.compile_cache import DEFAULT_DIR
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    status, body = _call_in_rank("echo_env", ["JAX_COMPILATION_CACHE_DIR"],
                                 monkeypatch)
    assert status == 200, body
    assert body["JAX_COMPILATION_CACHE_DIR"] == DEFAULT_DIR
    # set from outside: left alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/outside/choice")
    status, body = _call_in_rank("echo_env", ["JAX_COMPILATION_CACHE_DIR"],
                                 monkeypatch)
    assert body["JAX_COMPILATION_CACHE_DIR"] == "/outside/choice"


def test_compile_cache_helper(monkeypatch):
    from kubetorch_tpu import compile_cache
    monkeypatch.setenv(compile_cache.ENV, "/outside/choice")
    assert compile_cache.ensure_compile_cache() == "/outside/choice"
    assert os.environ[compile_cache.ENV] == "/outside/choice"
    # unset: one fixed directory inside the checkout — never a temp dir, the
    # home dir, a pid or a time (the path is part of the cache key)
    monkeypatch.delenv(compile_cache.ENV)
    import jax
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.ensure_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert os.environ[compile_cache.ENV] == path
        assert compile_cache.ensure_compile_cache() == path      # stable
        assert not path.startswith(("/tmp", os.path.expanduser("~") + "/.",
                                    "/var/tmp"))
        # jax was imported before the call (pytest): its live config follows
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # git ignores it
    assert ".jax_cache/" in open(os.path.join(REPO, ".gitignore")).read()


def test_compile_cache_helper_is_jax_free():
    """Rank start calls it before anything may import jax."""
    code = ("import sys; from kubetorch_tpu.compile_cache import "
            "ensure_compile_cache as e; e(); import kubetorch_tpu."
            "serving.process_worker; assert 'jax' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO,
                   timeout=60)


def test_rank_given_the_tpu_refuses_the_cpu(monkeypatch):
    """JAX_PLATFORMS naming tpu first is the statement that the rank owns
    the chip; here there is none, so the load fails typed and the error
    reaches the caller — the callable never runs on the CPU."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    status, body = _call_in_rank("summer", [1, 2], monkeypatch)
    assert status == 500, body
    assert body["error_type"] == "AcceleratorUnavailableError", body
    from kubetorch_tpu.exceptions import (AcceleratorUnavailableError,
                                          StartupError, rehydrate_exception)
    exc = rehydrate_exception(body)
    assert isinstance(exc, AcceleratorUnavailableError)
    assert isinstance(exc, StartupError)


def test_require_accelerator_unit(monkeypatch):
    from kubetorch_tpu.exceptions import AcceleratorUnavailableError
    from kubetorch_tpu.serving.process_worker import require_accelerator
    for held_to_cpu in ("cpu", "", "cpu,tpu"):
        monkeypatch.setenv("JAX_PLATFORMS", held_to_cpu)
        require_accelerator()                  # not given the chip: no check
    # given the chip, but this process's jax is on the CPU
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(AcceleratorUnavailableError) as e:
        require_accelerator()
    assert e.value.backend == "cpu"


def test_jax_env_names_libtpu_hosts_only_for_many(monkeypatch):
    from kubetorch_tpu.serving.env_contract import (FrameworkEnv, JaxEnv,
                                                    RankInfo)
    one = RankInfo(node_rank=0, local_rank=0, nproc_per_node=1, num_nodes=1,
                   pod_ips=["127.77.1.1"])
    two = RankInfo(node_rank=1, local_rank=0, nproc_per_node=1, num_nodes=2,
                   pod_ips=["10.0.0.1", "10.0.0.2"])
    env = JaxEnv().env(one)
    # libtpu reads these; a one-host world named by a loopback alias is not
    # a slice it can resolve
    assert "TPU_WORKER_HOSTNAMES" not in env and "TPU_WORKER_ID" not in env
    assert env["JAX_NUM_PROCESSES"] == "1"
    env = JaxEnv().env(two)
    assert env["TPU_WORKER_HOSTNAMES"] == "10.0.0.1,10.0.0.2"
    assert env["TPU_WORKER_ID"] == "1"
    # the cache is placed by the rank itself, for every framework
    for fw in (JaxEnv(), FrameworkEnv()):
        assert "JAX_COMPILATION_CACHE_DIR" not in fw.env(two)


# -- kernels: no quiet interpret, no caught-and-replaced ---------------------

def test_interpret_default_needs_the_cpu_to_be_asked_for():
    import jax

    from kubetorch_tpu.ops.backend import interpret_default
    assert jax.config.jax_platforms == "cpu"       # conftest asked for it
    assert interpret_default() is True
    before = jax.config.jax_platforms
    try:
        # wanted the chip, landed on the CPU: never interpret and pass
        for wanted in ("tpu,cpu", "tpu", ""):
            jax.config.update("jax_platforms", wanted)
            with pytest.raises(RuntimeError, match="asked for"):
                interpret_default()
    finally:
        jax.config.update("jax_platforms", before)


def test_flash_supported_is_a_shape_decision():
    from kubetorch_tpu.ops.attention import flash_supported
    assert flash_supported(128, 8, 4) and flash_supported(2048, 32, 8)
    assert not flash_supported(64, 8, 4)         # below the tile
    assert not flash_supported(197, 12, 12)      # ViT patches: no tile fits
    assert not flash_supported(256, 8, 3)        # GQA must divide


def test_no_except_between_a_kernel_and_a_reference():
    """A shape the kernel cannot take is decided beforehand; a compile error
    on the chip must surface, not select the XLA reference."""
    import re
    pkg = os.path.join(REPO, "kubetorch_tpu")
    hits = []
    for sub in ("ops", "models", "parallel", "serve"):
        for root, _, files in os.walk(os.path.join(pkg, sub)):
            for f in files:
                if not f.endswith(".py"):
                    continue
                src = open(os.path.join(root, f)).read()
                for m in re.finditer(
                        r"try:\n(?:.*\n){1,6}?.*(flash_attention|"
                        r"decode_attention|q4_matmul)\(.*\n(?:.*\n){0,4}?"
                        r"\s+except Exception", src):
                    hits.append((f, m.group(0)))
    assert not hits, hits


def test_kernels_run_sharded_over_batch_and_heads(cpu_mesh_devices):
    """Under a mesh the Pallas calls go through shard_map (GSPMD cannot
    partition a custom call): same numbers as the bare kernel, and a dim an
    axis does not divide stays unsharded on it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubetorch_tpu.ops.attention import flash_attention
    from kubetorch_tpu.ops.decode_attention import decode_attention
    from kubetorch_tpu.parallel import kernel_shard as ks
    from kubetorch_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"fsdp": 2, "tensor": 2}, devices=cpu_mesh_devices[:4])
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (2, 128, 4, 16), jnp.float32)
    k = jax.random.normal(keys[1], (2, 128, 2, 16), jnp.float32)
    v = jax.random.normal(keys[2], (2, 128, 2, 16), jnp.float32)
    want = flash_attention(q, k, v)
    got = jax.jit(lambda q, k, v: ks.flash_attention_sharded(
        q, k, v, mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert ks._axes(mesh, 2, 4, 2) == ("fsdp", "tensor")
    assert ks._axes(mesh, 1, 4, 2) == (None, "tensor")    # batch-1 prefill
    assert ks._axes(mesh, 2, 3, 3) == ("fsdp", None)      # heads don't divide
    assert ks._axes(None, 2, 4, 2) is None                # off-mesh: direct
    # gradients flow through the shard_map'd custom_vjp
    g = jax.grad(lambda q: jnp.sum(ks.flash_attention_sharded(
        q, k, v, mesh) ** 2))(q)
    gw = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gw), atol=1e-4)

    qd = jax.random.normal(keys[0], (4, 4, 16), jnp.float32)
    # the engine's stacked head-major grid (L, B, NKV, S, Hd), layer 1
    ck = jax.random.normal(keys[1], (2, 4, 2, 256, 16), jnp.float32)
    cv = jax.random.normal(keys[2], (2, 4, 2, 256, 16), jnp.float32)
    pos = jnp.asarray([0, 7, 130, 255], jnp.int32)
    want = decode_attention(qd, ck, cv, pos, 1)
    got = jax.jit(lambda *a: ks.decode_attention_sharded(*a, mesh))(
        qd, ck, cv, pos, jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_build_mesh_does_not_swallow_a_tpu_placement_failure():
    """Fake TPU devices that mesh_utils cannot place: the seed reshaped them
    in enumeration order and carried on."""
    from kubetorch_tpu.parallel.mesh import build_mesh
    fakes = [types.SimpleNamespace(platform="tpu", id=i, process_index=0)
             for i in range(4)]
    with pytest.raises(Exception):
        build_mesh({"fsdp": 4}, devices=fakes)


# -- no chip, no number -------------------------------------------------------

def _run(argv, **env):
    return subprocess.run([sys.executable] + argv, cwd=REPO, text=True,
                          capture_output=True, timeout=600,
                          env={**os.environ, **env})


def test_bench_exits_nonzero_without_a_tpu():
    r = _run(["bench.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout       # no number under any name
    assert "no TPU" in r.stderr


def test_bench_peak_flops_raises_on_an_unknown_device():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    assert bench.peak_flops(types.SimpleNamespace(
        device_kind="TPU v5 lite")) == 197e12
    with pytest.raises(KeyError, match="no bf16 peak"):
        bench.peak_flops(types.SimpleNamespace(device_kind="cpu"))
    with pytest.raises(KeyError):
        bench.peak_flops(types.SimpleNamespace(device_kind="TPU v9 mega"))
    src = open(os.path.join(REPO, "bench.py")).read()
    for gone in ("_cpu_fallback", "_cached_tpu_result", "probe_worker",
                 "KT_BENCH_BUDGET_S", "bench_fingerprint", "force_cpu"):
        assert gone not in src, gone


def test_chip_smoke_bare_invocation_fails_without_a_tpu():
    r = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout       # prints no result
    assert "no TPU" in r.stderr


def test_chip_smoke_rehearsal_runs_the_whole_control_flow():
    """--rehearse: controller daemon → pod → rank, serving + reload +
    training at tiny shapes on the CPU; labelled on every line and in the
    result, and never what a bare invocation does."""
    r = _run(["chip_smoke.py", "--rehearse"], JAX_PLATFORMS="cpu")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert all(l.startswith("[REHEARSAL on the CPU") for l in lines[:-1])
    report = json.load(open(os.path.join(
        REPO, "chiprun_out", "chip_smoke", "report.json")))
    assert report["requests"]["n"] >= 8
    assert report["reference"]["equals_generate"] is True
    assert len(report["kernels"]) == 9
    cold, warm = report["serving_cold"], report["serving_reloaded"]
    assert warm["pid"] != cold["pid"]
    assert warm["cache_entries_after_warmup"] == warm["cache_entries_at_start"]
    assert len(report["training"]["one_chip"]["losses"]) >= 3
