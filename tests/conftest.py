"""Test configuration.

Test strategy follows SURVEY.md §4: in-process server tests, a LOCAL_IPS-style
fake for multi-host discovery, and sharding tests on a virtual 8-device CPU
mesh (``xla_force_host_platform_device_count``) — no cluster and no TPU
required. The env vars must be set before jax is imported anywhere.
"""

import os

# Virtual 8-device CPU mesh for all sharding/parallelism tests: jax is held
# to the CPU in the env (subprocesses inherit it) and in the live config. The
# XLA flag is read at first backend init, which hasn't happened yet.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import hashlib  # noqa: E402
import uuid  # noqa: E402

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Test levels (reference tests/conftest.py:27-135): --level keeps only tests
# whose @pytest.mark.level matches. unit < minimal < release < tpu.
# Default: everything except tpu (which needs the real chip).
# ---------------------------------------------------------------------------

LEVELS = ("unit", "minimal", "release", "tpu")


def pytest_addoption(parser):
    parser.addoption("--level", default=None, choices=LEVELS,
                     help="run only tests marked with this level")


def pytest_configure(config):
    config.addinivalue_line("markers",
                            "level(name): test tier (unit/minimal/release/tpu)")


def pytest_collection_modifyitems(config, items):
    """--level X runs every tier UP TO X (unit < minimal < release), the
    reference's cumulative ordering: ``--level minimal`` is the fast default
    (`make test-fast`, skips the jit-heavy release matrix), no flag runs
    everything except tpu, ``--level tpu`` adds the real-chip tier."""
    want = config.getoption("--level")
    for item in items:
        mark = item.get_closest_marker("level")
        level = mark.args[0] if mark else "unit"
        if want is not None:
            if LEVELS.index(level) > LEVELS.index(want):
                item.add_marker(pytest.mark.skip(
                    reason=f"level {level} > requested {want}"))
        elif level == "tpu":
            item.add_marker(pytest.mark.skip(
                reason="tpu-level tests need --level tpu and a real chip"))


# Session-hash service-name prefix (reference conftest.py:138-161): every
# service deployed under this username is torn down at session end, so a
# crashed run never leaks pods into the next.
SESSION_HASH = "t-" + hashlib.sha1(uuid.uuid4().bytes).hexdigest()[:5]


@pytest.fixture(scope="session", autouse=True)
def session_isolation():
    import shutil
    import tempfile

    # force-set (saving any prior value): deploys MUST land under the sweep
    # prefix or a crashed run leaks pods
    prior = os.environ.get("KT_USERNAME")
    os.environ["KT_USERNAME"] = SESSION_HASH
    # isolate controller durability: a daemon started by this session must
    # not restore (or persist) workloads across test sessions
    prior_state_dir = os.environ.get("KT_CONTROLLER_STATE_DIR")
    state_dir = tempfile.mkdtemp(prefix="kt-test-state-")
    os.environ["KT_CONTROLLER_STATE_DIR"] = state_dir
    # a daemon left over from an older checkout must be replaced, not reused
    # (the interactive default warns and reuses when it hosts workloads)
    prior_replace = os.environ.get("KT_CONTROLLER_REPLACE")
    os.environ["KT_CONTROLLER_REPLACE"] = "always"
    from kubetorch_tpu.client import (ControllerClient, _read_running_local,
                                      shutdown_local_controller)
    from kubetorch_tpu.config import reset_config

    # the config singleton may already be materialized with the old
    # username; rebuild it so deploys land under the sweep prefix
    reset_config()
    preexisting_daemon = _read_running_local() is not None
    yield
    try:
        state = _read_running_local()
        if state is not None:
            client = ControllerClient(state["url"])
            for w in client.list_workloads():
                if w["name"].startswith(SESSION_HASH):
                    client.delete_workload(w["namespace"], w["name"])
            # only stop a daemon the session itself caused to exist — a
            # developer's persistent `kt controller start` (and their
            # workloads) must survive a pytest run
            if not preexisting_daemon:
                shutdown_local_controller()
    except Exception:
        pass
    if prior is None:
        os.environ.pop("KT_USERNAME", None)
    else:
        os.environ["KT_USERNAME"] = prior
    if prior_state_dir is None:
        os.environ.pop("KT_CONTROLLER_STATE_DIR", None)
    else:
        os.environ["KT_CONTROLLER_STATE_DIR"] = prior_state_dir
    if prior_replace is None:
        os.environ.pop("KT_CONTROLLER_REPLACE", None)
    else:
        os.environ["KT_CONTROLLER_REPLACE"] = prior_replace
    shutil.rmtree(state_dir, ignore_errors=True)


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax
    devices = jax.devices()
    assert len(devices) >= 8, "conftest must provide >= 8 virtual devices"
    return devices


@pytest.fixture()
def tmp_project(tmp_path):
    """A throwaway project dir with a marker so locate_working_dir resolves."""
    (tmp_path / ".git").mkdir()
    return tmp_path


@pytest.fixture()
def hold_heads(monkeypatch):
    """Steer the decode kernel's wrapper to ``per_step`` ("one", "divisor":
    the largest proper one, "all") KV heads a grid step the only way there
    is: through the VMEM budget it divides by. Returns the plan the wrapper
    will then make, after asserting it is the one asked for."""
    from kubetorch_tpu.ops import decode_attention as kernel_mod

    def hold(per_step, b, nkv, s, hd, itemsize, block_k=512):
        want = {"one": 1, "all": nkv}.get(per_step) or max(
            [h for h in range(1, nkv) if nkv % h == 0], default=1)
        bk = kernel_mod.decode_plan(b, nkv, s, hd, itemsize,
                                    block_k=block_k).block_k
        monkeypatch.setattr(kernel_mod, "KV_VMEM_BUDGET",
                            4 * want * bk * hd * itemsize)
        plan = kernel_mod.decode_plan(b, nkv, s, hd, itemsize,
                                      block_k=block_k)
        assert plan.heads == want, (plan, want)
        assert plan.grid == (b * nkv // want, s // bk), plan
        return plan
    return hold
