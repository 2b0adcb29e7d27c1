"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, metrics (end-to-end and per-layer),
limits and runner are files found by the names in ``BENCHMARK.json``; nothing
here knows a cell, a metric or a kind of configuration. The runner
(``runners/<kind>.py``) deploys the benchmark's service through the fabric's
own entry, drives the window and has the rank check what the timed path
produced against the plain reference; every metric is taken from what the
runner hands back by a reader of its own (``metrics/<name>.json`` names it).

This process never imports jax: a chip belongs to one process, and that is
the rank the fabric spawns. It starts the local controller daemon under a
``KT_CONFIG_DIR`` inside the checkout, and when the runner is done shuts it
down and prints one JSON line. Without a TPU it exits non-zero and prints no
result. ``--rehearse`` is a labelled CPU rehearsal of the same control flow
at tiny shapes (``benchmark/tests/data``); it prints no device metric.
"""

from __future__ import annotations

import time

T_START = time.monotonic()          # before anything heavy is imported

import argparse                      # noqa: E402
import importlib.util                # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import signal                        # noqa: E402
import sys                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench_traffic                 # noqa: E402

RUN_DIR = os.path.join(ROOT, ".bench_run")
REHEARSAL_DATA = os.path.join(HERE, "tests", "data")
# a runner or the limits tool says ``import run``: this module, run as a
# script or not, and never a second copy with a later T_START
sys.modules.setdefault("run", sys.modules[__name__])


class BenchFailure(Exception):
    pass


# ---------------------------------------------------------------------------
# The cell, from data
# ---------------------------------------------------------------------------

def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, bench_file: str, data_root: str) -> dict:
    """Everything one cell needs, found by name. ``data_root`` holds
    ``traffic/`` and ``limits/``; metric, reader and runner files are looked
    for there first and then in ``benchmark/``."""
    bench = read_json(bench_file)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise BenchFailure(f"no workload {workload!r} in {bench_file}; there "
                           f"are {[w['name'] for w in bench['workloads']]}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = read_json(os.path.join(ROOT, entry["file"]))
    mix = bench_traffic.load(cell["traffic"], data_root)

    def in_cell(m):
        return workload in m.get("workloads", [workload])

    def find(kind, name):
        for root in (data_root, HERE):
            path = os.path.join(root, kind, name)
            if os.path.exists(path):
                return path
        raise BenchFailure(f"no {kind}/{name} under {data_root} or {HERE}")

    def metrics(which):
        out = []
        for m in bench[which]:
            if in_cell(m):
                spec = read_json(find("metrics", m["name"] + ".json"))
                out.append({**m, **spec, "reader_file": find(
                    "readers", spec["reader"] + ".py")})
        return out

    return {"bench": bench, "cell": cell, "config": cfg, "mix": mix,
            "end_to_end": metrics("end_to_end"),
            "per_layer": metrics("per_layer"),
            "runner_file": find("runners", cfg["kind"] + ".py"),
            "limits": read_json(find("limits", workload + ".json"))}


def resolve_for(args) -> dict:
    """The cell a command line names: of the benchmark, or with
    ``--rehearse`` of the rehearsal's own small one."""
    if args.rehearse:
        return resolve(args.workload, os.path.join(
            REHEARSAL_DATA, "BENCHMARK.json"), REHEARSAL_DATA)
    return resolve(args.workload, os.path.join(ROOT, "BENCHMARK.json"), HERE)


def load_file(path: str, prefix: str = "bench_file_"):
    """A module from its file: a reader, a runner, a configuration's
    service."""
    name = prefix + os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Judging, and metrics through their readers
# ---------------------------------------------------------------------------

def judge(compared: dict, limits: dict) -> bool:
    """The comparison that decides ``correct``: every number compared lies
    at or under its limit (one that is not a number does not)."""
    return all(compared[k] <= limits[k] for k in compared)


def read_metrics(cell: dict, which: str, out: dict) -> dict:
    """Each metric of the cell's ``which`` list through its own reader; one
    that finds nothing to read returns None and is left out."""
    import bench_flops
    ctx = {"config": cell["config"], "mix": cell["mix"],
           "chips": cell["cell"]["chips"],
           "peak": bench_flops.peaks(out["device"]["kind"]),
           "window": out["window"], "trace": out["traced"],
           "records": out["records"], "flops": bench_flops}
    metrics = {}
    for m in cell[which]:
        value = load_file(m["reader_file"]).read(ctx, **m.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


# ---------------------------------------------------------------------------
# The fabric
# ---------------------------------------------------------------------------

def wait_pid_gone(pid: int, timeout: float = 120.0) -> None:
    import psutil
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if not psutil.pid_exists(pid) or \
                psutil.Process(pid).status() == psutil.STATUS_ZOMBIE:
            return
        time.sleep(0.1)
    raise BenchFailure(f"rank pid {pid} still alive {timeout:.0f}s after "
                       "teardown: the chip is not free")


def print_logs() -> None:
    """Pods and the daemon log to files; on failure their tails are the only
    place a rank that could not open the chip said so."""
    logs = [os.path.join(RUN_DIR, "kt", "local-controller.log")]
    pod_dir = os.path.join(RUN_DIR, "kt", "logs")
    if os.path.isdir(pod_dir):
        logs += sorted(os.path.join(pod_dir, f) for f in os.listdir(pod_dir))
    for path in logs:
        try:
            with open(path, errors="replace") as f:
                tail = "".join(line for line in f
                               if "cpu_aot_loader.cc" not in line)[-4000:]
        except OSError:
            continue
        print(f"---- {os.path.relpath(path, ROOT)} (tail) ----\n{tail}",
              file=sys.stderr)


def fabric_env(rehearse: bool, chips: int) -> dict:
    """The parent's own environment for the fabric, and what the rank gets."""
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    os.makedirs(os.path.join(RUN_DIR, "kt"))
    os.environ.update({
        "KT_CONFIG_DIR": os.path.join(RUN_DIR, "kt"),
        "KT_CONFIG_PATH": os.path.join(RUN_DIR, "kt", "config"),
        "KT_LOCAL_MODE": "1", "KT_USERNAME": "bench",
        "KT_STREAM_LOGS": "0", "KT_CONTROLLER_REPLACE": "always",
    })
    env = {
        # a fixed directory inside the checkout: the path is part of the
        # cache's key. One given from outside is left alone.
        "JAX_COMPILATION_CACHE_DIR": os.environ.get(
            "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache")),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
        # libtpu would log under /tmp/tpu_logs, outside the checkout
        "TPU_LOG_DIR": "disabled",
    }
    if rehearse:
        env.update({"JAX_PLATFORMS": "cpu", "XLA_FLAGS":
                    f"--xla_force_host_platform_device_count={chips}"})
    return env


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny shapes; not a chip result")
    args = ap.parse_args(argv)
    tag = "[REHEARSAL on the CPU: not a chip result] " if args.rehearse else ""

    def say(msg: str) -> None:
        print(f"{tag}{msg}", file=sys.stderr, flush=True)

    assert "jax" not in sys.modules, "the parent must stay off jax"
    try:
        import kubetorch_tpu as kt
        from kubetorch_tpu.client import shutdown_local_controller
    except ImportError as e:
        print(f"benchmark/run.py drives the checkout it sits in, and the "
              f"program is not there: {e}", file=sys.stderr)
        return 1
    try:
        cell = resolve_for(args)
    except BenchFailure as e:
        print(str(e), file=sys.stderr)
        return 1
    env = fabric_env(args.rehearse, cell["cell"]["chips"])

    def out_of_time(*_):
        raise BenchFailure("time limit reached")
    signal.signal(signal.SIGALRM, out_of_time)
    signal.alarm(1150)

    out = None
    try:
        out = load_file(cell["runner_file"]).run(kt, cell, args, env, say)
        assert "jax" not in sys.modules, "the parent imported jax"
    except BaseException as e:  # noqa: BLE001 — report, clean up, exit 1
        say(f"FAILED: {type(e).__name__}: {e}")
        print_logs()
    finally:
        signal.alarm(0)
        try:
            shutdown_local_controller()
        except Exception as e:  # noqa: BLE001
            say(f"controller shutdown: {e}")
    if out is None:
        return 1

    device = {k: out["device"][k]
              for k in ("platform", "kind", "count", "memory_peak_bytes")}
    result = {"correct": judge(out["compared"], out["limits"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    if args.rehearse:
        # counts only: a CPU timing is never written under a device metric
        result.update({"rehearsal": True, "metrics": {}})
    elif args.trace:
        device.update({"busy_s": out["traced"]["busy_s"],
                       "window_s": out["traced"]["window_s"]})
        result["metrics"] = read_metrics(cell, "per_layer", out)
        result["breakdown"] = out["traced"]["breakdown"]
    else:
        result["metrics"] = read_metrics(cell, "end_to_end", out)
    result["device"] = device
    result["compared"] = {k: {"value": v, "limit": out["limits"][k]}
                          for k, v in out["compared"].items()}
    for k, v in result["compared"].items():
        say(f"compared {k} = {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
