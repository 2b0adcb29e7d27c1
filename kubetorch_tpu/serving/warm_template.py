"""Pre-warmed template fork: replica boot without import or pickle (ISSUE 16).

A cold serving replica pays four bills serially: python import (~1–3s),
weight load (pickle/npz decode, multi-GB at scale), XLA compile (tens of
seconds cold), first token. This module collapses the first two to ~0
and hands the third to the persistent AOT cache
(``serve/aot_cache.py``):

- The **template** process imports everything, stages the model's
  weights into ONE shared-memory segment (``shm_ring.create_weight_
  segment`` — the module that owns all SharedMemory lifecycle), binds a
  unix socket, and waits. It deliberately NEVER initializes the JAX
  backend: XLA's thread pools don't survive ``fork()``, so the template
  stays a pure python+numpy process and each forked child initializes
  JAX fresh — the compile win comes from the on-disk AOT cache, not an
  inherited jit cache.
- A **fork request** makes the template ``os.fork()``; the child
  attaches the weight segment (one memcpy per leaf, zero pickle),
  builds the engine against the warm AOT cache, generates a probe
  token, writes its per-phase boot anatomy to the result dir, exits.
- The **supervisor** (driver side) spawns the template, requests forks,
  respawns the template if it dies (the ``kill-template`` chaos verb),
  re-forks children that die mid-boot (``kill-joiner``), and best-effort
  unlinks the weight segment by name on teardown — a SIGKILLed template
  runs no destructor, so crash cleanup is the supervisor's job and
  ``/dev/shm`` never leaks across generations.

Chaos determinism: the TEMPLATE consumes both kill plans (it is the
sole forker). ``kill-template@N`` self-delivers at its N-th fork op;
``kill-joiner@N`` is popped from the plan when fork index N is first
requested and the signal rides the fork call into that child — so a
re-forked survivor with the same index lives, and the drill converges.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, Optional

from . import shm_ring

READY_PREFIX = "KT_TEMPLATE_READY "


# -- weights on disk (numpy-only: the template must not touch jax) ----------

def save_weights(path: os.PathLike, params: Any) -> None:
    """Write a params pytree as a numpy-pickled blob a process can load
    WITHOUT initializing jax (np.asarray any jax leaves first)."""
    import numpy as np

    def _np(o):
        if isinstance(o, dict):
            return {k: _np(v) for k, v in o.items()}
        if isinstance(o, tuple):
            return tuple(_np(v) for v in o)
        if isinstance(o, list):
            return [_np(v) for v in o]
        return np.asarray(o)

    np.save(os.fspath(path), np.array(_np(params), dtype=object),
            allow_pickle=True)


def load_weights(path: os.PathLike) -> Any:
    import numpy as np
    return np.load(os.fspath(path), allow_pickle=True).item()


# -- model spec → config (built in the CHILD, post-fork) --------------------

def _build_cfg(model: Dict[str, Any]):
    """Config object from the spec's model dict. Kinds are the bench/test
    models; real deployments construct the engine directly and only use
    the cache + segment layers."""
    kind = model.get("kind", "llama-tiny")
    if kind == "llama-tiny":
        import jax.numpy as jnp
        from ..models.llama import LlamaConfig
        kwargs = dict(model.get("kwargs") or {})
        kwargs.setdefault("attn_impl", "xla")
        kwargs.setdefault("remat", False)
        return LlamaConfig.tiny(dtype=jnp.float32, **kwargs)
    raise ValueError(f"unknown template model kind {kind!r}")


# -- the forked replica (and the cold-boot A/B arm) -------------------------

def _boot_engine(spec: Dict, params_np, phases: Dict[str, float],
                 aot_root: Optional[str]):
    """Shared engine-boot tail: device_put the host weights (attach
    phase's second half), init the engine through the AOT cache, probe
    one token. Returns (engine, aot_stats)."""
    import jax.numpy as jnp
    import jax

    t = time.monotonic()
    params = jax.tree_util.tree_map(jnp.asarray, params_np)
    phases["weight_attach"] = phases.get("weight_attach", 0.0) + (
        time.monotonic() - t)

    cache = None
    if aot_root:
        from ..serve.aot_cache import AOTCompileCache
        cache = AOTCompileCache(aot_root)
    t = time.monotonic()
    from ..serve.engine import GenerationEngine
    eng = GenerationEngine(params, _build_cfg(spec.get("model") or {}),
                           aot_cache=cache,
                           **(spec.get("engine") or {}))
    phases["compile_or_cache"] = time.monotonic() - t

    t = time.monotonic()
    probe = spec.get("probe_prompt") or [1, 2, 3]
    h = eng.submit(list(probe), max_new_tokens=int(
        spec.get("probe_tokens", 2)))
    while eng.step():
        pass
    h.result(timeout=0)
    phases["first_token"] = time.monotonic() - t
    return eng, (eng.aot_stats() if cache else {})


def _write_result(spec: Dict, name: str, payload: Dict) -> None:
    out = Path(spec["result_dir"])
    out.mkdir(parents=True, exist_ok=True)
    tmp = out / f".{name}.tmp"
    tmp.write_text(json.dumps(payload))
    os.replace(tmp, out / f"{name}.json")


def _observe_phases(phases: Dict[str, float], total: float) -> None:
    try:
        from .. import telemetry
        fam = telemetry.cold_start_metrics()
        for phase, dt in phases.items():
            fam["phase_seconds"].observe(dt, phase=phase)
        fam["total"].set(total)
        fam["boot_ts"].set(time.time())
    except Exception:
        pass


def _replica_main(spec: Dict, manifest: Dict, idx: int,
                  kill_sig: Optional[int]) -> None:
    """Runs in the forked child: attach → (chaos) → engine → probe →
    result file. Never returns (``os._exit``) so the child can't fall
    back into the template's accept loop."""
    code = 0
    try:
        t_start = time.monotonic()
        phases: Dict[str, float] = {"import": 0.0}   # template paid it
        t = time.monotonic()
        params_np = shm_ring.attach_weight_segment(manifest)
        phases["weight_attach"] = time.monotonic() - t
        if kill_sig is not None:
            # kill-joiner: die mid-boot, weights attached but not serving
            os.kill(os.getpid(), kill_sig)
        eng, aot = _boot_engine(spec, params_np, phases,
                                spec.get("aot_root"))
        total = time.monotonic() - t_start
        _observe_phases(phases, total)
        _write_result(spec, f"replica_{idx}",
                      {"idx": idx, "pid": os.getpid(), "mode": "fork",
                       "ok": True, "phases": phases, "total_s": total,
                       "aot": aot})
        eng.stop()
    except BaseException as e:  # noqa: BLE001 — child reports, never raises
        code = 1
        try:
            _write_result(spec, f"replica_{idx}",
                          {"idx": idx, "pid": os.getpid(), "mode": "fork",
                           "ok": False, "error": f"{type(e).__name__}: {e}"})
        except Exception:
            pass
    finally:
        os._exit(code)


def cold_boot_main(spec_path: str, idx: int, import_t0: float) -> None:
    """The A/B baseline: a fresh interpreter that pays import + weight
    load + compile with no template and (typically) an empty AOT dir.
    ``import_t0`` is the wall-clock the parent recorded at spawn, so the
    import phase covers the interpreter+jax import bill this process
    already paid before reaching here."""
    spec = json.loads(Path(spec_path).read_text())
    t_start = time.monotonic()
    phases: Dict[str, float] = {"import": max(0.0, time.time() - import_t0)}
    t = time.monotonic()
    params_np = load_weights(spec["weights"])
    phases["weight_fetch"] = time.monotonic() - t
    eng, aot = _boot_engine(spec, params_np, phases, spec.get("aot_root"))
    total = phases["import"] + (time.monotonic() - t_start)
    _observe_phases(phases, total)
    _write_result(spec, f"cold_{idx}",
                  {"idx": idx, "pid": os.getpid(), "mode": "cold",
                   "ok": True, "phases": phases, "total_s": total,
                   "aot": aot})
    eng.stop()


# -- the template process ---------------------------------------------------

def template_main(spec_path: str) -> None:
    """The template's whole life: load weights (numpy), stage the shm
    segment, announce readiness on stdout, serve fork requests over the
    unix socket until ``shutdown``. No jax backend init, ever — see the
    module docstring."""
    spec = json.loads(Path(spec_path).read_text())
    chaos_spec = spec.get("chaos")            # None → read KT_CHAOS env
    from ..chaos import template_kill_plan, joiner_kill_plan
    kill_plan = template_kill_plan(chaos_spec)
    joiner_plan = dict(joiner_kill_plan(chaos_spec))

    # Pre-pay the import bill for every future child: jax and the engine
    # module are IMPORT-safe to fork (no backend, no threads — asserted
    # below) even though backend INIT is not. Children inherit warm
    # sys.modules and only initialize XLA post-fork.
    if spec.get("preimport", True):
        import jax._src.xla_bridge as _xb
        from ..serve import engine as _engine  # noqa: F401
        assert not _xb.backends_are_initialized(), \
            "template imported a module that initialized the JAX backend " \
            "— forked children would inherit dead XLA thread pools"

    params_np = load_weights(spec["weights"])
    seg = shm_ring.create_weight_segment(params_np, tag="template")
    sock_path = spec["socket"]
    try:
        os.unlink(sock_path)
    except FileNotFoundError:
        pass
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.bind(sock_path)
    srv.listen(16)
    print(f"{READY_PREFIX}{json.dumps({'segment': seg.name})}", flush=True)

    fork_op = 0
    try:
        while True:
            conn, _ = srv.accept()
            with conn:
                try:
                    req = json.loads(conn.makefile("r").readline() or "{}")
                except ValueError:
                    continue
                cmd = req.get("cmd")
                if cmd == "ping":
                    conn.sendall(b'{"ok": true}\n')
                elif cmd == "manifest":
                    conn.sendall((json.dumps(
                        {"ok": True, "manifest": seg.manifest}) + "\n")
                        .encode())
                elif cmd == "shutdown":
                    conn.sendall(b'{"ok": true}\n')
                    return
                elif cmd == "fork":
                    sig_no = kill_plan.get(fork_op)
                    fork_op += 1
                    if sig_no is not None:
                        # kill-template: die on the fork op, BEFORE the
                        # fork — the supervisor sees EOF and respawns
                        os.kill(os.getpid(), sig_no)
                    idx = int(req.get("idx", fork_op - 1))
                    child_sig = joiner_plan.pop(idx, None)
                    pid = os.fork()
                    if pid == 0:
                        try:
                            srv.close()
                        except Exception:  # noqa: BLE001
                            pass
                        _replica_main(spec, seg.manifest, idx, child_sig)
                        # unreachable: _replica_main os._exits
                    conn.sendall((json.dumps(
                        {"ok": True, "pid": pid, "idx": idx}) + "\n")
                        .encode())
                else:
                    conn.sendall(b'{"ok": false, "error": "bad cmd"}\n')
            # reap any exited children so the accept loop never
            # accumulates zombies across a long burst
            try:
                while os.waitpid(-1, os.WNOHANG)[0]:
                    pass
            except ChildProcessError:
                pass
    finally:
        seg.close()                           # owner: close AND unlink
        try:
            srv.close()
            os.unlink(sock_path)
        except Exception:  # noqa: BLE001
            pass


# -- driver-side supervisor -------------------------------------------------

class TemplateSupervisor:
    """Owns one template subprocess: spawn, fork-by-socket, respawn on
    death, crash-safe segment cleanup. The chaos drill's convergence
    logic lives here — a dead template (kill-template) is respawned with
    its chaos schedule consumed, a dead joiner (kill-joiner) is re-forked
    by the caller via :meth:`fork` with the same index."""

    def __init__(self, spec: Dict, *, timeout: float = 120.0):
        self.spec = dict(spec)
        self.spec.setdefault("chaos",
                             os.environ.get("KT_CHAOS") or None)
        self.timeout = timeout
        self.proc: Optional[subprocess.Popen] = None
        self.segment_name: Optional[str] = None
        self.respawns = 0
        self._tmp = Path(tempfile.mkdtemp(prefix="kt-template-"))
        self.spec.setdefault("socket", str(self._tmp / "template.sock"))
        self._spawn()

    # -- lifecycle ----------------------------------------------------------

    def _spawn(self) -> None:
        import select

        spec_file = self._tmp / f"spec_{self.respawns}.json"
        spec_file.write_text(json.dumps(self.spec))
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "kubetorch_tpu.serving.warm_template",
             str(spec_file)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        deadline = time.monotonic() + self.timeout
        # select() on the stdout fd so the deadline holds even while
        # nothing is printed — a template that wedges before READY (alive
        # but silent; its stderr is DEVNULL) must time out and die, not
        # hang the supervisor on a blocking readline
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                self.proc.kill()
                try:
                    self.proc.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    pass
                raise TimeoutError("template not READY in time")
            readable, _, _ = select.select([self.proc.stdout], [], [],
                                           min(remaining, 1.0))
            if not readable:
                if self.proc.poll() is not None:
                    raise RuntimeError("template died before READY")
                continue
            # READY is one short flush()ed print — an atomic pipe write,
            # so a readable fd means the full line arrives without
            # blocking past the deadline
            line = self.proc.stdout.readline()
            if line.startswith(READY_PREFIX):
                self.segment_name = json.loads(
                    line[len(READY_PREFIX):])["segment"]
                break
            if not line:
                # EOF before READY: the template is dead (or severed its
                # stdout, which is the same thing to us) — reap it
                try:
                    self.proc.wait(timeout=5)
                except Exception:  # noqa: BLE001
                    self.proc.kill()
                raise RuntimeError("template died before READY")

    def _respawn(self) -> None:
        old = self.segment_name
        try:
            if self.proc is not None:
                self.proc.kill()
                self.proc.wait(timeout=10)
        except Exception:  # noqa: BLE001
            pass
        # the dead template ran no destructor: reclaim its segment by
        # name so the burst leaks nothing even under SIGKILL
        if old:
            shm_ring.unlink_weight_segment(old)
        self.respawns += 1
        # the schedule is consumed-once per lineage: the respawned
        # template must not re-arm the verb that just killed it
        self.spec["chaos"] = ""
        self._spawn()

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    # -- protocol -----------------------------------------------------------

    def _call(self, req: Dict, timeout: float = 30.0) -> Dict:
        c = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        c.settimeout(timeout)
        try:
            c.connect(self.spec["socket"])
            c.sendall((json.dumps(req) + "\n").encode())
            line = c.makefile("r").readline()
            if not line:
                raise ConnectionError("template hung up")
            return json.loads(line)
        finally:
            c.close()

    def fork(self, idx: int) -> Dict:
        """Request fork ``idx``; if the template is dead (or dies on this
        very request — kill-template), respawn once and retry. Counted in
        ``kt_template_forks_total``."""
        from .. import telemetry
        forks = telemetry.cold_start_metrics()["forks"]
        try:
            out = self._call({"cmd": "fork", "idx": idx})
            forks.inc(outcome="ok" if out.get("ok") else "error")
            return out
        except (OSError, ValueError):
            forks.inc(outcome="template_dead")
            self._respawn()
            out = self._call({"cmd": "fork", "idx": idx})
            forks.inc(outcome="ok" if out.get("ok") else "error")
            return out

    def manifest(self) -> Dict:
        return self._call({"cmd": "manifest"})["manifest"]

    def shutdown(self) -> None:
        try:
            self._call({"cmd": "shutdown"}, timeout=10)
        except Exception:  # noqa: BLE001
            pass
        try:
            if self.proc is not None:
                self.proc.wait(timeout=10)
        except Exception:  # noqa: BLE001
            try:
                self.proc.kill()
            except Exception:  # noqa: BLE001
                pass
        if self.segment_name:
            # idempotent: a clean template already unlinked it
            shm_ring.unlink_weight_segment(self.segment_name)

    def __enter__(self) -> "TemplateSupervisor":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()


def main(argv) -> None:
    # before either path imports jax; forked replicas inherit it
    from ..compile_cache import ensure_compile_cache
    ensure_compile_cache()
    if argv and argv[0] == "--cold":
        cold_boot_main(argv[1], int(argv[2]), float(argv[3]))
    else:
        template_main(argv[0])


if __name__ == "__main__":
    main(sys.argv[1:])
