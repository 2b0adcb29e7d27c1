"""Image-setup cache: replay pseudo-dockerfile instructions inside a live pod.

Reference (``serving/http_server.py:510-831``): the new dockerfile is diffed
line-by-line against the last-applied one and only instructions from the
first mismatch onward are replayed — RUN via shell (with
``$KT_PIP_INSTALL_CMD`` substitution), ENV into the process env, COPY a
no-op (ktsync already placed files), CMD (re)starts the app process. A
pip-freeze diff evicts changed modules from ``sys.modules`` so new package
versions are importable without a pod restart.
"""

from __future__ import annotations

import asyncio
import os
import shlex
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

_CACHED_DOCKERFILE: List[str] = []
_PIP_INSTALL_CMD = os.environ.get("KT_PIP_INSTALL_CMD", f"{sys.executable} -m pip install")


def _parse(dockerfile: str) -> List[Tuple[str, str]]:
    out = []
    for line in dockerfile.splitlines():
        line = line.strip()
        if not line or line.startswith("#") or line.upper().startswith("FROM "):
            continue
        kind, _, value = line.partition(" ")
        out.append((kind.upper(), value.strip()))
    return out


def first_mismatch(old: List[Tuple[str, str]], new: List[Tuple[str, str]]) -> int:
    for i, (a, b) in enumerate(zip(old, new)):
        if a != b:
            return i
    return min(len(old), len(new))


async def run_image_setup(dockerfile: str, state=None) -> Dict:
    """Apply only the changed suffix of the dockerfile. Returns stats."""
    global _CACHED_DOCKERFILE

    new = _parse(dockerfile)
    old = _parse("\n".join(_CACHED_DOCKERFILE))
    start = first_mismatch(old, new)
    replayed = 0
    pip_touched = any("pip install" in v.replace("$KT_PIP_INSTALL_CMD",
                                                 _PIP_INSTALL_CMD)
                      for k, v in new[start:] if k == "RUN")
    before = _installed_versions() if pip_touched else {}
    effects = 0       # instructions that changed this pod (FROM/COPY don't)
    for kind, value in new[start:]:
        effects += kind in ("RUN", "ENV", "CMD")
        if kind == "RUN":
            cmd = value.replace("$KT_PIP_INSTALL_CMD", _PIP_INSTALL_CMD)
            proc = await asyncio.create_subprocess_shell(
                cmd, stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.STDOUT)
            out, _ = await proc.communicate()
            if proc.returncode != 0:
                raise RuntimeError(
                    f"image setup RUN failed ({proc.returncode}): {cmd}\n"
                    f"{out.decode()[-2000:]}")
        elif kind == "ENV":
            key, _, val = value.partition("=")
            os.environ[key.strip()] = val.strip()
        elif kind == "COPY":
            pass  # ktsync already placed the files (reference: no-op verify)
        elif kind == "SYNC":
            pass  # handled by the code-sync step before setup
        elif kind == "CMD":
            if state is not None:
                await start_app_process(state, value)
        replayed += 1

    if pip_touched:
        _evict_changed_distributions(before)
    _CACHED_DOCKERFILE = dockerfile.splitlines()
    return {"instructions": len(new), "replayed": replayed,
            "effects": effects}


def _installed_versions() -> dict:
    import importlib
    import importlib.metadata as md

    importlib.invalidate_caches()
    out = {}
    for dist in md.distributions():
        try:
            out[dist.metadata["Name"]] = dist.version
        except Exception:
            continue
    return out


def _evict_changed_distributions(before: dict) -> None:
    """Pip-freeze diff (reference :775-815): evict only the modules of
    distributions whose version changed — never the whole of site-packages
    (dropping live jax/aiohttp would break the running server and re-init
    libtpu, which is single-client)."""
    import importlib
    import importlib.metadata as md

    importlib.invalidate_caches()
    after = _installed_versions()
    changed = {name for name, ver in after.items()
               if before.get(name) != ver}
    if not changed:
        return
    evict_roots = set()
    for dist_name in changed:
        try:
            dist = md.distribution(dist_name)
            top = (dist.read_text("top_level.txt") or "").split()
            evict_roots.update(top or [dist_name.replace("-", "_")])
        except Exception:
            evict_roots.add(dist_name.replace("-", "_"))
    evict_roots.discard("kubetorch_tpu")
    for name in list(sys.modules):
        if name.split(".")[0] in evict_roots:
            sys.modules.pop(name, None)


async def start_app_process(state, command: str,
                            wait_start_s: float = 2.0) -> None:
    """(Re)start the App child process (reference CMD handling +
    wait_for_app_start)."""
    if getattr(state, "app_process", None) is not None and \
            state.app_process.returncode is None:
        state.app_process.terminate()
        try:
            await asyncio.wait_for(state.app_process.wait(), 10)
        except asyncio.TimeoutError:
            state.app_process.kill()
    state.app_process = await asyncio.create_subprocess_exec(
        *shlex.split(command))
    await asyncio.sleep(wait_start_s)
    if state.app_process.returncode is not None:
        raise RuntimeError(
            f"App process exited immediately (rc={state.app_process.returncode}): "
            f"{command}")
