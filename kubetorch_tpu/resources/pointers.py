"""Callable pointer extraction — how a local function becomes addressable.

Reference ``resources/callables/utils.py``: ``extract_pointers`` (:53) derives
``(root_path, module_import_path, callable_name)`` from a live object via
``inspect``; ``locate_working_dir`` (:114) walks up from the defining file to
a project marker (``.git``, ``pyproject.toml``...) so the sync layer knows
which directory tree to ship; ``build_call_body`` (:255) shapes the RPC body.
"""

from __future__ import annotations

import inspect
import os
import sys
from dataclasses import dataclass, asdict
from pathlib import Path
from typing import Any, Dict, Optional

WORKING_DIR_MARKERS = (".git", "pyproject.toml", "setup.py", "setup.cfg", "requirements.txt")


@dataclass
class Pointers:
    """Where a callable lives, expressed relative to a shippable root."""

    project_root: str      # absolute local path of the dir that gets synced
    module_name: str       # dotted import path relative to project_root
    file_path: str         # file path relative to project_root
    cls_or_fn_name: str

    def to_dict(self) -> Dict[str, str]:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, str]) -> "Pointers":
        return cls(**{k: d[k] for k in ("project_root", "module_name", "file_path", "cls_or_fn_name")})


def locate_working_dir(start: str) -> str:
    """Walk up from ``start`` to the nearest project marker (reference :114)."""
    path = Path(start).resolve()
    if path.is_file():
        path = path.parent
    for candidate in (path, *path.parents):
        for marker in WORKING_DIR_MARKERS:
            if (candidate / marker).exists():
                return str(candidate)
    return str(path)


def extract_pointers(obj: Any) -> Pointers:
    """Derive shippable pointers for a function or class (reference :53).

    Interactive callables (REPL / notebook cells) have no importable file; the
    reference extracts notebook functions to a file (:23). Here we serialize
    their source to ``__kt_interactive__.py`` under cwd at deploy time — see
    :func:`dump_interactive_source`.
    """
    if not (inspect.isfunction(obj) or inspect.isclass(obj)):
        raise TypeError(f"Expected a function or class, got {type(obj).__name__}")

    qualname = obj.__qualname__
    if "." in qualname:
        raise ValueError(
            f"{qualname!r} is a nested class/function — only module-top-level "
            "callables can be addressed remotely (the pod imports them by name)")
    name = obj.__name__
    try:
        src_file = inspect.getfile(obj)
    except TypeError:
        raise ValueError(f"Cannot locate source file for {name!r} (builtin?)")

    if src_file.startswith("<"):  # REPL / exec'd source
        return _interactive_pointers(obj, name)

    src_file = os.path.abspath(src_file)
    root = locate_working_dir(src_file)
    rel = os.path.relpath(src_file, root)
    if rel.startswith("..") or not all(
            part.isidentifier() for part in Path(rel).parent.parts):
        # a directory between the marker and the file that is no package
        # name (".cache/x", "my-checkout") cannot be spelled as an import
        # path: ship the file's own directory instead
        root = str(Path(src_file).parent)
        rel = os.path.basename(src_file)
    module_name = rel[:-3].replace(os.sep, ".") if rel.endswith(".py") else Path(rel).stem
    if module_name.endswith(".__init__"):
        module_name = module_name[: -len(".__init__")]
    return Pointers(project_root=root, module_name=module_name, file_path=rel, cls_or_fn_name=name)


_INTERACTIVE_FILE = "__kt_interactive__.py"
_SECTION_BEGIN = "# __kt_section__: "


def _interactive_pointers(obj: Any, name: str) -> Pointers:
    """Persist an interactive callable's source into a named section of the
    sync'd interactive module, *replacing* any previous version of the same
    name so reverts deploy what the user currently has."""
    try:
        source = inspect.getsource(obj)
    except OSError:
        raise ValueError(
            f"{name!r} is defined interactively and its source cannot be recovered; "
            "define it in a .py file."
        )
    root = os.getcwd()
    path = Path(root) / _INTERACTIVE_FILE
    sections: Dict[str, str] = {}
    if path.exists():
        current = None
        for line in path.read_text().splitlines(keepends=True):
            if line.startswith(_SECTION_BEGIN):
                current = line[len(_SECTION_BEGIN):].strip()
                sections[current] = ""
            elif current is not None:
                sections[current] += line
    sections[name] = source
    with open(path, "w") as f:
        for sec_name, sec_src in sections.items():
            f.write(f"{_SECTION_BEGIN}{sec_name}\n{sec_src.rstrip()}\n\n")
    return Pointers(project_root=root, module_name=_INTERACTIVE_FILE[:-3],
                    file_path=_INTERACTIVE_FILE, cls_or_fn_name=name)


def build_call_body(args: tuple, kwargs: dict, debugger: Optional[dict] = None) -> Dict[str, Any]:
    """RPC body shape (reference :255): args/kwargs plus optional debugger spec."""
    body: Dict[str, Any] = {"args": list(args), "kwargs": kwargs}
    if debugger:
        body["debugger"] = debugger
    return body


def patch_sys_path(root: str) -> None:
    """Ensure the synced project root is importable (reference http_server.py:1005)."""
    if root not in sys.path:
        sys.path.insert(0, root)


def import_callable(pointers: Pointers, reload: bool = False) -> Any:
    """Import ``cls_or_fn_name`` from its module, with file-path fallback.

    Mirrors ``load_callable_from_env`` (reference http_server.py:1039-1106):
    try a normal import of ``module_name``; if the module isn't importable
    (e.g. not a package member), exec the file directly.
    """
    import importlib
    import importlib.util

    patch_sys_path(pointers.project_root)
    mod = None
    try:
        mod = importlib.import_module(pointers.module_name)
        if reload:
            mod = importlib.reload(mod)
    except ImportError:
        file_path = os.path.join(pointers.project_root, pointers.file_path)
        spec = importlib.util.spec_from_file_location(pointers.module_name, file_path)
        if spec is None or spec.loader is None:
            raise ImportError(f"Cannot import {pointers.module_name} from {file_path}")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[pointers.module_name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            # Mirror importlib's own cleanup: never cache a half-built module,
            # or retries would mask the real error with an AttributeError.
            sys.modules.pop(pointers.module_name, None)
            raise
    try:
        return getattr(mod, pointers.cls_or_fn_name)
    except AttributeError:
        raise ImportError(
            f"Module {pointers.module_name!r} has no attribute {pointers.cls_or_fn_name!r}"
        )
