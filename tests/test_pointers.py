"""Pointer extraction + import fallback (reference resources/callables/utils.py)."""

import os
import sys
import textwrap

import pytest

from kubetorch_tpu.resources import pointers as ptr


def test_extract_from_installed_module():
    import tests.assets.payloads as payloads
    p = ptr.extract_pointers(payloads.summer)
    assert p.cls_or_fn_name == "summer"
    assert p.module_name.endswith("payloads")
    assert p.file_path.endswith("payloads.py")


def test_locate_working_dir(tmp_project):
    sub = tmp_project / "pkg" / "sub"
    sub.mkdir(parents=True)
    f = sub / "mod.py"
    f.write_text("x = 1\n")
    assert ptr.locate_working_dir(str(f)) == str(tmp_project)


def test_extract_under_a_directory_that_is_no_package_name(tmp_project):
    """A checkout unpacked under ".scratch/clean-copy" inside a marked
    project: the path to the file cannot be spelled as an import path, so
    the file's own directory is what ships (seen with chip_smoke.py run
    from a git-ignored copy: ``import_module(".chipcheck.clean.…")``)."""
    import importlib.util

    sub = tmp_project / ".scratch" / "clean-copy"
    sub.mkdir(parents=True)
    (sub / "oddmod.py").write_text("def f():\n    return 7\n")
    spec = importlib.util.spec_from_file_location("oddmod",
                                                  str(sub / "oddmod.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    p = ptr.extract_pointers(mod.f)
    assert (p.project_root, p.module_name, p.file_path) == (
        str(sub), "oddmod", "oddmod.py")
    assert ptr.import_callable(p)() == 7
    sys.modules.pop("oddmod", None)


def test_import_callable_roundtrip(tmp_project):
    (tmp_project / "workmod.py").write_text(textwrap.dedent("""
        def double(x):
            return x * 2
    """))
    p = ptr.Pointers(project_root=str(tmp_project), module_name="workmod",
                     file_path="workmod.py", cls_or_fn_name="double")
    fn = ptr.import_callable(p)
    assert fn(21) == 42
    sys.modules.pop("workmod", None)


def test_import_callable_missing_attr(tmp_project):
    (tmp_project / "emptymod.py").write_text("pass\n")
    p = ptr.Pointers(project_root=str(tmp_project), module_name="emptymod",
                     file_path="emptymod.py", cls_or_fn_name="nope")
    with pytest.raises(ImportError):
        ptr.import_callable(p)
    sys.modules.pop("emptymod", None)


def test_reject_non_callable():
    with pytest.raises(TypeError):
        ptr.extract_pointers(42)


def test_build_call_body():
    body = ptr.build_call_body((1, 2), {"k": "v"})
    assert body == {"args": [1, 2], "kwargs": {"k": "v"}}
    body = ptr.build_call_body((), {}, debugger={"mode": "pdb", "port": 5678})
    assert body["debugger"]["port"] == 5678


def test_self_deploy_from_pod_refused(monkeypatch):
    """An unguarded driver script imported by its own pod worker must fail
    fast instead of re-deploying itself and deadlocking on its own warmup."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "assets"))
    import payloads

    import kubetorch_tpu as kt

    f = kt.fn(payloads.echo_env)
    monkeypatch.setenv("POD_NAME", "kt-payload-0")
    monkeypatch.setenv("KT_SERVICE_NAME", f.name)
    with pytest.raises(RuntimeError, match="from inside pod"):
        f.to(kt.Compute(cpus=1))

    # username mismatch (k8s images default to 'kt') must NOT fail open:
    # the pod's module pointers still identify the self-deploy
    monkeypatch.setenv("KT_SERVICE_NAME", "alice-" + f.name)
    monkeypatch.setenv("KT_CLS_OR_FN_NAME", f.pointers.cls_or_fn_name)
    monkeypatch.setenv("KT_MODULE_NAME", f.pointers.module_name)
    with pytest.raises(RuntimeError, match="from inside pod"):
        f.to(kt.Compute(cpus=1))
