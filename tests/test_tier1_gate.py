"""The gate itself (tests/conftest.py): the long modules start first, a test
that waits fails by name, and no subprocess under tests/ can wait for ever."""

import ast
import glob
import os

import conftest
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))


def test_long_modules_are_collected_first_and_name_files(request):
    missing = sorted(name for name in conftest._SLOW_MODULES
                     if not os.path.exists(os.path.join(TESTS, name + ".py")))
    assert not missing, f"_SLOW_MODULES names no file: {missing}"
    long = [conftest._in_long_module(item) for item in request.session.items]
    assert False in long, "this very test is outside the set"
    first_other = long.index(False)
    late = sorted({item.module.__name__
                   for item in request.session.items[first_other:]
                   if conftest._in_long_module(item)})
    assert not late, f"collected after a short module: {late}"


def test_a_test_that_waits_fails_by_name(tmp_path, monkeypatch):
    """A run of pytest on a sleeping test under this repo's conftest, with
    the limit cut so that the check costs no 300 s."""
    monkeypatch.setattr(conftest, "_TEST_LIMIT_SEC", 0.05)
    (tmp_path / "test_sleeper.py").write_text(
        "import time\ndef test_sleeps():\n    time.sleep(30)\n")
    reports = []

    class Listener:
        @staticmethod
        def pytest_runtest_logreport(report):
            reports.append(report)

    code = pytest.main(
        [str(tmp_path / "test_sleeper.py"), "-q", "--rootdir", str(tmp_path),
         "-p", "conftest", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly"],
        plugins=[Listener()])
    assert code == pytest.ExitCode.TESTS_FAILED
    call, = [r for r in reports if r.when == "call"]
    assert call.failed and call.duration < 5
    assert "test_sleeper.py::test_sleeps" in call.longreprtext
    assert "limit of 0.05 s" in call.longreprtext


_RUNNERS = {"run", "call", "check_call", "check_output"}


def _calls_without_timeout(path):
    """`subprocess.run` and its kin, and `.wait()` / `.communicate()` in a
    file that imports subprocess, with neither a `timeout=` nor a `**`."""
    with open(path) as f:
        source = f.read()
    if "subprocess" not in source:
        return []
    found = []
    for node in ast.walk(ast.parse(source)):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        func = node.func
        runner = (func.attr in _RUNNERS and isinstance(func.value, ast.Name)
                  and func.value.id == "subprocess")
        waiter = func.attr in ("wait", "communicate") and not node.args
        if (runner or waiter) and not any(
                k.arg in ("timeout", None) for k in node.keywords):
            found.append(f"{os.path.basename(path)}:{node.lineno} "
                         f"{ast.unparse(func)}")
    return found


def test_no_subprocess_call_without_timeout():
    found = [where for path in sorted(glob.glob(os.path.join(TESTS, "*.py")))
             for where in _calls_without_timeout(path)]
    assert not found, f"these can wait for ever: {found}"
