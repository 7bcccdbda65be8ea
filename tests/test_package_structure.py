"""The arrows under ``horovod_tpu/ops`` and ``horovod_tpu/models`` point one
way, ``models/`` -> ``ops/<family>.py`` -> ``ops/_pallas.py``, read from
the source (an ``ast`` walk; nothing is imported)."""

import ast
import pathlib

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "horovod_tpu"
SHARED = PACKAGE / "ops" / "_pallas.py"


def _modules():
    for directory in ("ops", "models"):
        for path in sorted((PACKAGE / directory).glob("*.py")):
            yield path, ast.parse(path.read_text())


def test_one_module_asks_for_the_backend_and_no_private_name_crosses():
    """``jax.default_backend`` is read in ``ops/_pallas.py`` alone (a test
    that compiles for a described chip patches ``_pallas.interpret`` and
    nothing else), and no module imports an underscore name from another
    module of the package (``from horovod_tpu.ops import _pallas``, the
    module itself, is how the families reach what they share)."""
    asks, crosses = [], []
    for path, tree in _modules():
        for node in ast.walk(tree):
            if (isinstance(node, ast.Attribute)
                    and node.attr == "default_backend"):
                asks.append(f"{path.name}:{node.lineno}")
            if (isinstance(node, ast.ImportFrom) and node.module
                    and node.module.startswith("horovod_tpu.")):
                source = PACKAGE.parent.joinpath(
                    *node.module.split(".")).with_suffix(".py")
                crosses += [f"{path.name}:{node.lineno}: {alias.name}"
                            for alias in node.names
                            if alias.name.startswith("_")
                            and source.is_file() and source != path]
    assert asks and all(a.startswith(SHARED.name) for a in asks), asks
    assert not crosses, crosses
