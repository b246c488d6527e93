"""The arrows of `ray_tpu/` point one way: kernels at the bottom, then the
compiled step, then the models; the runtime (`_private`, `util`) beside
them and knowing none of them; data and air over the runtime; train on top.

Pure `ast` over the package's sources, function-level imports included, so
a lazy import cannot hide an edge. One case a box: a box is every module
whose dotted name starts with one of its prefixes, and it may import no
module that starts with one of the names it is denied.
"""
import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
PKG = "ray_tpu"


def _module_name(path: pathlib.Path) -> str:
    parts = path.relative_to(ROOT).with_suffix("").parts
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


def _imports(path: pathlib.Path):
    """(line, absolute dotted name) of every `ray_tpu` import in a file.
    `from a.b import c` yields `a.b.c`: `c` may be a module."""
    module = _module_name(path)
    package = module if path.name == "__init__.py" else \
        module.rpartition(".")[0]
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package.split(".")[:len(package.split(".")) -
                                        node.level + 1]
                base = ".".join(up + ([base] if base else []))
            names = [f"{base}.{a.name}" for a in node.names]
        else:
            continue
        for name in names:
            if name == PKG or name.startswith(PKG + "."):
                yield node.lineno, name


def _under(name: str, prefix: str) -> bool:
    return name == prefix or name.startswith(prefix + ".")


def _edges(box, denied=(), allowed=None):
    """Imports out of `box` into `denied`, or, with `allowed`, into
    anything of the package that is neither the box nor allowed."""
    box = [f"{PKG}.{b}" for b in box]
    denied = [f"{PKG}.{d}" for d in denied]
    found = []
    for path in sorted((ROOT / PKG).rglob("*.py")):
        module = _module_name(path)
        if not any(_under(module, b) for b in box):
            continue
        for line, name in _imports(path):
            if allowed is not None:
                ok = [f"{PKG}.{a}" for a in allowed] + box
                bad = not any(_under(name, a) for a in ok)
            else:
                bad = any(_under(name, d) for d in denied)
            if bad:
                found.append(f"{path.relative_to(ROOT)}:{line} imports "
                             f"{name}")
    return found


BOXES = {
    # the kernels import nothing of the package outside `ops`
    "ops": dict(box=["ops"], allowed=[]),
    "parallel": dict(box=["parallel"],
                     denied=["models", "data", "air", "train", "serve",
                             "util.collective"]),
    "models": dict(box=["models"], allowed=["ops", "parallel"]),
    "runtime": dict(box=["_private", "util"],
                    denied=["ops", "parallel", "models", "data", "air",
                            "train"]),
    "data": dict(box=["data"],
                 denied=["parallel", "models", "air", "train"]),
    "air": dict(box=["air"], denied=["parallel", "models", "train"]),
}


@pytest.mark.parametrize("box", sorted(BOXES))
def test_box_imports_nothing_above_it(box):
    assert _edges(**BOXES[box]) == []


LAYERS = f"{PKG}.models.layers"
# `models/layers/`, lowest first: a module, and what of the package it may
# import. `core` <- `attention`, `mixers`, `mlp` <- `moe` <- `ends`; the
# three in the middle know nothing of each other, `moe` takes the shared
# expert from `mlp`, `ends` the share's config from `moe`.
INSIDE_LAYERS = {
    "core": (),
    "attention": ("core",),
    "mixers": ("core",),
    "mlp": ("core",),
    "moe": ("core", "mlp"),
    "ends": ("core", "moe"),
}


def _layer_modules():
    return {p.stem for p in (ROOT / PKG / "models" / "layers").glob("*.py")
            } - {"__init__"}


def _model_edges():
    """Imports of a model file that reach another model, or a module of
    `models.layers` past the package."""
    seams = _layer_modules()
    found = []
    for path in sorted((ROOT / PKG / "models").glob("*.py")):
        for line, name in _imports(path):
            if not _under(name, f"{PKG}.models") or \
                    _under(name, _module_name(path)):
                continue
            if not _under(name, LAYERS) or \
                    name[len(LAYERS) + 1:].split(".")[0] in seams:
                found.append(f"{path.relative_to(ROOT)}:{line} imports "
                             f"{name}")
    return found


def _inside_layers(module: str):
    """What `models/layers/<module>.py` imports of its package that
    `INSIDE_LAYERS` does not give it, and every import of the package that
    is not at the file's top level."""
    path = ROOT / PKG / "models" / "layers" / f"{module}.py"
    top = {node.lineno for node in ast.parse(path.read_text()).body}
    found = []
    for line, name in _imports(path):
        if not _under(name, LAYERS):
            continue
        seam = name[len(LAYERS) + 1:].split(".")[0]
        if seam not in INSIDE_LAYERS[module]:
            found.append(f"{module}.py:{line} imports {name}")
        elif line not in top:
            found.append(f"{module}.py:{line} imports {name} in a function")
    return found


def test_a_model_imports_no_other_model():
    """What two models share lives in `models.layers`, and a model file
    imports the package, not a module of it."""
    assert _model_edges() == []


@pytest.mark.parametrize("module", list(INSIDE_LAYERS))
def test_inside_models_layers_the_imports_point_one_way(module):
    assert _layer_modules() == set(INSIDE_LAYERS)
    assert _inside_layers(module) == []


def test_one_function_asks_where_a_call_runs():
    """Kernel or plain form hangs on `ops.target.where` alone: nothing else
    under `ops/` or `models/` asks JAX for its backend or a device for its
    platform (`parallel/` asks for other reasons)."""
    found = []
    for box in ("ops", "models"):
        for path in sorted((ROOT / PKG / box).rglob("*.py")):
            for node in ast.walk(ast.parse(path.read_text(), str(path))):
                if isinstance(node, ast.Attribute) and node.attr in (
                        "default_backend", "platform"):
                    found.append(f"{path.relative_to(ROOT)}:{node.lineno}")
    assert {f.rpartition(":")[0] for f in found} == {
        f"{PKG}/ops/target.py"}, found


def test_the_walk_sees_function_level_and_relative_imports(tmp_path):
    """The check's own guard: an edge inside a function, and one spelled
    relatively, are both found; so are, in `models/layers/`, an edge that
    points up, one sideways and one made inside a function, and a model
    file that imports a module of the package."""
    pkg = tmp_path / PKG / "ops"
    pkg.mkdir(parents=True)
    src = pkg / "k.py"
    src.write_text("def f():\n    from ray_tpu.train import ddp\n"
                   "from ..models import gpt2\n")
    layers = tmp_path / PKG / "models" / "layers"
    layers.mkdir(parents=True)
    for module in INSIDE_LAYERS:
        (layers / f"{module}.py").write_text("")
    (layers / "__init__.py").write_text("from ray_tpu.models.layers.moe "
                                        "import apply_moe\n")
    (layers / "core.py").write_text("from ray_tpu.models.layers import moe\n")
    (layers / "mixers.py").write_text("from .attention import rope\n")
    (layers / "moe.py").write_text(
        "from ray_tpu.models.layers import core, mlp\n"
        "def f():\n    from ray_tpu.models.layers.core import rope\n")
    (layers.parent / "a.py").write_text(
        "from ray_tpu.models import layers as L\n"
        "from ray_tpu.models.layers import rope\n")
    (layers.parent / "b.py").write_text(
        "from ray_tpu.models.layers import moe\n"
        "from ray_tpu.models import a\n")
    global ROOT
    real, ROOT = ROOT, tmp_path
    try:
        assert sorted(n for _, n in _imports(src)) == [
            "ray_tpu.models.gpt2", "ray_tpu.train.ddp"]
        assert len(_edges(box=["ops"], allowed=[])) == 2
        assert _inside_layers("core") == [
            "core.py:1 imports ray_tpu.models.layers.moe"]
        assert _inside_layers("mixers") == [
            "mixers.py:1 imports ray_tpu.models.layers.attention.rope"]
        assert _inside_layers("moe") == [
            "moe.py:3 imports ray_tpu.models.layers.core.rope in a function"]
        assert _inside_layers("ends") == []
        assert _model_edges() == [
            "ray_tpu/models/b.py:1 imports ray_tpu.models.layers.moe",
            "ray_tpu/models/b.py:2 imports ray_tpu.models.a"]
    finally:
        ROOT = real
