"""The seams between the package, the benchmark and the documents, read
from the sources alone (no jax): the package imports nothing from the
repo's root, every name the benchmark imports from the package is
there, every registered metric is emitted by code the package itself
calls, and the documents tell a reader to run only scripts that exist.
"""

import ast
import importlib
import re
from pathlib import Path

import pytest

from deeplearning4j_tpu.analysis.source import (
    SourceFile,
    call_name,
    const_str,
    load_sources,
)

ROOT = Path(__file__).resolve().parents[1]
PKG = ROOT / "deeplearning4j_tpu"

# what stands at the root, above the package
ROOT_MODULES = re.compile(
    r"^(bench\w*|__graft_entry__|chip_smoke|benchmark|tools)(\.|$)")


@pytest.fixture(scope="module")
def sources():
    """Every file of the package, parsed once."""
    return load_sources(PKG, ROOT)


def _imports(tree: ast.AST):
    """(module, name or None, line) of every import statement."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, None, node.lineno
        elif isinstance(node, ast.ImportFrom) and not node.level:
            for a in node.names:
                yield node.module, a.name, node.lineno


def _names(tree: ast.AST):
    """Every identifier and attribute name the code mentions."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute):
            yield node.attr, node
        elif isinstance(node, ast.Name):
            yield node.id, node


def test_package_imports_nothing_from_the_root(sources):
    """A lower layer does not know the scripts above it: no module of
    the package imports a root script, the benchmark or `tools`, loads
    a file by path, or puts a directory on `sys.path`."""
    bad = []
    for sf in sources:
        for module, _, line in _imports(sf.tree):
            if ROOT_MODULES.match(module):
                bad.append(f"{sf.rel}:{line} imports {module}")
        for name, node in _names(sf.tree):
            if name == "spec_from_file_location":
                bad.append(f"{sf.rel}:{node.lineno} loads a file by path")
            elif (name == "path" and isinstance(node, ast.Attribute)
                    and getattr(node.value, "id", None) == "sys"):
                bad.append(f"{sf.rel}:{node.lineno} touches sys.path")
    assert not bad, "\n".join(bad)


def test_program_records_name_sources_inside_the_package(sources):
    """Every `source=` a ProgramRecord is built with in the package is
    a file of the package: the lint set holds no program whose code
    lives above it."""
    bad = []
    for sf in sources:
        consts = {t.id: const_str(n.value) for n in ast.walk(sf.tree)
                  if isinstance(n, ast.Assign)
                  for t in n.targets if isinstance(t, ast.Name)}
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and call_name(node) == "ProgramRecord"):
                continue
            for kw in node.keywords:
                if kw.arg != "source":
                    continue
                src = const_str(kw.value) or consts.get(
                    getattr(kw.value, "id", None))
                if not (src and src.startswith("deeplearning4j_tpu/")
                        and (ROOT / src).is_file()):
                    bad.append(f"{sf.rel}:{node.lineno} source={src!r}")
    assert not bad, "\n".join(bad)


def _from_package(sf: SourceFile):
    return [(m, name, line) for m, name, line in _imports(sf.tree)
            if m.split(".")[0] == "deeplearning4j_tpu"]


def _benchmark_files():
    """The benchmark's files that import from the package."""
    return [sf.rel for sf in load_sources(ROOT / "benchmark", ROOT)
            if _from_package(sf)]


@pytest.mark.parametrize("rel", _benchmark_files())
def test_benchmark_imports_resolve(rel):
    """Every name a benchmark file imports from the package is there.
    The benchmark imports inside functions, so a name the package lost
    would otherwise first be missed on the chip."""
    missing = []
    for module, name, line in _from_package(
            SourceFile.parse(ROOT / rel, ROOT)):
        mod = importlib.import_module(module)
        if name is not None and name != "*" and not hasattr(mod, name):
            try:        # `from package import submodule`
                importlib.import_module(f"{module}.{name}")
            except ImportError:
                missing.append(f"{rel}:{line} {module}.{name}")
    assert not missing, "\n".join(missing)


EMITTERS = {"count", "observe", "set_gauge", "gauge_fn", "count_observe",
            "inc", "observe_keyed"}


def test_every_registered_metric_is_emitted_by_code_the_package_calls(
        sources):
    """A registered name needs more than a call site: the function that
    holds the site has to be one the package itself calls (by name,
    anywhere in the package). A gauge set only by a method that nothing
    but a test or a script calls is a gauge no running process sets."""
    from deeplearning4j_tpu.observability.metrics import (
        DERIVED_METRICS,
        REGISTERED_METRICS,
    )

    called = {name for sf in sources for name, _ in _names(sf.tree)}
    live = set()
    for sf in sources:
        if sf.rel.endswith("observability/metrics.py"):
            continue
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and call_name(node) in EMITTERS):
                continue
            owner = sf.qualname_of(node).rpartition(".")[2]
            if not owner or owner.startswith("__") or owner in called:
                live.update(filter(None, map(const_str, node.args[:2])))
    unset = set(REGISTERED_METRICS) - set(DERIVED_METRICS) - live
    assert not unset, sorted(unset)


COMMAND = re.compile(r"\bpython3? +([\w./-]+\.py)\b")


@pytest.mark.parametrize("doc", ["README.md",
                                 ".claude/skills/verify/SKILL.md"])
def test_documents_name_scripts_that_exist(doc):
    """Every `python <script>.py` a document tells its reader to run
    names a file of the repository."""
    text = (ROOT / doc).read_text()
    gone = sorted({m for m in COMMAND.findall(text)
                   if not (ROOT / m).is_file()})
    assert not gone, f"{doc} runs {gone}"
