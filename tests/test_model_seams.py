"""The seams of `kafka_tpu/models/` (ISSUE 58), as static facts: which module
may import which (the AST walk of
`tests/test_step_programs.py::test_step_programs_does_not_import_the_engine`),
that `forward`'s layer body picks a mixer from the table and not by a
comparison on the kind, and that every kind of every registered configuration
has a row there.

Bottom to top: `config` <- `quant` <- `cache` <- `mixers/*`, `ffn`,
`residual` <- `hybrid`, `init_params` <- `llama`.  Nothing imports upward, and
nothing under `kafka_tpu/` imports `models.llama` inside a function body to
dodge a cycle: there is none left to dodge.
"""

import ast
import glob
import inspect
import os
import pathlib
import re
import textwrap

import pytest

import kafka_tpu
from kafka_tpu.models import llama
from kafka_tpu.models.config import (
    CONFIGS, CROSS, MAMBA, config_from_hf_json,
)
from kafka_tpu.models.mixers import MIXERS
from kafka_tpu.tracing import DEVICE_SCOPES

ROOT = pathlib.Path(kafka_tpu.__file__).parent
MODELS = ROOT / "models"
REPO = ROOT.parent
CONFIG_FILES = sorted(
    glob.glob(str(REPO / "benchmarks" / "configs" / "*.json"))
    + glob.glob(str(REPO / "benchmarks" / "tests" / "*" / "configs"
                    / "*.json")))


def _imports(path, bodies_only=False):
    """Every module a file imports as a dotted name, relative ones resolved
    against the file's package (`bodies_only`: the imports inside function
    bodies alone)."""
    package = ["kafka_tpu", *path.relative_to(ROOT).parts[:-1]]
    tree = ast.parse(path.read_text())
    roots = ([n for n in ast.walk(tree)
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
             if bodies_only else [tree])
    for root in roots:
        for node in ast.walk(root):
            if isinstance(node, ast.Import):
                yield from (a.name for a in node.names)
            elif isinstance(node, ast.ImportFrom):
                base = (package[:len(package) - node.level + 1]
                        if node.level else [])
                module = ".".join(base + ([node.module] if node.module
                                          else []))
                yield from (f"{module}.{a.name}" for a in node.names)


def _siblings(path):
    """The modules of `kafka_tpu.models` a file imports, by their first
    name under the package (`cache`, `mixers`, `llama`, ...)."""
    return {m.group(1) for name in _imports(path)
            if (m := re.match(r"kafka_tpu\.models\.(\w+)", name))}


LOWER = sorted([*(MODELS / "mixers").glob("*.py"), MODELS / "ffn.py",
                MODELS / "residual.py", MODELS / "cache.py"])


def test_the_cache_layer_imports_no_sibling_but_quant_and_config():
    assert _siblings(MODELS / "cache.py") <= {"quant", "config"}
    assert _siblings(MODELS / "quant.py") <= {"config"}
    # (the vision tower's own config: a leaf that imports no sibling)
    assert _siblings(MODELS / "config.py") == {"vision"}
    assert _siblings(MODELS / "vision.py") == set()


@pytest.mark.parametrize("path", LOWER, ids=lambda p: p.stem)
def test_no_mixer_imports_a_forward_pass(path):
    """Mixers, the feed-forward half and the residual stream import the
    cache layer, `ops/` and the config: neither forward pass, no
    initialiser."""
    assert not _siblings(path) & {"llama", "hybrid", "init_params"}, path


def test_hybrid_imports_nothing_of_llama_and_only_llama_runs_it():
    assert "llama" not in _siblings(MODELS / "hybrid.py")
    users = {p.name for p in ROOT.rglob("*.py")
             if p.name != "hybrid.py" and any(
                 re.match(r"kafka_tpu\.models\.hybrid(\.|$)", name)
                 for name in _imports(p))}
    # (`init_params` dispatches to its initialiser: ROADMAP D2 (ii))
    assert users == {"llama.py", "init_params.py"}


def test_no_function_body_imports_models_llama():
    """A `from ..models.llama import` inside a function was how a module
    under the model dodged the cycle with it."""
    found = [(str(p.relative_to(ROOT)), name)
             for p in sorted(ROOT.rglob("*.py"))
             for name in _imports(p, bodies_only=True)
             if re.match(r"kafka_tpu\.models\.(llama|hybrid)(\.|$)", name)]
    assert found == []


def test_the_model_package_is_imported_from_the_owners():
    """Under `kafka_tpu/`, what is imported from `models.llama` is what it
    owns: the forward pass, the head, the contiguous cache and (re-imported
    for callers outside the package) the entry points."""
    owned = {"forward", "init_kv_cache", "_logits_head", "KVCache",
             "init_params"}
    for path in sorted(ROOT.rglob("*.py")):
        for name in _imports(path):
            m = re.match(r"kafka_tpu\.models\.llama\.(\w+)$", name)
            assert m is None or m.group(1) in owned, (path.name, name)


def test_llama_holds_the_forward_pass_and_nothing_else():
    source = (MODELS / "llama.py").read_text()
    assert source.count("\n") < 700
    defined = {n.name for n in ast.parse(source).body
               if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
    assert defined == {"forward", "_logits_head", "init_kv_cache"}
    # what benchmarks/ has always imported from here stays importable
    for name in ("KVCache", "PagedView", "forward", "init_params"):
        assert name in llama.__all__ and hasattr(llama, name)


def test_the_layer_body_compares_no_kind():
    """`forward` holds no comparison on `kind` (`==`, `in`, a dict keyed by
    it) and its layer body no branch on the family (`cfg.is_latent`,
    `cfg.by_kind`): the table's row is the only thing a kind selects."""
    tree = ast.parse(textwrap.dedent(inspect.getsource(llama.forward)))
    for node in ast.walk(tree):
        if isinstance(node, ast.Compare):
            sides = [node.left, *node.comparators]
            # (`cfg.kind_of(i) == kind` COUNTS the layers of a kind ahead of
            # one: `before`; it selects no code)
            counts = any(isinstance(s, ast.Call) for s in sides)
            assert counts or not any(
                isinstance(s, ast.Name) and s.id == "kind" for s in sides
            ), ast.unparse(node)
    body, = [n for n in ast.walk(tree)
             if isinstance(n, ast.FunctionDef) and n.name == "layer_body"]
    attrs = {n.attr for n in ast.walk(body) if isinstance(n, ast.Attribute)}
    assert not attrs & {"is_latent", "by_kind", "kind_leaves"}
    assert "mixer_of" in attrs
    assert body.end_lineno - body.lineno < 40


def _named(path):
    return os.path.basename(path)[:-len(".json")]


@pytest.mark.parametrize("path", CONFIG_FILES, ids=_named)
def test_every_kind_of_a_configuration_has_its_row(path):
    cfg = config_from_hf_json(path)
    if cfg.hybrid_decoder:
        # phi4flash's kinds are models/hybrid.forward's own (ROADMAP D2 (ii))
        assert MAMBA in cfg.kinds and CROSS in cfg.kinds
        return
    for kind in cfg.kinds:
        if cfg.mixer_of(kind) is None:
            # (a one-sublayer pattern's feed-forward layer has no mixer)
            assert cfg.lone_layers and cfg.has_ffn(kind)
            continue
        mixer = MIXERS[cfg.mixer_of(kind)]
        assert callable(mixer.mix) and mixer.scope in DEVICE_SCOPES


def test_every_preset_resolves_through_the_table():
    for name, cfg in sorted(CONFIGS.items()):
        for kind in cfg.kinds:
            assert cfg.mixer_of(kind) in MIXERS, (name, kind)
