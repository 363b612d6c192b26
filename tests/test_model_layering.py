"""The layering of ``brpc_tpu/models/``: a model module (one that defines
``init_params``) builds from the shared modules beside it (``layers``,
``train_step``, ``experts``, ``chunked_loss``) and from ``ops/``, never from
another model; and the choice between a kernel and its plain form lives in
``ops/`` beside the kernels, not in a model that only calls them."""

import ast
import os

import pytest

MODELS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "brpc_tpu", "models")
PACKAGE = "brpc_tpu.models"


def _tree(name: str) -> ast.Module:
    with open(os.path.join(MODELS_DIR, name + ".py")) as f:
        return ast.parse(f.read())


def _model_modules() -> list:
    return sorted(
        name[:-3] for name in os.listdir(MODELS_DIR)
        if name.endswith(".py") and any(
            isinstance(node, ast.FunctionDef) and node.name == "init_params"
            for node in _tree(name[:-3]).body))


MODELS = _model_modules()


def _imported(tree: ast.Module) -> set:
    """The modules of ``brpc_tpu.models`` that a module imports, by name,
    at any depth of it."""
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found |= {alias.name[len(PACKAGE) + 1:].split(".")[0]
                      for alias in node.names
                      if alias.name.startswith(PACKAGE + ".")}
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level == 1:             # relative: beside it in models/
                module = PACKAGE + ("." + module if module else "")
            if module == PACKAGE:
                found |= {alias.name for alias in node.names}
            elif module.startswith(PACKAGE + "."):
                found.add(module[len(PACKAGE) + 1:].split(".")[0])
    return found


def test_the_five_models_are_found():
    assert {"llama", "deepseek", "hybrid", "looped", "windowed"} <= set(
        MODELS)


@pytest.mark.parametrize("model", MODELS)
def test_a_model_imports_no_other_model(model):
    others = _imported(_tree(model)) & (set(MODELS) - {model})
    assert not others, f"{model} imports {sorted(others)}"


@pytest.mark.parametrize("model", ["llama", "deepseek", "hybrid", "looped"])
def test_a_model_that_only_calls_kernels_chooses_none(model):
    """``ops/`` chooses between its kernels and their plain forms
    (``flash_attention.attention``, ``lowered.choose``): these models
    name no ``lax.platform_dependent`` of their own."""
    chooses = [node.lineno for node in ast.walk(_tree(model))
               if isinstance(node, ast.Attribute)
               and node.attr == "platform_dependent"]
    assert not chooses, f"{model}.py lines {chooses}"
