"""The two seams every new architecture lands on (ROADMAP D18 a, D6):
no kernel file imports another kernel file, no decoder another decoder.
What several share lives in ``ops/pallas_common.py`` and
``models/decoder.py``; a file that needs a sibling's helper moves it
there. Read from the AST: nothing is imported, no JAX is touched."""
import ast
import glob
import os

import pytest

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu")
# ``__init__.py`` re-exports flash_attention's two public functions
KERNEL_FILES = sorted(
    os.path.basename(p) for p in glob.glob(os.path.join(PACKAGE, "ops", "*.py"))
    if os.path.basename(p) != "__init__.py")
DECODER_FILES = ["zaya.py", "afmoe.py", "qwen3_next.py", "minicpm_sala.py",
                 "granite_hybrid.py"]


def _imports(directory: str, file: str):
    """-> [(module, name)] of every import of ``paddle_tpu/<directory>/
    <file>``, at any depth of the file, with the module absolute."""
    here = ["paddle_tpu", directory]
    with open(os.path.join(PACKAGE, directory, file)) as f:
        tree = ast.parse(f.read())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found += [(alias.name, "") for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - node.level + 1] if node.level else []
            module = ".".join(base + ([node.module] if node.module else []))
            found += [(module, alias.name) for alias in node.names]
    return found


def _siblings(directory: str, file: str):
    """The files of its own directory that ``file`` imports (``from .x
    import y``, ``from . import x``, or either spelled from the root)."""
    package = f"paddle_tpu.{directory}"
    out = set()
    for module, name in _imports(directory, file):
        if module == package and name:
            out.add(name)
        elif module.startswith(package + "."):
            out.add(module[len(package) + 1:].split(".")[0])
    return out


def test_the_seams_are_there():
    assert "pallas_common.py" in KERNEL_FILES and len(KERNEL_FILES) >= 12
    assert os.path.exists(os.path.join(PACKAGE, "models", "decoder.py"))


@pytest.mark.parametrize("file", KERNEL_FILES)
def test_a_kernel_file_imports_no_other_kernel_file(file):
    assert _siblings("ops", file) <= {"pallas_common"}, (
        f"ops/{file}: what two kernel files share lives in "
        "ops/pallas_common.py")


@pytest.mark.parametrize("file", DECODER_FILES)
def test_a_decoder_imports_no_other_decoder(file):
    assert _siblings("models", file) <= {"decoder"}, (
        f"models/{file}: what two decoders share lives in models/decoder.py")
    private = [(module, name) for module, name in _imports("models", file)
               if module.startswith("paddle_tpu") and name.startswith("_")]
    assert not private, f"models/{file} imports private names: {private}"
