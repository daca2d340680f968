"""No test module defines a top-level name twice.

A second `def test_x` silently replaces the first, so the first never
runs.  The modules are read with `ast`, never imported.
"""
import ast
import collections
import pathlib

ROOT = pathlib.Path(__file__).resolve().parent.parent
TEST_TREES = ("consensus_specs_tpu/spec_tests", "tests")


def _repeated_names(path: pathlib.Path) -> list[str]:
    tree = ast.parse(path.read_text(), filename=str(path))
    counts = collections.Counter(
        node.name for node in tree.body
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)))
    return sorted(name for name, n in counts.items() if n > 1)


def test_no_repeated_test_names():
    repeated = {}
    for tree in TEST_TREES:
        for path in sorted((ROOT / tree).rglob("*.py")):
            names = _repeated_names(path)
            if names:
                repeated[str(path.relative_to(ROOT))] = names
    assert repeated == {}
