"""The documents a new owner reads name only files that are in the tree.

``README.md`` and ``docs/*.md`` describe the program as it is; a file
or command they name must exist. ``PERF.md``, ``ROADMAP.md`` and
``CHANGES.md`` are histories that may name what was deleted and are not
read here.
"""

import functools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", "docs/AOT.md", "docs/ELASTICITY.md",
        "docs/OBSERVABILITY.md", "docs/PERFORMANCE.md", "docs/SERVING.md",
        "docs/PARITY.md"]

#: a path is written from the root, from the package, from
#: ``workloads/`` (``out/...``) or from the document's own directory
BASES = ["", "hetu_tpu", "workloads", "docs"]

EXTENSIONS = (".py", ".md", ".json", ".jsonl", ".yaml", ".yml", ".cpp",
              ".cc", ".h", ".sh", ".txt", ".ini")

#: what a RUN writes into its own directory (``Trainer`` telemetry
#: export, checkpoint metadata): named in the documents, never in the tree
RUN_OUTPUTS = {"telemetry.jsonl", "trace.json", "meta.json"}


@functools.lru_cache(maxsize=None)
def _tree_basenames():
    names = set()
    for top, dirs, files in os.walk(ROOT):
        # scratch copies (`_parent/`, `_final/`, ...) and caches are not
        # the tree
        dirs[:] = [d for d in dirs
                   if d[0] not in "._" and d != "chiprun_out"]
        names.update(files)
    return names


def _in_tree(path):
    if "/" not in path:
        return path in _tree_basenames()
    first = path.split("/")[0]
    if not any(os.path.isdir(os.path.join(ROOT, base, first))
               for base in BASES):
        return True             # another system's path (the reference's)
    return any(os.path.exists(os.path.join(ROOT, base, path))
               for base in BASES)


def _named_paths(text):
    """Repo paths inside single backticks: ``dir/file.ext`` (with any
    ``::name`` / ``:line`` suffix cut), ``dir/``, and bare ``file.ext``."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = re.split(r"::|:\d|#", word.strip("(),;'\""))[0]
            if not re.fullmatch(r"[\w./\-]+", word) or word in RUN_OUTPUTS:
                continue        # placeholders (<rank>, *, {a,b}), prose
            if word.endswith(EXTENSIONS) or word.endswith("/"):
                yield word


def _named_commands(text):
    """``python <script.py>`` and ``python -m <module>`` anywhere in the
    document, fenced blocks included."""
    for script in re.findall(r"python3?\s+([\w./\-]+\.py)\b", text):
        yield script, script
    for module in re.findall(r"python3?\s+-m\s+((?:hetu_tpu|benchmark|"
                             r"workloads)[\w.]*)", text):
        yield module, module.replace(".", "/") + ".py"


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    missing = {path for path in _named_paths(text) if not _in_tree(path)}
    for shown, path in _named_commands(text):
        if not os.path.exists(os.path.join(ROOT, path)):
            missing.add(shown)
    assert not missing, f"{doc} names files that are not in the tree: " \
                        f"{sorted(missing)}"


# -- the test tree: what two test modules share lives in tests/served.py ------

def _test_tree():
    """Python files under ``tests/`` outside ``tests/benchmark/`` (the
    benchmark's own) -> ``(path from tests/, its syntax tree)``."""
    import ast
    tests = os.path.join(ROOT, "tests")
    for top, dirs, files in os.walk(tests):
        dirs[:] = [d for d in dirs if d != "benchmark" and d[0] not in "._"]
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(top, name)
                with open(path) as f:
                    yield os.path.relpath(path, tests), ast.parse(f.read())


def test_no_test_module_imports_a_test_module():
    """A helper two files need is in ``tests/served.py``: a test module
    imported for its helpers is collected twice over (its module
    fixtures and its parametrised tables built again) and ties one
    architecture's file to another's."""
    import ast
    sideways = []
    for path, tree in _test_tree():
        for node in ast.walk(tree):
            names = [a.name for a in node.names] \
                if isinstance(node, ast.Import) else \
                [node.module or ""] if isinstance(node, ast.ImportFrom) \
                else []
            sideways += [(path, n) for n in names
                         if n.split(".")[-1].startswith("test_")]
    assert not sideways, sideways


def test_the_contract_runs_only_through_its_subclasses():
    """``tests/served.py`` defines no ``test_*`` function and no class
    pytest would take for a test class (``python_classes`` is the
    default, ``Test*``): the contract's methods run where an
    architecture's file subclasses them, and nowhere else."""
    import ast
    (tree,) = [t for p, t in _test_tree() if p == "served.py"]
    tests = [n.name for n in tree.body
             if isinstance(n, (ast.FunctionDef, ast.ClassDef))
             and n.name.lower().startswith("test")]
    assert tests == []
    with open(os.path.join(ROOT, "pytest.ini")) as f:
        ini = f.read()
    assert "python_classes" not in ini and "python_files" not in ini
    contract = [n for n in tree.body if isinstance(n, ast.ClassDef)
                and n.name == "ServedArchContract"]
    assert contract and any(
        isinstance(n, ast.FunctionDef) and n.name.startswith("test_")
        for n in contract[0].body)
