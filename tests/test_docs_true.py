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
