"""Documents name only what exists: every back-quoted source, data or
document path in the documents a new session reads first is a file of the
tree, or the tail of one's path (``backend.py``, ``lib/hostgraph.py``). A file
that is deleted or renamed while a document still sends readers to it fails
here.
"""
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = [
    "README.md", ".claude/skills/verify/SKILL.md", "PERF.md", "OBSERVABILITY.md",
    "COMMANDS.md", "CLUSTER.md", "DURABILITY.md", "EDGE.md", "RESILIENCE.md",
    "PARITY.md",
]
QUOTED = re.compile(r"`([^`\n]+)`")
PATH = re.compile(r"^[\w.-]+(/[\w.-]+)*\.(py|json|md|yml|cpp)$")
# hidden directories hold caches and scratch copies of other trees, not the
# tree's own files; these two are the tree's
HIDDEN_OF_THE_TREE = (".claude", ".github")


@pytest.fixture(scope="module")
def tree_files() -> set:
    files = set()
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [
            d for d in dirs
            if d not in ("__pycache__", "chiprun_out")
            and (not d.startswith(".") or d in HIDDEN_OF_THE_TREE)
        ]
        rel = os.path.relpath(root, REPO)
        for name in names:
            files.add(name if rel == "." else f"{rel}/{name}".replace(os.sep, "/"))
    return files


def quoted_paths(text: str) -> set:
    """The repo-relative paths among a document's back-quoted words, with any
    ``::name`` or ``:line`` suffix cut. Absolute paths, globs and placeholders
    (``/tmp/x.json``, ``perf/*.py``, ``configs/<name>.json``) are not claims
    about the tree."""
    found = set()
    for span in QUOTED.findall(text):
        for word in span.split():
            word = word.strip("()[],;'\"").rstrip(".").split("::")[0]
            word = re.sub(r":\d[\d,:-]*$", "", word)
            if PATH.match(word):
                found.add(word)
    return found


@pytest.mark.parametrize("doc", DOCS)
def test_quoted_paths_exist(doc, tree_files):
    files = tree_files
    with open(os.path.join(REPO, doc)) as f:
        paths = quoted_paths(f.read())
    assert paths, f"{doc} quotes no path: the scan is broken"
    missing = sorted(
        p for p in paths
        if p not in files and not any(f.endswith("/" + p) for f in files)
    )
    assert not missing, f"{doc} names files the tree does not have: {missing}"
