"""The living documents name files that exist.

A document that sends its reader to a script, a module or a record is
checked against the tree: PRs 21-29 moved and removed files while the
docs, the workflow and the verify notes kept naming them. PERF.md,
ROADMAP.md and CHANGES.md are history — they name files that went on
purpose — and are not cases.
"""
import glob
import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = [
    "README.md",
    *sorted(
        os.path.relpath(p, REPO_ROOT)
        for p in glob.glob(os.path.join(REPO_ROOT, "docs", "*.md"))
    ),
    ".claude/skills/verify/SKILL.md",
    ".github/workflows/test.yaml",
    "format.sh",
]

#: Where a document's short names resolve: `cli.py` and `serve/engine.py`
#: are the package's, `pb/costs.py` the benchmark's.
ROOTS = ("", "ray_lightning_tpu", "perfbench")

_FILE = re.compile(r"^[\w.\-/]+\.(?:py|sh|json|md|yaml|toml)$")
_LINES = re.compile(r":[\d,\-–:]*$")
_BACKTICKED = re.compile(r"`([^`\n]+)`")
_INVOKED = re.compile(r"\bpython3?\s+(?:-\w+\s+)*([^\s`'\"]+)")


def _named_paths(text, prose=True):
    """Every repo path ``text`` names: a backticked or ``python``-invoked
    word — in a script or a workflow (``prose=False``) any word — that
    ends in a source or record suffix (a ``:line`` suffix stripped), at
    the top level or with directories before it. Patterns (``*``),
    placeholders (``<n>``, ``{tag}``), URLs and paths outside the checkout
    (``/tmp/...``, ``~/...``) are not repo paths."""
    if prose:
        words = [w for span in _BACKTICKED.findall(text) for w in span.split()]
        words += _INVOKED.findall(text)
    else:
        words = text.split()
    found = set()
    for word in words:
        word = _LINES.sub("", word.strip("()[],;\"'"))
        if word.startswith(("/", "~", "-")) or "://" in word:
            continue
        if _FILE.match(word):
            found.add(word[2:] if word.startswith("./") else word)
    return sorted(found)


def _in_tree(path):
    return any(
        os.path.isfile(os.path.join(REPO_ROOT, root, path)) for root in ROOTS
    )


def test_the_extraction_reads_what_a_document_writes():
    text = (
        "Run `python tools/flash_check.py --all`, see `serve/engine.py:758-764`\n"
        "and `chip_smoke.py`; not `tests/*.py`, `out_<n>.json`, `/tmp/x.json`\n"
        "or https://example.com/a/config.json.\n"
        "      - run: python -u gone.py\n"
    )
    assert _named_paths(text) == [
        "chip_smoke.py", "gone.py", "serve/engine.py", "tools/flash_check.py",
    ]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_in_the_tree(document):
    with open(os.path.join(REPO_ROOT, document)) as f:
        named = _named_paths(f.read(), prose=document.endswith(".md"))
    missing = [p for p in named if not _in_tree(p)]
    assert not missing, f"{document} names files not in the tree: {missing}"
