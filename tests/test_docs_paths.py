"""The documents name only files and targets that exist.

One case per document (``README.md``, ``Makefile``, each ``docs/*.md``):
every path it names that ends in ``.py``, ``.json``, ``.jsonl`` or
``.md`` (so every ``exps/run_*.py``), every script of a ``python <file>``
command and every ``make <target>`` exists in the tree / the Makefile.
A path is ours when its first segment is a top-level entry of this repo
or of the package (``telemetry/collectors.py``); the reference's
``magi_attention/...`` and ``cp_benchmark.md:...`` citations are not.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "Makefile"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)

_PATH = re.compile(r"[\w.\-/<>*{}$()]*\.(?:py|jsonl|json|md)\b(?!\w)")
_COMMAND = re.compile(r"\bpython3? ((?!-)[\w./-]+\.py)\b")
_MAKE = re.compile(
    r"\bmake ([a-z][a-z0-9]*(?:-[a-z0-9]+)+"
    r"|check|test|lint|install|analyze|typecheck)\b"
)
# a name with a placeholder or a glob in it is a pattern, not a path
_PATTERN_CHARS = set("<>*{}$()")


def _makefile_targets() -> set[str]:
    with open(os.path.join(REPO, "Makefile")) as f:
        return set(re.findall(r"^([a-z][a-z0-9-]*):", f.read(), re.M))


def _named_paths(text: str, doc: str) -> set[str]:
    """Paths of this repo that ``text`` names, relative to the root; a
    ``../`` link is resolved against the document's directory."""
    top = set(os.listdir(REPO))
    package = set(os.listdir(os.path.join(REPO, "magiattention_tpu")))
    out = set(_COMMAND.findall(text))
    for m in _PATH.finditer(text):
        name = m.group(0).lstrip("/")
        if _PATTERN_CHARS & set(name) or "/" not in name:
            continue
        if name.startswith("../"):
            name = os.path.normpath(os.path.join(os.path.dirname(doc), name))
        first = name.split("/", 1)[0]
        if first in top:
            out.add(name)
        elif first in package:
            out.add(os.path.join("magiattention_tpu", name))
    return out


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_what_exists(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    missing = sorted(
        p for p in _named_paths(text, doc)
        if not os.path.exists(os.path.join(REPO, p))
    )
    targets = _makefile_targets()
    missing += sorted(
        f"make {t}" for t in set(_MAKE.findall(text)) if t not in targets
    )
    assert not missing, f"{doc} names what is not in the tree: {missing}"
