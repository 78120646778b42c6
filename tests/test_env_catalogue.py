"""The flag catalogue is true: ``env.py``, its document and its readers.

One case per ``MAGI_ATTENTION_*`` name ``env.py`` defines (a string
literal inside an accessor function). The name

(a) has a row in the "Runtime flags" table of ``docs/env_variables.md``;
(b) is read, through an accessor, by a module of the package other than
    ``env.py``;
(c) if a module of ``api/``, ``meta/``, ``parallel/``, ``comm/``,
    ``ops/`` or ``tuning/`` reads it, an accessor of it is a member of
    ``flags_fingerprint``, or the name stands in ``NOT_FINGERPRINTED``
    with the reason it cannot change a plan or a traced program.

And the other way round: every name the document's own part lists
exists in ``env.py``. All of it is read from source; nothing is imported.
"""

import ast
import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "magiattention_tpu")
HOT = ("api", "meta", "parallel", "comm", "ops", "tuning")
_NAME = re.compile(r"MAGI_ATTENTION_[A-Z0-9_]*[A-Z0-9]")

# read on the hot path and yet no part of the runtime key: why that is safe
NOT_FINGERPRINTED = {
    "MAGI_ATTENTION_AUTOTUNE_CACHE_DIR": "a directory: where tuned rungs "
    "persist, not which rung a workload gets",
    "MAGI_ATTENTION_MIN_CHUNKS_PER_RANK": "only the default of chunk_size, "
    "and the resolved chunk_size is a field of the key",
    "MAGI_ATTENTION_PLAN_CACHE_SIZE": "capacity of an LRU: when a plan is "
    "evicted, never what a cached plan holds",
    "MAGI_ATTENTION_RUNTIME_DICT_SIZE": "capacity of an LRU, as above",
    "MAGI_ATTENTION_SANITY_CHECK": "checks the mask at key creation and "
    "raises; never changes what is built",
    "MAGI_ATTENTION_VALIDATE": "checks a built plan and raises; never "
    "changes what is built",
}


def _parse(path):
    with open(path) as f:
        return ast.parse(f.read())


@functools.cache
def _env_functions():
    return {
        n.name: n
        for n in _parse(os.path.join(PKG, "env.py")).body
        if isinstance(n, ast.FunctionDef)
    }


def _accessors():
    """name -> the functions of env.py that hold it as a string literal."""
    out = {}
    for fn, node in _env_functions().items():
        for c in ast.walk(node):
            if (
                isinstance(c, ast.Constant)
                and isinstance(c.value, str)
                and _NAME.fullmatch(c.value)
            ):
                out.setdefault(c.value, set()).add(fn)
    return out


ACCESSORS = _accessors()


@pytest.fixture(scope="module")
def fingerprint_members():
    return {
        c.func.id
        for c in ast.walk(_env_functions()["flags_fingerprint"])
        if isinstance(c, ast.Call) and isinstance(c.func, ast.Name)
    }


@pytest.fixture(scope="module")
def readers():
    """accessor -> package-relative files (env.py apart) that use it as
    ``env.<accessor>`` (under any alias) or import it from ``env``."""
    funcs = set(_env_functions())
    out = {}
    for d, _, files in os.walk(PKG):
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), PKG)
            if not f.endswith(".py") or rel == "env.py":
                continue
            nodes = list(ast.walk(_parse(os.path.join(d, f))))
            aliases = {"env"}
            for n in nodes:
                if isinstance(n, ast.ImportFrom):
                    from_env = (n.module or "").split(".")[-1] == "env"
                    for a in n.names:
                        if a.name == "env":
                            aliases.add(a.asname or "env")
                        elif from_env and a.name in funcs:
                            out.setdefault(a.name, set()).add(rel)
            for n in nodes:
                if (
                    isinstance(n, ast.Attribute)
                    and isinstance(n.value, ast.Name)
                    and n.value.id in aliases
                    and n.attr in funcs
                ):
                    out.setdefault(n.attr, set()).add(rel)
    return out


@pytest.fixture(scope="module")
def document():
    """(names with a row of their own, every name of the package's part)
    of docs/env_variables.md; the part ends where the reference's flags
    that have no counterpart here begin."""
    with open(os.path.join(REPO, "docs", "env_variables.md")) as f:
        own = f.read().split("## Where the reference's remaining flags went")[0]
    rows = set()
    for line in own.splitlines():
        if line.startswith("| `MAGI"):
            rows |= set(_NAME.findall(line.split("|")[1]))
    return rows, set(_NAME.findall(own))


@pytest.mark.parametrize("name", sorted(ACCESSORS))
def test_flag_is_documented_read_and_keyed(
    name, document, readers, fingerprint_members
):
    rows, _ = document
    assert name in rows, f"{name} has no row in docs/env_variables.md"
    read_by = set().union(*(readers.get(a, set()) for a in ACCESSORS[name]))
    assert read_by, (
        f"no module but env.py reads {name} "
        f"(accessors: {sorted(ACCESSORS[name])})"
    )
    hot = sorted(r for r in read_by if r.split(os.sep)[0] in HOT)
    keyed = bool(ACCESSORS[name] & fingerprint_members)
    if name in NOT_FINGERPRINTED:
        assert hot and not keyed, f"{name}: the exemption is stale"
    elif hot:
        assert keyed, (
            f"{name} is read by {hot} and is no member of "
            "flags_fingerprint: a changed flag would reuse a stale runtime"
        )


def test_document_lists_only_flags_that_exist(document):
    _, named = document
    assert named <= set(ACCESSORS), sorted(named - set(ACCESSORS))
    assert set(NOT_FINGERPRINTED) <= set(ACCESSORS)
