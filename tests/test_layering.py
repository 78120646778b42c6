"""The package's layer order, held as a test.

One case per module of ``magiattention_tpu``: every import it makes of
the package's own code (module-level and function-local, relative and
absolute) lands in its own layer or in one below. All of it is read from
source with ``ast``; nothing is imported.

``LAYERS`` is ROADMAP D9's order with three packages put where the code
has them, which is where the fewest exceptions are needed (6; the order
as D9 wished it, with ``telemetry`` and ``resilience`` near the top,
needs 38 such pairs in 28 modules):

- ``telemetry`` and ``resilience`` sit in the second layer, beside
  ``env`` and ``utils``. Recorders, spans and fault hooks are called
  from every layer down to ``utils/instrument.py``, and since the
  profilers that re-ran plans went (PR 57) nothing of ``telemetry/``
  reads a plan's, an engine's or a model's module: ``memory.py`` and
  ``aggregate.py`` take what they price as arguments. The four import
  one another (``env`` validates a chaos spec through ``resilience``,
  ``utils/instrument.py`` opens spans), so they are one layer: sideways
  is allowed, a layer is not ordered within.
- ``config`` sits above ``meta``: ``config.py`` composes the solvers'
  own config classes.

The root ``__init__`` is the facade and the top. ``examples/``, ``exps/``
and ``benchmarks/`` are outside the package and above it.

``ALLOWED_UPWARD`` lists the upward imports the tree still has, as exact
``(module, imported layer)`` pairs, each with the debt ROADMAP.md carries
for it. A second test holds every pair to the source, so the table
shrinks when a debt is paid. A new module is a new case; a new upward
import is a failure, not a new row, unless the PR's issue names the debt.
"""

import ast
import functools
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "magiattention_tpu"
ROOT = "__init__"  # the package's own __init__.py, as a layer member

LAYERS = (
    ("common",),
    ("utils", "env", "telemetry", "resilience"),
    ("csrc",),
    ("meta",),
    ("config",),
    ("comm",),
    ("ops",),
    ("tuning",),
    ("parallel",),
    ("api",),
    ("models", "serving", "extensions"),
    ("analysis", "fleet", "testing", "benchmarking"),
    (ROOT,),
)
RANK = {name: i for i, layer in enumerate(LAYERS) for name in layer}

ALLOWED_UPWARD = {
    ("ops/flex_attn.py", "tuning"): "D4: the rung table and the entry "
    "estimate belong in one module below both",
    ("parallel/dist_attn.py", "analysis"): "D9: the "
    "MAGI_ATTENTION_VALIDATE hook, which the caller in api/ could run",
    ("parallel/baselines/nsa.py", "api"): "D8: a baseline no cell "
    "compares against borrows api's window-mask builder",
    ("api/__init__.py", "serving"): "D9: the facade re-exports serving's "
    "names; R6 decides what a serving user imports",
    ("telemetry/collectors.py", "ops"): "D2: BWD_FORM, a choice with one "
    "value, read for its counter's label",
    ("telemetry/events.py", ROOT): "D9: the root's boot marks (PR 51), "
    "which the root could hand to telemetry instead",
}


def _modules():
    out = []
    for d, _, files in os.walk(os.path.join(REPO, PKG)):
        out += [
            os.path.relpath(os.path.join(d, f), os.path.join(REPO, PKG))
            for f in files
            if f.endswith(".py")
        ]
    return sorted(p.replace(os.sep, "/") for p in out)


MODULES = _modules()


def _layer_member(module):
    """``ops/flex_attn.py`` -> ``ops``; ``env.py`` -> ``env``."""
    head = module.split("/")[0]
    return head[:-3] if head.endswith(".py") else head


def _is_submodule(name):
    base = os.path.join(REPO, PKG, name)
    return os.path.isdir(base) or os.path.isfile(base + ".py")


@functools.cache
def _imports(module):
    """The layer members ``module`` imports, its own apart, each with the
    first line that does. A name taken from the root that is no module
    of the package (``from .. import _BOOT``) is the root itself."""
    with open(os.path.join(REPO, PKG, module)) as f:
        tree = ast.parse(f.read())
    here = [PKG] + module.split("/")[:-1]
    found = {}

    def note(path, names, line):
        # path: dotted parts from the package's root down, root included
        if len(path) > 1:
            members = [path[1]]
        else:
            members = [n if _is_submodule(n) else ROOT for n in names]
        for m in members:
            found.setdefault(m, line)

    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            names = [a.name for a in node.names]
            tail = node.module.split(".") if node.module else []
            if node.level:
                base = here[: len(here) - (node.level - 1)]
                note(base + tail, names, node.lineno)
            elif tail[:1] == [PKG]:
                note(tail, names, node.lineno)
        elif isinstance(node, ast.Import):
            for a in node.names:
                parts = a.name.split(".")
                if parts[0] == PKG:
                    note(parts, [], node.lineno)
    found.pop(_layer_member(module), None)
    return found


def test_every_module_has_a_layer():
    members = {_layer_member(m) for m in MODULES}
    assert members == set(RANK), sorted(members ^ set(RANK))
    assert len(RANK) == sum(len(layer) for layer in LAYERS)


@pytest.mark.parametrize("module", MODULES)
def test_module_imports_downward_or_sideways(module):
    mine = RANK[_layer_member(module)]
    upward = {
        f"{module}:{line} imports {member}"
        for member, line in _imports(module).items()
        if RANK[member] > mine and (module, member) not in ALLOWED_UPWARD
    }
    assert not upward, (
        f"{sorted(upward)}: an import that points up the layer order "
        "(tests/test_layering.py LAYERS). Move what is shared below both, "
        "or hand it in as an argument"
    )


@pytest.mark.parametrize("pair", sorted(ALLOWED_UPWARD), ids="->".join)
def test_listed_exception_is_still_in_the_source(pair):
    module, member = pair
    assert module in MODULES, f"{module} is gone: drop its row"
    assert member in _imports(module), (
        f"{module} no longer imports {member}: the debt is paid, "
        "drop its row"
    )
    assert RANK[member] > RANK[_layer_member(module)], (
        f"{module} -> {member} does not point up: drop its row"
    )
