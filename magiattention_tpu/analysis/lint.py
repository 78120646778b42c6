"""Pass 1 — AST compat/idiom linter (rule codes MAGI001..MAGI004).

Walks python source ASTs (no imports, no jax) and enforces the repo
rules that keep the SPMD stack portable and legible:

- **MAGI001** — no direct ``jax.shard_map`` / ``jax.experimental
  .shard_map`` / ``pltpu.CompilerParams`` / ``pltpu.TPUCompilerParams``
  outside ``utils/compat.py``. Direct spellings are exactly the
  version-skew class that took ~207 tier-1 tests offline before ISSUE 7;
  the compat shims are behavior-identical on current jax.
- **MAGI002** — no environment reads (``os.environ`` / ``os.getenv``)
  outside ``env.py``. Every flag gets one documented accessor so
  planning-relevant flags can be folded into ``flags_fingerprint`` and
  ``docs/env_variables.md`` stays the single catalog.
- **MAGI003** — no host-sync idioms (``.item()``, ``float()`` / ``int()``
  / ``np.asarray()`` on traced values) inside the ``ops/`` / ``parallel/``
  / ``serving/`` / ``comm/`` hot paths. A host sync inside a traced
  region either crashes under jit or silently serializes the pipeline.
  "Traced context" is heuristic (see :func:`_is_traced_function`); the
  allowlist and the ``# magi-allow: MAGI003`` pragma cover deliberate
  host-side uses.
- **MAGI004** — every ``lax.ppermute`` / ``lax.all_to_all`` /
  ``lax.psum`` call site lexically wrapped in a ``named_scope`` so
  profiler timelines stay legible.
  ISSUE 13 extends the rule to ``jax.device_put`` inside ``serving/``:
  there a device_put IS a wire hop (the page-stream / pool-pinning
  transfer), and an unscoped hop is invisible in a device trace.
- **MAGI005** — no ``axis_index`` / ``process_index``-dependent host
  control flow (``if``/``while``/ternary) lexically guarding a
  collective issue site. Rank-gated host branching around a collective
  is the static root cause of cross-rank schedule divergence — one
  rank traces an extra (or missing) collective and the pod hangs, not
  errors (the value-level half of this check is
  ``analysis/spmd_audit.py``). Rank-dependent *data* belongs in traced
  selects (``jnp.where(lax.axis_index(...) == r, ...)``), never in
  host branches around collective issue sites.

Deliberate exceptions live in ``exps/data/analysis_allowlist.json`` as
``{rule, path, symbol, justification}`` records (symbol = dotted
enclosing def/class scope, ``"*"`` wildcard), or inline as a
``# magi-allow: MAGI00X`` comment on the flagged line.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
from typing import Iterable, Sequence

RULES: dict[str, str] = {
    "MAGI001": (
        "direct jax.shard_map / pltpu.CompilerParams — route through "
        "utils/compat (shard_map / tpu_compiler_params)"
    ),
    "MAGI002": "environment read outside env.py — add an env.py accessor",
    "MAGI003": "host-sync idiom on a traced value inside a hot path",
    "MAGI004": (
        "collective (ppermute/all_to_all/psum) not wrapped in named_scope"
    ),
    "MAGI005": (
        "axis_index/process_index-dependent host control flow guards a "
        "collective issue site — per-rank schedule divergence (pod "
        "hang); use a traced select or restructure"
    ),
}

# rule scopes (path prefixes are repo-relative, posix separators)
_PACKAGE = "magiattention_tpu"
_COMPAT_FILE = f"{_PACKAGE}/utils/compat.py"
_ENV_FILE = f"{_PACKAGE}/env.py"
_HOT_PATHS = tuple(
    f"{_PACKAGE}/{d}/" for d in ("ops", "parallel", "serving", "comm")
)
_COLLECTIVES = ("ppermute", "all_to_all", "psum")
# the wire-collective set MAGI005 treats as an issue site (a superset
# of the MAGI004 scoping set — any of these inside rank-gated host
# control flow diverges the per-rank schedule)
_WIRE_COLLECTIVES = (
    "ppermute",
    "all_to_all",
    "psum",
    "psum_scatter",
    "all_gather",
    "reduce_scatter",
)
_RANK_SOURCES = ("axis_index", "process_index")
# serving/ device_puts are wire hops (page streams, pool pinning) and
# fall under MAGI004's named_scope rule there
_DEVICE_PUT_SCOPE = f"{_PACKAGE}/serving/"
_PRAGMA = "# magi-allow:"


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    path: str  # repo-relative posix path
    line: int
    symbol: str  # dotted enclosing scope, "<module>" at top level
    message: str

    def render(self) -> str:
        return (
            f"{self.path}:{self.line}: {self.rule} {self.message} "
            f"[{self.symbol}]"
        )


def _attr_chain(node: ast.AST) -> str | None:
    """``a.b.c`` -> "a.b.c" for pure Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_named_scope_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    chain = _attr_chain(node.func)
    return bool(chain) and chain.split(".")[-1] == "named_scope"


def _annotation_mentions_jax_array(node: ast.AST | None) -> bool:
    if node is None:
        return False
    try:
        text = ast.unparse(node)
    except Exception:  # pragma: no cover - malformed annotation
        return False
    return "jax.Array" in text or text == "Array"


def _all_params(fn) -> list[ast.arg]:
    args = fn.args
    return (
        list(args.posonlyargs)
        + list(args.args)
        + list(args.kwonlyargs)
        + ([args.vararg] if args.vararg else [])
    )


def _has_traced_decorator(fn) -> bool:
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        chain = _attr_chain(target) or ""
        leaf = chain.split(".")[-1]
        if leaf in ("shard_map", "jit"):
            return True
        if leaf == "partial" and isinstance(dec, ast.Call) and dec.args:
            first = _attr_chain(dec.args[0]) or ""
            if first.split(".")[-1] in ("shard_map", "jit"):
                return True
    return False


def _traced_info(fn) -> tuple[bool, set[str]]:
    """Heuristic trace analysis of one function def.

    Returns ``(is_traced_context, traced_param_names)``:

    - a ``shard_map`` / ``jit`` decorated fn (directly or via
      ``functools.partial``) traces with EVERY parameter traced;
    - a fn with ``jax.Array``-annotated parameters is a traced context,
      but only the annotated parameters themselves count as traced
      values (``scale: float`` next to ``q: jax.Array`` is host-static —
      the pre-ISSUE-7 tree is full of such mixed signatures, all
      legitimate);
    - anything else is host code.
    """
    if _has_traced_decorator(fn):
        return True, {a.arg for a in _all_params(fn)}
    traced = {
        a.arg
        for a in _all_params(fn)
        if _annotation_mentions_jax_array(a.annotation)
    }
    return bool(traced), traced


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, source: str):
        self.path = path
        self.lines = source.splitlines()
        self.violations: list[Violation] = []
        self._scope: list[str] = []
        self._with_scope_depth = 0  # inside a `with named_scope(...)`
        self._traced_depth = 0  # inside a traced-context function
        self._in_hot_path = path.startswith(_HOT_PATHS)
        self._traced_params: list[set[str]] = []
        # names bound from axis_index()/process_index() calls, one set
        # per lexical scope (nested scopes inherit — a closure over the
        # rank is still the rank)
        self._rank_names: list[set[str]] = [set()]

    # -- helpers --------------------------------------------------------

    def _symbol(self) -> str:
        return ".".join(self._scope) if self._scope else "<module>"

    def _flag(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 0)
        # inline pragma: `# magi-allow: MAGI003` (optionally several
        # comma-separated codes) anywhere on the flagged line
        if 0 < line <= len(self.lines):
            text = self.lines[line - 1]
            if _PRAGMA in text:
                allowed = text.split(_PRAGMA, 1)[1]
                if rule in [c.strip() for c in allowed.split(",")]:
                    return
        self.violations.append(
            Violation(rule, self.path, line, self._symbol(), message)
        )

    # -- scope tracking -------------------------------------------------

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._scope.append(node.name)
        self.generic_visit(node)
        self._scope.pop()

    def _visit_function(self, node) -> None:
        self._scope.append(node.name)
        is_traced, traced_names = _traced_info(node)
        # nesting inside a traced fn keeps the traced *context* (for
        # .item()) but does not make the nested fn's own params traced
        traced = is_traced or self._traced_depth > 0
        self._traced_depth += 1 if traced else 0
        self._traced_params.append(traced_names)
        self._rank_names.append(set(self._rank_names[-1]))
        self.generic_visit(node)
        self._rank_names.pop()
        self._traced_params.pop()
        self._traced_depth -= 1 if traced else 0
        self._scope.pop()

    visit_FunctionDef = _visit_function
    visit_AsyncFunctionDef = _visit_function

    # -- MAGI005: rank-gated host control flow over collectives ----------

    def visit_Assign(self, node: ast.Assign) -> None:
        is_rank = (
            isinstance(node.value, ast.Call)
            and (_attr_chain(node.value.func) or "").split(".")[-1]
            in _RANK_SOURCES
        )
        for t in node.targets:
            if isinstance(t, ast.Name):
                if is_rank:
                    self._rank_names[-1].add(t.id)
                else:
                    # rebinding to a non-rank value clears the taint —
                    # `r = axis_index(..); ...; r = 0` is rank-free
                    self._rank_names[-1].discard(t.id)
        self.generic_visit(node)

    def _mentions_rank(self, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                chain = _attr_chain(sub.func) or ""
                if chain.split(".")[-1] in _RANK_SOURCES:
                    return True
            elif (
                isinstance(sub, ast.Name)
                and sub.id in self._rank_names[-1]
            ):
                return True
        return False

    def _issues_collective(self, nodes) -> bool:
        for n in nodes:
            for sub in ast.walk(n):
                if isinstance(sub, ast.Call):
                    chain = _attr_chain(sub.func) or ""
                    if chain.split(".")[-1] in _WIRE_COLLECTIVES:
                        return True
        return False

    def _check_rank_gate(self, node, guarded) -> None:
        if self._mentions_rank(node.test) and self._issues_collective(
            guarded
        ):
            self._flag("MAGI005", node, RULES["MAGI005"])

    def visit_If(self, node: ast.If) -> None:
        self._check_rank_gate(node, node.body + node.orelse)
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._check_rank_gate(node, node.body + node.orelse)
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp) -> None:
        self._check_rank_gate(node, [node.body, node.orelse])
        self.generic_visit(node)

    def visit_With(self, node: ast.With) -> None:
        scoped = any(
            _is_named_scope_call(item.context_expr) for item in node.items
        )
        self._with_scope_depth += 1 if scoped else 0
        self.generic_visit(node)
        self._with_scope_depth -= 1 if scoped else 0

    # -- MAGI001 / MAGI002: imports -------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        # `import jax.experimental.shard_map [as sm]` — aliasing does not
        # make the skew class portable
        if self.path != _COMPAT_FILE:
            for a in node.names:
                if a.name.startswith("jax.experimental.shard_map"):
                    self._flag("MAGI001", node, RULES["MAGI001"])
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        names = {a.name for a in node.names}
        if self.path != _COMPAT_FILE:
            if mod == "jax" and "shard_map" in names:
                self._flag("MAGI001", node, RULES["MAGI001"])
            # both `from jax.experimental.shard_map import shard_map`
            # and `from jax.experimental import shard_map`
            if mod.startswith("jax.experimental.shard_map") or (
                mod == "jax.experimental" and "shard_map" in names
            ):
                self._flag("MAGI001", node, RULES["MAGI001"])
            if names & {"CompilerParams", "TPUCompilerParams"} and (
                "pallas" in mod
            ):
                self._flag("MAGI001", node, RULES["MAGI001"])
        if (
            self.path != _ENV_FILE
            and mod == "os"
            and names & {"environ", "getenv"}
        ):
            # `from os import environ` would let every later use evade
            # the os.environ chain check — flag the import itself
            self._flag("MAGI002", node, RULES["MAGI002"])
        self.generic_visit(node)

    # -- expression-level rules -----------------------------------------

    def visit_Attribute(self, node: ast.Attribute) -> None:
        chain = _attr_chain(node)
        if chain and self.path != _COMPAT_FILE:
            if chain.endswith(".shard_map") and chain.split(".")[0] == "jax":
                self._flag("MAGI001", node, RULES["MAGI001"])
            if node.attr in ("CompilerParams", "TPUCompilerParams"):
                self._flag("MAGI001", node, RULES["MAGI001"])
        if chain == "os.environ" and self.path != _ENV_FILE:
            self._flag("MAGI002", node, RULES["MAGI002"])
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        chain = _attr_chain(node.func) or ""
        leaf = chain.split(".")[-1] if chain else ""

        if chain == "os.getenv" and self.path != _ENV_FILE:
            self._flag("MAGI002", node, RULES["MAGI002"])

        # MAGI004: bare collectives (lax.X / jax.lax.X spellings)
        if (
            leaf in _COLLECTIVES
            and chain in (f"lax.{leaf}", f"jax.lax.{leaf}")
            and self._with_scope_depth == 0
        ):
            self._flag(
                "MAGI004",
                node,
                f"lax.{leaf} call site not under a named_scope block",
            )

        # MAGI004 (ISSUE 13): serving-layer device_put is a wire hop
        # (page stream / pool pinning) and needs a scope for the hop
        # timeline, same as the collectives above. Leaf-matched like
        # MAGI005's rank sources, so aliased spellings
        # (`from jax import device_put`) cannot evade it.
        if (
            leaf == "device_put"
            and self.path.startswith(_DEVICE_PUT_SCOPE)
            and self._with_scope_depth == 0
        ):
            self._flag(
                "MAGI004",
                node,
                "jax.device_put (serving wire hop) not under a "
                "named_scope block",
            )

        # MAGI003: host-sync idioms in traced hot-path contexts
        if self._in_hot_path and self._traced_depth > 0:
            traced_names = (
                self._traced_params[-1] if self._traced_params else set()
            )
            if leaf == "item" and isinstance(node.func, ast.Attribute):
                self._flag(
                    "MAGI003",
                    node,
                    ".item() forces a device->host sync under tracing",
                )
            elif chain in ("float", "int") and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Name) and arg.id in traced_names:
                    self._flag(
                        "MAGI003",
                        node,
                        f"{chain}() on traced value {arg.id!r} host-syncs",
                    )
            elif chain in ("np.asarray", "np.array", "numpy.asarray",
                           "numpy.array") and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Name) and arg.id in traced_names:
                    self._flag(
                        "MAGI003",
                        node,
                        f"{chain}() on traced value {arg.id!r} host-syncs",
                    )
        self.generic_visit(node)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def lint_source(source: str, path: str) -> list[Violation]:
    """Lint one python source blob; ``path`` is the repo-relative posix
    path used for rule scoping (compat/env exemptions, hot-path MAGI003)."""
    tree = ast.parse(source, filename=path)
    linter = _Linter(path, source)
    linter.visit(tree)
    return linter.violations


def lint_paths(
    root: str, rel_paths: Iterable[str]
) -> list[Violation]:
    out: list[Violation] = []
    for rel in sorted(rel_paths):
        full = os.path.join(root, rel)
        with open(full, "r", encoding="utf-8") as f:
            src = f.read()
        out.extend(lint_source(src, rel.replace(os.sep, "/")))
    return out


def _python_files(root: str, subdir: str) -> list[str]:
    found = []
    base = os.path.join(root, subdir)
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = [
            d for d in dirnames if d not in ("__pycache__", ".git")
        ]
        for name in filenames:
            if name.endswith(".py"):
                rel = os.path.relpath(os.path.join(dirpath, name), root)
                found.append(rel.replace(os.sep, "/"))
    return found


def lint_package(
    root: str,
    *,
    extra_compat_roots: Sequence[str] = ("tests", "exps", "examples"),
) -> list[Violation]:
    """Lint the full package tree under ``root`` (the repo checkout).

    All four rules run over ``magiattention_tpu/``; the
    ``extra_compat_roots`` (tests/exps/examples) are checked for MAGI001
    only — a test spelling ``from jax import shard_map`` breaks at
    collection on the next rename, which is exactly the class this
    linter exists to pin down.
    """
    violations = lint_paths(root, _python_files(root, _PACKAGE))
    for extra in extra_compat_roots:
        if not os.path.isdir(os.path.join(root, extra)):
            continue
        violations.extend(
            v
            for v in lint_paths(root, _python_files(root, extra))
            if v.rule == "MAGI001"
        )
    return violations


# ---------------------------------------------------------------------------
# allowlist
# ---------------------------------------------------------------------------


def load_allowlist(path: str) -> list[dict]:
    with open(path, "r", encoding="utf-8") as f:
        entries = json.load(f)
    for e in entries:
        missing = {"rule", "path", "symbol", "justification"} - set(e)
        if missing:
            raise ValueError(
                f"allowlist entry {e!r} missing fields {sorted(missing)}"
            )
        if not str(e["justification"]).strip():
            raise ValueError(f"allowlist entry {e!r} needs a justification")
    return entries


def apply_allowlist(
    violations: Sequence[Violation], entries: Sequence[dict]
) -> tuple[list[Violation], list[dict]]:
    """Filter ``violations`` through the allowlist.

    Returns ``(remaining, stale_entries)`` — stale entries matched
    nothing and should be deleted (the violation they covered is gone),
    keeping the allowlist an honest record instead of a grandfather
    file.
    """
    used = [False] * len(entries)
    remaining: list[Violation] = []
    for v in violations:
        suppressed = False
        for i, e in enumerate(entries):
            if (
                e["rule"] == v.rule
                and e["path"] == v.path
                and (e["symbol"] == "*" or e["symbol"] == v.symbol)
            ):
                used[i] = True
                suppressed = True
        if not suppressed:
            remaining.append(v)
    stale = [e for i, e in enumerate(entries) if not used[i]]
    return remaining, stale
