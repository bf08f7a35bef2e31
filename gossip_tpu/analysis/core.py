"""Static-analysis core: findings, parsed modules, and the suppression
baseline every checker family shares.

Thirteen PRs of hard-won invariants lived only in prose and reviewer
memory — the PR 9/12 serving recompile lessons, the PR 13 batcher
shutdown race, the repo's provenance/budget/`Ledger.event` contract
conventions (docs/STATIC_ANALYSIS.md has the full catalog).  The Go
reference culture leans on ``go vet`` + the race detector for exactly
this bug class; this package is that discipline pointed at our own
source: pure-stdlib AST passes, no jax import anywhere (the analyzer
must run on a box with no chip).

Contracts:

  * a :class:`Finding` is identified by ``(rule, path, symbol)`` — the
    suppression key is content-addressed (qualified name), never a
    line number, so an unrelated edit above a baselined site cannot
    orphan its suppression;
  * the baseline (tools/staticcheck_baseline.json) is the
    validate_artifacts allowlist discipline applied to lint: every
    entry carries a non-empty ``rationale`` string, a stale entry (one
    matching no live finding) is itself a finding, and the committed
    entry count is pinned by tests/test_staticcheck.py so the file can
    only shrink;
  * checkers are pure functions ``(modules, ...) -> [Finding]`` over
    pre-parsed :class:`Module` objects, so the planted-violation
    fixtures under tests/data/staticcheck/ run through exactly the
    code path the live tree does.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
from typing import Dict, Iterable, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the one committed suppression file (runner + tests share the path)
BASELINE_PATH = os.path.join("tools", "staticcheck_baseline.json")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One invariant violation at one site.

    ``checker`` is the family (``recompile`` / ``locks`` /
    ``conventions`` / ``baseline``); ``rule`` the specific invariant;
    ``symbol`` the dotted qualname of the enclosing def/class (or ""
    at module level) — the stable half of the suppression key."""

    checker: str
    rule: str
    path: str            # repo-relative, forward slashes
    line: int
    symbol: str
    message: str

    def key(self) -> str:
        return f"{self.rule}:{self.path}:{self.symbol}"

    def render(self) -> str:
        sym = f" [{self.symbol}]" if self.symbol else ""
        return (f"{self.path}:{self.line}: {self.checker}/{self.rule}"
                f"{sym}: {self.message}")


class Module:
    """A parsed source file plus the parent/qualname maps every
    checker needs (computed once here, never per pass)."""

    def __init__(self, path: str, relpath: str, source: str,
                 tree: ast.Module):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.tree = tree
        self.parents: Dict[ast.AST, ast.AST] = {}
        self._qualnames: Dict[ast.AST, str] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node

    def qualname(self, node: ast.AST) -> str:
        """Dotted name of the innermost enclosing def/class chain
        (``Batcher._admit``), "" at module level."""
        if node in self._qualnames:
            return self._qualnames[node]
        parts: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            cur = self.parents.get(cur)
        qn = ".".join(reversed(parts))
        self._qualnames[node] = qn
        return qn

    def enclosing_function(self, node: ast.AST):
        """The innermost FunctionDef/AsyncFunctionDef containing
        ``node``, or None at module/class level."""
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(cur)
        return None

    def enclosing_class(self, node: ast.AST):
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur
            cur = self.parents.get(cur)
        return None


def parse_file(path: str, root: str) -> Module:
    with open(path, encoding="utf-8") as f:
        source = f.read()
    rel = os.path.relpath(path, root)
    return Module(path, rel, source, ast.parse(source, filename=path))


def load_modules(root: str, relpaths: Iterable[str]) -> Dict[str, Module]:
    """{relpath: Module} for every existing path; a missing file is
    skipped (scope lists name optional modules), a SYNTAX error is
    not — the analyzer refuses to bless a tree it cannot parse."""
    out: Dict[str, Module] = {}
    for rel in relpaths:
        path = os.path.join(root, rel)
        if not os.path.isfile(path):
            continue
        mod = parse_file(path, root)
        out[mod.relpath] = mod
    return out


def iter_py_files(root: str, subdirs: Iterable[str],
                  exclude_dirs: Tuple[str, ...] = ("tests/data",
                                                   "__pycache__")):
    """Repo-relative paths of every .py under ``subdirs`` (or the
    files themselves), excluding fixture/cache dirs — the planted
    violations under tests/data/staticcheck must never count against
    the live tree."""
    for sub in subdirs:
        base = os.path.join(root, sub)
        if os.path.isfile(base):
            yield sub
            continue
        for dirpath, dirnames, filenames in os.walk(base):
            rel_dir = os.path.relpath(dirpath, root).replace(os.sep, "/")
            if any(rel_dir == e or rel_dir.startswith(e + "/")
                   for e in exclude_dirs):
                dirnames[:] = []
                continue
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield f"{rel_dir}/{fn}"


# -- small AST helpers shared by the checker families -----------------

def call_name(node: ast.Call) -> str:
    """Dotted text of the call target (``jnp.stack``, ``self._stop
    .is_set``) — terminal-name matching beats full resolution for
    passes that must stay import-free."""
    return expr_text(node.func)


def expr_text(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:           # pragma: no cover - unparse is total on 3.10
        return ""


def keyword_arg(node: ast.Call, name: str):
    for kw in node.keywords:
        if kw.arg == name:
            return kw
    return None


def has_decorator(fn, *names: str) -> bool:
    """True when any decorator's terminal name matches (``lru_cache``
    matches ``functools.lru_cache(maxsize=32)`` and bare
    ``@lru_cache``)."""
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        text = expr_text(target)
        term = text.rsplit(".", 1)[-1]
        if term in names:
            return True
    return False


def str_const(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


# -- suppression baseline ---------------------------------------------

REQUIRED_ENTRY_KEYS = ("rule", "path", "symbol", "rationale")


def load_baseline(path: str):
    """(entries, problems): the committed suppressions plus any
    baseline-discipline findings — a malformed entry or one with a
    missing/empty rationale is a FINDING (checker ``baseline``), not a
    parse warning: a suppression nobody can justify is exactly the
    silent grandfathering this file exists to forbid."""
    problems: List[Finding] = []
    if not os.path.isfile(path):
        return [], problems
    rel = os.path.basename(path)
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (ValueError, OSError) as e:
        # an unreadable/unparseable baseline is a FINDING, never a
        # traceback: the analyzer must exit 1 with a named reason, not
        # crash every dry run on a hand-edit's trailing comma
        return [], [Finding(
            "baseline", "malformed-baseline", rel, 1, "",
            f"baseline does not parse: {e}")]
    if not isinstance(doc, dict):
        return [], [Finding(
            "baseline", "malformed-baseline", rel, 1, "",
            "baseline must be a JSON object with a 'suppressions' "
            f"list, got {type(doc).__name__}")]
    entries = doc.get("suppressions", [])
    if not isinstance(entries, list):
        return [], [Finding(
            "baseline", "malformed-baseline", rel, 1, "",
            "'suppressions' must be a list of entry objects")]
    good = []
    for i, e in enumerate(entries):
        if not isinstance(e, dict) or any(k not in e
                                          for k in REQUIRED_ENTRY_KEYS):
            problems.append(Finding(
                "baseline", "malformed-baseline", rel, 1, "",
                f"entry {i} must carry the keys "
                f"{REQUIRED_ENTRY_KEYS}: {e!r:.120}"))
            continue
        if not str(e["rationale"]).strip():
            problems.append(Finding(
                "baseline", "missing-rationale", rel, 1,
                str(e.get("symbol", "")),
                f"entry {i} ({e['rule']}:{e['path']}) has an empty "
                "rationale — every accepted finding must say WHY it "
                "is accepted (the allowlist-only-shrinks contract)"))
            continue
        good.append(e)
    return good, problems


def apply_baseline(findings: List[Finding], entries: List[dict],
                   baseline_rel: str = BASELINE_PATH):
    """(unsuppressed, suppressed, stale) — a finding is suppressed iff
    some entry matches its ``(rule, path, symbol)`` exactly; an entry
    matching NOTHING is stale and becomes a finding itself, so fixing
    a violation forces its suppression out of the file (the baseline
    only shrinks — tests/test_staticcheck.py pins the count)."""
    by_key = {}
    for e in entries:
        by_key[f"{e['rule']}:{e['path']}:{e['symbol']}"] = e
    unsuppressed, suppressed = [], []
    used = set()
    for f in findings:
        e = by_key.get(f.key())
        if e is not None:
            used.add(f.key())
            suppressed.append(f)
        else:
            unsuppressed.append(f)
    stale = [Finding(
        "baseline", "stale-suppression",
        baseline_rel.replace(os.sep, "/"), 1, str(e["symbol"]),
        f"suppression {k} matches no live finding — the violation "
        "was fixed (or the symbol moved); delete the entry, the "
        "baseline only shrinks")
        for k, e in by_key.items() if k not in used]
    return unsuppressed, suppressed, stale
