"""jaxlint core: one AST parse per file, rule registry, suppressions,
baseline, reporters.

The analyzer exists because this repo's worst bugs are *invisible in
review*: a ``jax.jit`` of a fresh closure re-traces on every call (the
warm-bucket serving tier exists precisely to avoid that), a stray
``.item()`` on the step path stalls the chip on a host sync, and a
lock acquired in a different order on two paths deadlocks only under production load.
Compiler stacks make such invariants checkable properties of the program
representation (Relay arXiv:1810.00952, nGraph arXiv:1801.08058); this
module does the same for the Python/JAX layer so they gate tier-1
instead of living in review lore.

Design contract:

- **one parse** — every file is read and ``ast.parse``d exactly once
  (:class:`SourceFile`); every rule walks that shared tree.  Rules are
  cheap visitors, the file walk is the expensive part.
- **suppressions carry reasons** — ``# jaxlint: disable=<rule> -- why``
  on the finding's line (or a comment line directly above).  A
  suppression without reason text still silences its target but raises
  ``bad-suppression``, which can itself never be suppressed or
  baselined: you cannot silence the analyzer without saying why.
  ``# jaxlint: sync-ok -- why`` is sugar for ``disable=host-sync``.
- **baseline** — grandfathered findings live in a committed JSON file
  keyed by (rule, path, source-line text), not line numbers, so
  unrelated edits above a finding don't resurface it.
  ``--baseline-update`` rewrites the file from the current findings.
- **reporters** — stable text (``path:line:col: rule: message``) and a
  JSON document for machine consumers.
"""
from __future__ import annotations

import ast
import json
import re
import time
from collections import Counter
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Finding", "SourceFile", "Rule", "Linter", "RunResult",
           "register_rule", "all_rule_ids", "make_rules",
           "render_text", "render_json", "load_baseline", "save_baseline",
           "BAD_SUPPRESSION", "PARSE_ERROR"]

#: meta rule ids — produced by the framework itself, never suppressible
#: or baselineable (they police the escape hatches)
BAD_SUPPRESSION = "bad-suppression"
PARSE_ERROR = "parse-error"
META_RULES = (BAD_SUPPRESSION, PARSE_ERROR)

_PRAGMA_RE = re.compile(r"#\s*jaxlint:\s*(?P<body>.*)$")
_DISABLE_RE = re.compile(
    r"^\s*(?:disable=(?P<rules>[A-Za-z0-9_,\s-]+?)|(?P<syncok>sync-ok))"
    r"\s*(?:--\s*(?P<reason>.*))?$")


class Finding:
    """One diagnostic.  ``context`` is the stripped source line — the
    line-number-independent half of the baseline key."""

    __slots__ = ("rule", "path", "line", "col", "message", "context",
                 "suppressed", "baselined")

    def __init__(self, rule: str, path: str, line: int, col: int,
                 message: str, context: str = ""):
        self.rule = rule
        self.path = path
        self.line = int(line)
        self.col = int(col)
        self.message = message
        self.context = context
        self.suppressed = False
        self.baselined = False

    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.context)

    def location(self) -> str:
        return f"{self.path}:{self.line}:{self.col}"

    def to_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message,
                "context": self.context, "suppressed": self.suppressed,
                "baselined": self.baselined}


class _Suppression:
    __slots__ = ("rules", "reason", "line", "used")

    def __init__(self, rules: Sequence[str], reason: str, line: int):
        self.rules = tuple(rules)
        self.reason = reason
        self.line = line
        self.used = False


class SourceFile:
    """One parsed file shared by every rule (the single-parse contract)."""

    def __init__(self, path: Path, root: Path):
        self.path = path
        try:
            self.relpath = path.resolve().relative_to(
                root.resolve()).as_posix()
        except ValueError:      # outside the root (tmp fixtures): as-is
            self.relpath = path.resolve().as_posix()
        self.text = path.read_text(encoding="utf-8")
        self.lines = self.text.splitlines()
        self.tree: Optional[ast.Module] = None
        self.parse_error: Optional[SyntaxError] = None
        try:
            self.tree = ast.parse(self.text, filename=str(path))
        except SyntaxError as e:
            self.parse_error = e
        #: line -> suppressions whose scope includes that line
        self._supp_by_line: Dict[int, List[_Suppression]] = {}
        self.suppressions: List[_Suppression] = []
        self.pragma_errors: List[Finding] = []
        self._parse_pragmas()

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    # -- pragmas ---------------------------------------------------------
    def _parse_pragmas(self) -> None:
        pending: List[_Suppression] = []      # comment-line pragmas
        for lineno, raw in enumerate(self.lines, 1):
            m = _PRAGMA_RE.search(raw)
            stripped = raw.strip()
            is_comment_only = stripped.startswith("#")
            # ANY code line consumes the pending comment-line pragmas —
            # including a code line that carries its own inline pragma;
            # leaking pending past it would silently suppress the NEXT
            # unrelated line
            if stripped and not is_comment_only:
                for s in pending:
                    self._supp_by_line.setdefault(lineno, []).append(s)
                pending = []
            if m is None:
                continue
            body = m.group("body").strip()
            dm = _DISABLE_RE.match(body)
            if dm is None:
                self.pragma_errors.append(Finding(
                    BAD_SUPPRESSION, self.relpath, lineno, 0,
                    f"unparseable jaxlint pragma {body!r} (expected "
                    "'disable=<rule>[,<rule>...] -- <reason>' or "
                    "'sync-ok -- <reason>')", self.line_text(lineno)))
                continue
            if dm.group("syncok") is not None:
                rules = ["host-sync"]
            else:
                rules = [r.strip() for r in dm.group("rules").split(",")
                         if r.strip()]
            reason = (dm.group("reason") or "").strip()
            supp = _Suppression(rules, reason, lineno)
            self.suppressions.append(supp)
            if not reason:
                self.pragma_errors.append(Finding(
                    BAD_SUPPRESSION, self.relpath, lineno, 0,
                    f"suppression of {', '.join(rules)} has no reason "
                    "text — write '# jaxlint: disable=<rule> -- <why>' "
                    "(the reason is the review record)",
                    self.line_text(lineno)))
            for r in rules:
                if r in META_RULES:
                    self.pragma_errors.append(Finding(
                        BAD_SUPPRESSION, self.relpath, lineno, 0,
                        f"rule {r!r} polices the escape hatches and can "
                        "never be suppressed", self.line_text(lineno)))
            if is_comment_only:
                pending.append(supp)          # applies to the next code line
            else:
                self._supp_by_line.setdefault(lineno, []).append(supp)

    def suppression_for(self, rule: str, line: int) -> \
            Optional[_Suppression]:
        for s in self._supp_by_line.get(line, ()):
            if rule in s.rules:
                return s
        return None

    def check_unknown_rules(self, known: Sequence[str]) -> List[Finding]:
        """Pragmas naming rules this run doesn't know — a typo'd id is a
        suppression that silently protects nothing."""
        out = []
        known_set = set(known) | set(META_RULES)
        for s in self.suppressions:
            for r in s.rules:
                if r not in known_set and r not in META_RULES:
                    out.append(Finding(
                        BAD_SUPPRESSION, self.relpath, s.line, 0,
                        f"suppression names unknown rule {r!r} "
                        f"(known: {', '.join(sorted(known_set))})",
                        self.line_text(s.line)))
        return out


class Rule:
    """One analyzer.  ``visit`` runs once per file against the shared
    tree; ``finalize`` runs after every file for cross-file properties
    (lock-order cycles, duplicate metric registrations).  Rules are
    instantiated fresh per run — they may keep cross-file state."""

    id = "rule"
    summary = ""

    def visit(self, src: SourceFile, report) -> None:  # pragma: no cover
        raise NotImplementedError

    def finalize(self, report) -> None:
        pass


_RULE_FACTORIES: Dict[str, type] = {}


def register_rule(cls: type) -> type:
    """Class decorator adding a rule to the global registry."""
    _RULE_FACTORIES[cls.id] = cls
    return cls


def all_rule_ids() -> List[str]:
    """Every id a finding can carry: primary rule ids plus the sibling
    ids multi-check rules emit under (e.g. the telemetry rule's
    telemetry-help)."""
    _ensure_builtin_rules()
    ids = set(_RULE_FACTORIES)
    for cls in _RULE_FACTORIES.values():
        ids.update(getattr(cls, "sibling_ids", ()))
    return sorted(ids)


def make_rules(only: Optional[Sequence[str]] = None) -> List[Rule]:
    """Instantiate rules.  ``only`` may name primary OR sibling ids; a
    sibling id pulls in its emitting rule (finding filtering to exactly
    the requested ids happens in the Linter)."""
    _ensure_builtin_rules()
    if only is None:
        return [cls() for _i, cls in sorted(_RULE_FACTORIES.items())]
    by_any_id: Dict[str, type] = dict(_RULE_FACTORIES)
    for cls in _RULE_FACTORIES.values():
        for sid in getattr(cls, "sibling_ids", ()):
            by_any_id.setdefault(sid, cls)
    unknown = [r for r in only if r not in by_any_id]
    if unknown:
        raise ValueError(
            f"unknown rule id(s) {unknown}; known: {all_rule_ids()}")
    chosen, seen = [], set()
    for r in only:
        cls = by_any_id[r]
        if cls.id not in seen:
            seen.add(cls.id)
            chosen.append(cls())
    return chosen


def _ensure_builtin_rules() -> None:
    # import side effect registers the built-in rule set exactly once
    from tools.jaxlint import (rules_dataflow, rules_hostsync,  # noqa: F401
                               rules_locks, rules_retrace,
                               rules_telemetry, rules_threads)


# -- baseline -------------------------------------------------------------

def load_baseline(path: Path) -> Counter:
    """Multiset of grandfathered finding keys.  A missing file is an
    empty baseline, a torn one is a hard error (silently linting without
    the baseline would fail CI on every grandfathered finding)."""
    if not path.exists():
        return Counter()
    data = json.loads(path.read_text(encoding="utf-8"))
    out: Counter = Counter()
    for e in data.get("findings", []):
        out[(e["rule"], e["path"], e.get("context", ""))] += 1
    return out


def save_baseline(path: Path, findings: Sequence[Finding],
                  extra_keys: Sequence[Tuple[str, str, str]] = ()) -> None:
    """Write findings (+ preserved out-of-scope ``extra_keys`` from a
    previous baseline — a path/rule-filtered update must not delete
    entries it never re-checked)."""
    entries = sorted(
        ([{"rule": f.rule, "path": f.path, "context": f.context}
          for f in findings] +
         [{"rule": r, "path": p, "context": c}
          for (r, p, c) in extra_keys]),
        key=lambda e: (e["path"], e["rule"], e["context"]))
    payload = {
        "_comment": [
            "jaxlint baseline: grandfathered findings, keyed by",
            "(rule, path, source-line text) so line drift above a",
            "finding does not resurface it.  Regenerate with",
            "`python -m tools.jaxlint --baseline-update` after fixing",
            "or annotating findings — never hand-add entries to silence",
            "new code (new code gets fixed or a reasoned suppression).",
        ],
        "version": 1,
        "findings": entries,
    }
    path.write_text(json.dumps(payload, indent=1) + "\n", encoding="utf-8")


class RunResult:
    def __init__(self):
        self.findings: List[Finding] = []       # active (fail the run)
        self.suppressed: List[Finding] = []
        self.baselined: List[Finding] = []
        self.stale_baseline: List[Tuple[str, str, str]] = []
        #: baseline entries whose code is GONE — file deleted, or the
        #: recorded line text no longer present anywhere in the file.
        #: Warnings by default, errors under --baseline-strict.
        self.dead_baseline: List[Tuple[Tuple[str, str, str], str]] = []
        self.files_scanned = 0
        self.scanned_relpaths: List[str] = []
        self.rules_run: List[str] = []
        self.active_ids: set = set()
        self.stats: Dict[str, object] = {}      # rule-contributed counters
        #: wall-clock decomposition: {"parse_s", "per_rule_s", "total_s"}
        self.timings: Dict[str, object] = {}

    @property
    def exit_code(self) -> int:
        return 1 if self.findings else 0

    def all_findings(self) -> List[Finding]:
        return self.findings + self.suppressed + self.baselined


class Linter:
    """Drives one run: collect files → parse once → rules → suppression
    and baseline filtering."""

    def __init__(self, root: Path, rules: Optional[Sequence[str]] = None,
                 baseline: Optional[Counter] = None):
        self.root = Path(root)
        self.rules = make_rules(rules)
        if rules is None:
            self.active_ids = set(all_rule_ids())
        else:
            self.active_ids = set(rules)
        self.baseline = baseline if baseline is not None else Counter()

    def run(self, paths: Sequence[Path]) -> RunResult:
        t_start = time.perf_counter()
        result = RunResult()
        result.rules_run = [r.id for r in self.rules]
        result.active_ids = set(self.active_ids)
        files = self._collect(paths)
        raw: List[Finding] = []
        sources: List[SourceFile] = []
        known_ids = all_rule_ids()
        parse_s = 0.0
        rule_s: Dict[str, float] = {r.id: 0.0 for r in self.rules}
        for path in files:
            t0 = time.perf_counter()
            src = SourceFile(path, self.root)
            parse_s += time.perf_counter() - t0
            sources.append(src)
            result.files_scanned += 1
            result.scanned_relpaths.append(src.relpath)
            raw.extend(src.pragma_errors)
            raw.extend(src.check_unknown_rules(known_ids))
            if src.parse_error is not None:
                e = src.parse_error
                raw.append(Finding(
                    PARSE_ERROR, src.relpath, e.lineno or 1, e.offset or 0,
                    f"syntax error: {e.msg}", src.line_text(e.lineno or 1)))
                continue
            for rule in self.rules:
                t0 = time.perf_counter()
                rule.visit(src, raw.append)
                rule_s[rule.id] += time.perf_counter() - t0
        for rule in self.rules:
            t0 = time.perf_counter()
            rule.finalize(raw.append)
            rule_s[rule.id] += time.perf_counter() - t0
            stats = getattr(rule, "collect_stats", None)
            if stats is not None:
                result.stats.update(stats())
        self._filter(raw, sources, result)
        self._check_dead_baseline(sources, result)
        result.timings = {
            "parse_s": round(parse_s, 4),
            "per_rule_s": {k: round(v, 4)
                           for k, v in sorted(rule_s.items())},
            "total_s": round(time.perf_counter() - t_start, 4),
        }
        return result

    def _check_dead_baseline(self, sources: List[SourceFile],
                             result: RunResult) -> None:
        """Baseline hygiene: an entry whose file is gone, or whose
        recorded line text no longer appears anywhere in the file, is
        grandfathering code that no longer exists.  Checked against the
        WHOLE baseline (not just this run's scope) so a path-filtered
        run still surfaces rot."""
        by_rel = {s.relpath: s for s in sources}
        line_cache: Dict[str, Optional[set]] = {}
        for key in sorted(set(self.baseline)):
            rule, relpath, context = key
            stripped = line_cache.get(relpath)
            if stripped is None and relpath not in line_cache:
                src = by_rel.get(relpath)
                if src is not None:
                    stripped = {ln.strip() for ln in src.lines}
                else:
                    p = self.root / relpath
                    if p.is_file():
                        try:
                            stripped = {
                                ln.strip() for ln in
                                p.read_text(encoding="utf-8").splitlines()}
                        except OSError:
                            stripped = None
                    else:
                        stripped = None
                line_cache[relpath] = stripped
            if stripped is None:
                result.dead_baseline.append((key, "file deleted"))
            elif context and context not in stripped:
                result.dead_baseline.append(
                    (key, "line text no longer present in the file"))

    def _collect(self, paths: Sequence[Path]) -> List[Path]:
        out: List[Path] = []
        for p in paths:
            p = Path(p)
            if p.is_dir():
                out.extend(sorted(p.rglob("*.py")))
            elif p.suffix == ".py":
                out.append(p)
        # de-dup while keeping order (overlapping path filters)
        seen, uniq = set(), []
        for p in out:
            rp = p.resolve()
            if rp not in seen:
                seen.add(rp)
                uniq.append(p)
        return uniq

    def _filter(self, raw: List[Finding], sources: List[SourceFile],
                result: RunResult) -> None:
        by_rel: Dict[str, SourceFile] = {s.relpath: s for s in sources}
        budget = Counter(self.baseline)
        seen = set()        # rules may re-visit shared subtrees; dedupe
        for f in sorted(raw, key=lambda f: (f.path, f.line, f.rule)):
            ident = (f.rule, f.path, f.line, f.col, f.message)
            if ident in seen:
                continue
            seen.add(ident)
            if f.rule not in self.active_ids and f.rule not in META_RULES:
                continue        # emitted by a multi-id rule, not requested
            if not f.context:
                src = by_rel.get(f.path)
                if src is not None:
                    f.context = src.line_text(f.line)
            if f.rule in META_RULES:
                result.findings.append(f)     # never silenceable
                continue
            src = by_rel.get(f.path)
            supp = src.suppression_for(f.rule, f.line) if src else None
            if supp is not None:
                supp.used = True
                f.suppressed = True
                result.suppressed.append(f)
                continue
            if budget[f.key()] > 0:
                budget[f.key()] -= 1
                f.baselined = True
                result.baselined.append(f)
                continue
            result.findings.append(f)
        # only entries THIS run could have matched count as stale: a
        # path-filtered or rule-filtered run must not call out-of-scope
        # grandfathered entries stale (and must never prune them)
        scanned = set(s.relpath for s in sources)
        result.stale_baseline = sorted(
            k for k, n in budget.items()
            if n > 0 and k[1] in scanned and k[0] in self.active_ids
            for _ in range(n))


# -- reporters ------------------------------------------------------------

def render_text(result: RunResult, verbose: bool = False,
                stats: bool = False) -> str:
    lines = []
    for f in result.findings:
        lines.append(f"{f.location()}: {f.rule}: {f.message}")
    for key in result.stale_baseline:
        lines.append(
            "baseline: stale entry "
            f"{key[0]} @ {key[1]} ({key[2]!r}) no longer matches any "
            "finding — run --baseline-update to prune")
    for key, why in result.dead_baseline:
        lines.append(
            "baseline: dead entry "
            f"{key[0]} @ {key[1]} ({key[2]!r}): {why} — run "
            "--baseline-update to prune (errors under --baseline-strict)")
    n_act = len(result.findings)
    lines.append(
        f"jaxlint: {'FAIL' if n_act else 'OK'} "
        f"({result.files_scanned} files, {len(result.rules_run)} rules, "
        f"{n_act} findings, {len(result.suppressed)} suppressed, "
        f"{len(result.baselined)} baselined)")
    if stats and result.timings:
        lines.append(f"stats: parse {result.timings['parse_s']:.3f}s")
        per_rule = result.timings.get("per_rule_s", {})
        for rid, secs in sorted(per_rule.items(),
                                key=lambda kv: -kv[1]):
            lines.append(f"stats: rule {rid} {secs:.3f}s")
        lines.append(
            f"stats: total {result.timings['total_s']:.3f}s "
            f"({result.files_scanned} files)")
    if verbose:
        for f in result.suppressed:
            lines.append(f"  suppressed {f.location()}: {f.rule}")
        for f in result.baselined:
            lines.append(f"  baselined  {f.location()}: {f.rule}")
    return "\n".join(lines)


def render_json(result: RunResult) -> dict:
    return {
        "version": 1,
        "files_scanned": result.files_scanned,
        "rules": result.rules_run,
        "findings": [f.to_dict() for f in result.findings],
        "suppressed": [f.to_dict() for f in result.suppressed],
        "baselined": [f.to_dict() for f in result.baselined],
        "stale_baseline": [list(k) for k in result.stale_baseline],
        "dead_baseline": [[list(k), why]
                          for k, why in result.dead_baseline],
        "timings": result.timings,
        "exit_code": result.exit_code,
    }


# -- shared AST helpers (used by several rule modules) --------------------

def call_name(node: ast.Call) -> str:
    """Dotted name of a call's func when statically printable ('' when
    not): ``jax.jit`` -> 'jax.jit', ``jit`` -> 'jit'."""
    return dotted(node.func)


def dotted(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def iter_functions(tree: ast.Module):
    """Yield (class_name_or_None, funcdef) for every function in the
    module, including methods and nested defs."""
    stack: List[Tuple[Optional[str], ast.AST]] = [(None, tree)]
    while stack:
        cls, node = stack.pop()
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                stack.append((child.name, child))
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield cls, child
                stack.append((cls, child))


def walk_shallow(node: ast.AST):
    """``ast.walk`` that does not descend into nested function/class
    definitions — "the statements of THIS scope" for rules where a
    nested def is its own separate scope (it runs on its own schedule,
    e.g. a worker-thread body created under a lock does not execute
    under that lock)."""
    from collections import deque
    todo = deque(ast.iter_child_nodes(node))
    while todo:
        child = todo.popleft()
        yield child
        if not isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
            todo.extend(ast.iter_child_nodes(child))
