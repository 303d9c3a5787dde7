"""rtlint core: file context, suppression handling, baseline, runner.

v2 is a two-pass, project-aware analyzer. Pass 1 parses every target
file once and reduces it to a plain-dict summary (tools/rtlint/
project.py); the summaries join into a ``ProjectModel`` — symbol table,
import/re-export resolution, call graph, and the context closures
(traced / async / actor-reachable / control-plane-reachable) the
interprocedural rules consume. Pass 2 runs the rules per file with the
model attached to the ``FileContext``.

Robustness contract: the analyzer never aborts on bad input. A file
that fails to parse, a summarizer crash on exotic code, or a rule
raising mid-walk all degrade to a single RT000 note for that file/rule
and the run continues.

Performance: ``analyze_paths(jobs=N)`` fans pass 1 and pass 2 out over
a process pool, and a content-hash cache (default
``<root>/.rtlint_cache.json``) keyed on (file sha, project digest, rule
signature) makes warm re-runs skip both parsing and rule execution.

Baseline fingerprints are *line-independent* — ``rule|path|scope|token``
— so unrelated edits above a baselined site do not churn the file. Two
identical violations in one scope share a fingerprint; the baseline
stores a count per fingerprint and only a count *increase* is reported.
"""

from __future__ import annotations

import ast
import glob
import hashlib
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from tools.rtlint.project import (ProjectModel, empty_summary,
                                  module_name_of, summarize_module)

_SUPPRESS_RE = re.compile(r"#\s*rtlint:\s*disable(?:=([A-Za-z0-9_,\s]+))?")

# Engine/summary-shape version: invalidates the whole cache on bump.
# Rule-logic edits are caught automatically by _rulepack_digest(), which
# hashes the linter's own sources into every findings-cache key — before
# that, editing a rule silently served stale findings until the *target*
# file changed.
ENGINE_VERSION = "3.0"

_RULEPACK_DIGEST: Optional[str] = None


def _rulepack_digest() -> str:
    """Content hash of the rule pack itself (every .py under
    tools/rtlint). Memoized per process."""
    global _RULEPACK_DIGEST
    if _RULEPACK_DIGEST is None:
        h = hashlib.sha256()
        pkg = os.path.dirname(os.path.abspath(__file__))
        for dirpath, dirs, files in os.walk(pkg):
            dirs[:] = sorted(d for d in dirs if d != "__pycache__")
            for fn in sorted(files):
                if fn.endswith(".py"):
                    h.update(fn.encode())
                    try:
                        with open(os.path.join(dirpath, fn), "rb") as f:
                            h.update(f.read())
                    except OSError:
                        pass
        _RULEPACK_DIGEST = h.hexdigest()[:16]
    return _RULEPACK_DIGEST

# The repo-wide default target set (relative to the lint root): the
# runtime and the tooling (rtlint lints itself).
DEFAULT_TARGETS = ("ray_tpu", "tools")


@dataclass
class Finding:
    rule: str          # "RT001"
    path: str          # repo-relative, forward slashes
    line: int
    col: int
    message: str
    scope: str = "<module>"   # enclosing function qualname
    token: str = ""           # short stable detail (call/attr name)

    @property
    def fingerprint(self) -> str:
        return f"{self.rule}|{self.path}|{self.scope}|{self.token}"

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.scope}] {self.message}")

    def to_dict(self) -> Dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message,
                "scope": self.scope, "token": self.token,
                "fingerprint": self.fingerprint}

    @classmethod
    def from_dict(cls, d: Dict) -> "Finding":
        return cls(d["rule"], d["path"], d["line"], d["col"],
                   d["message"], d.get("scope", "<module>"),
                   d.get("token", ""))


class FileContext:
    """Everything a rule needs about one parsed file."""

    def __init__(self, source: str, path: str,
                 project: Optional[ProjectModel] = None,
                 tree: Optional[ast.AST] = None):
        self.source = source
        self.path = path.replace(os.sep, "/")
        self.module = module_name_of(self.path)
        self.project = project
        self.lines = source.splitlines()
        self.tree = tree if tree is not None else ast.parse(
            source, filename=path)
        self._parents: Dict[ast.AST, ast.AST] = {}
        self._qualnames: Dict[ast.AST, str] = {}
        # DFS pre-order of every node + subtree spans, captured during
        # the same traversal that builds the parent map: rules re-walk
        # subtrees constantly, and slicing this list is ~10x cheaper
        # than spinning up nested ast.walk generators each time.
        self._order: List[ast.AST] = []
        self._span: Dict[ast.AST, Tuple[int, int]] = {}
        self._link(self.tree, None, prefix="")
        # Module aliases: which local names mean ray_tpu / jax / numpy.
        self.rt_aliases = {"ray_tpu"}
        self.jax_aliases = {"jax"}
        self.np_aliases = {"numpy"}
        self.time_aliases = {"time"}
        self.from_imports: Dict[str, str] = {}  # local name -> module
        self._collect_imports()

    # -- tree plumbing ----------------------------------------------------
    def _link(self, node: ast.AST, parent: Optional[ast.AST], prefix: str):
        if parent is not None:
            self._parents[node] = parent
        start = len(self._order)
        self._order.append(node)
        name = getattr(node, "name", None)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            prefix = f"{prefix}.{name}" if prefix else name
            self._qualnames[node] = prefix
        for child in ast.iter_child_nodes(node):
            self._link(child, node, prefix)
        self._span[node] = (start, len(self._order))

    def walk(self, node: Optional[ast.AST] = None) -> List[ast.AST]:
        """All nodes of `node`'s subtree (default: the whole file) in
        DFS pre-order. Drop-in for ast.walk when visit order does not
        matter; nodes not from this tree fall back to a real walk."""
        if node is None or node is self.tree:
            return self._order
        span = self._span.get(node)
        if span is None:
            return list(ast.walk(node))
        return self._order[span[0]:span[1]]

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return self._parents.get(node)

    def ancestors(self, node: ast.AST) -> Iterable[ast.AST]:
        cur = self._parents.get(node)
        while cur is not None:
            yield cur
            cur = self._parents.get(cur)

    def scope_of(self, node: ast.AST) -> str:
        """Qualname of the innermost enclosing function/class."""
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                return self._qualnames[anc]
        return "<module>"

    def qualname_of(self, node: ast.AST) -> str:
        """Qualname of a def/class node itself."""
        return self._qualnames.get(node, "<module>")

    def enclosing_function(self, node: ast.AST):
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return anc
        return None

    def in_loop(self, node: ast.AST, within: Optional[ast.AST] = None) -> bool:
        """Is `node` lexically inside a for/while body (not crossing a
        nested function boundary unless that function is `within`)?"""
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.For, ast.AsyncFor, ast.While)):
                return True
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)) and anc is not within:
                return False
        return False

    def under_lock(self, node: ast.AST) -> bool:
        """Is `node` inside a ``with <something lock-ish>:`` block?"""
        for anc in self.ancestors(node):
            if isinstance(anc, ast.With):
                for item in anc.items:
                    if _mentions_lock(item.context_expr):
                        return True
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
        return False

    # -- imports ----------------------------------------------------------
    def _collect_imports(self):
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    local = a.asname or a.name.split(".")[0]
                    if a.name.split(".")[0] == "ray_tpu":
                        self.rt_aliases.add(local)
                    elif a.name == "jax" or a.name.startswith("jax."):
                        self.jax_aliases.add(local)
                    elif a.name == "numpy":
                        self.np_aliases.add(local)
                    elif a.name == "time":
                        self.time_aliases.add(local)
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.from_imports[a.asname or a.name] = node.module

    def is_module_attr(self, func: ast.AST, aliases: set, attr: str) -> bool:
        """Match ``<alias>.<attr>`` (e.g. rt.get, jax.jit)."""
        return (isinstance(func, ast.Attribute) and func.attr == attr
                and isinstance(func.value, ast.Name)
                and func.value.id in aliases)


def _mentions_lock(expr: ast.AST) -> bool:
    # A Condition ("cond") wraps a lock; `with self._cond:` acquires it.
    for node in ast.walk(expr):
        name = None
        if isinstance(node, ast.Attribute):
            name = node.attr
        elif isinstance(node, ast.Name):
            name = node.id
        if name and any(h in name.lower()
                        for h in ("lock", "mutex", "cond")):
            return True
    return False


# -- suppressions ---------------------------------------------------------
def _suppressed_lines(ctx: FileContext) -> Dict[int, Optional[set]]:
    """line -> set of disabled rule ids (None = all rules).

    A ``# rtlint: disable`` comment on a ``def``/``class`` (or decorator)
    line extends over the whole definition body.
    """
    per_line: Dict[int, Optional[set]] = {}
    marked: Dict[int, Optional[set]] = {}
    for i, text in enumerate(ctx.lines, start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        rules = None
        if m.group(1):
            rules = {r.strip().upper() for r in m.group(1).split(",")
                     if r.strip()}
        marked[i] = rules
        per_line[i] = rules
    if not marked:
        return per_line

    def merge(line: int, rules: Optional[set]):
        cur = per_line.get(line, set())
        if cur is None or rules is None:
            per_line[line] = None
        else:
            per_line[line] = cur | rules

    for node in ctx.walk():
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
            continue
        head_lines = {node.lineno}
        head_lines.update(d.lineno for d in node.decorator_list)
        for hl in head_lines:
            if hl in marked:
                for line in range(node.lineno, (node.end_lineno or
                                                node.lineno) + 1):
                    merge(line, marked[hl])
    return per_line


def _is_suppressed(finding: Finding,
                   per_line: Dict[int, Optional[set]]) -> bool:
    rules = per_line.get(finding.line, ...)
    if rules is ...:
        return False
    return rules is None or finding.rule in rules


# -- baseline -------------------------------------------------------------
class Baseline:
    """Committed ledger of known findings: fingerprint -> count."""

    def __init__(self, counts: Optional[Dict[str, int]] = None):
        self.counts: Dict[str, int] = dict(counts or {})

    @classmethod
    def load(cls, path: str) -> "Baseline":
        if not os.path.exists(path):
            return cls()
        with open(path) as f:
            data = json.load(f)
        return cls(data.get("findings", {}))

    def save(self, path: str):
        payload = {
            "comment": ("rtlint baseline: known pre-existing findings "
                        "(fingerprint -> count). Regenerate with "
                        "`python -m tools.rtlint --write-baseline` "
                        "AFTER confirming every new entry is deliberate "
                        "debt, not a new bug."),
            "findings": dict(sorted(self.counts.items())),
        }
        with open(path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=False)
            f.write("\n")

    @classmethod
    def from_findings(cls, findings: Sequence[Finding]) -> "Baseline":
        counts: Dict[str, int] = {}
        for fd in findings:
            counts[fd.fingerprint] = counts.get(fd.fingerprint, 0) + 1
        return cls(counts)

    def new_findings(self, findings: Sequence[Finding]) -> List[Finding]:
        """Findings beyond the baselined count per fingerprint (stable
        order: a fingerprint's first N occurrences are absorbed)."""
        seen: Dict[str, int] = {}
        out = []
        for fd in findings:
            seen[fd.fingerprint] = seen.get(fd.fingerprint, 0) + 1
            if seen[fd.fingerprint] > self.counts.get(fd.fingerprint, 0):
                out.append(fd)
        return out

    def stale_entries(self, findings: Sequence[Finding]) -> List[str]:
        """Baselined fingerprints no longer present at all (debt paid —
        candidates for a baseline refresh)."""
        live = {f.fingerprint for f in findings}
        return sorted(k for k in self.counts if k not in live)


# -- per-file lint (pass 2) -----------------------------------------------
def _check_file(ctx: FileContext, rules: Sequence,
                ) -> Tuple[List[Finding], Dict[str, int]]:
    """Run every rule over one parsed file. A rule that raises degrades
    to an RT000 note instead of aborting the run. Returns (unsuppressed
    findings, suppressed-count-per-rule)."""
    per_line = _suppressed_lines(ctx)
    findings: List[Finding] = []
    suppressed: Dict[str, int] = {}
    for rule in rules:
        try:
            rule_findings = list(rule.check(ctx))
        except Exception as e:  # analyzer must degrade, never abort
            findings.append(Finding(
                "RT000", ctx.path, 0, 0,
                f"rule {rule.id} crashed on this file "
                f"({type(e).__name__}: {e}) — findings for it are "
                f"incomplete here", token=f"crash-{rule.id}"))
            continue
        for fd in rule_findings:
            if _is_suppressed(fd, per_line):
                suppressed[fd.rule] = suppressed.get(fd.rule, 0) + 1
            else:
                findings.append(fd)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings, suppressed


def lint_source(source: str, path: str,
                rules: Optional[Sequence] = None,
                project: Optional[ProjectModel] = None) -> List[Finding]:
    """Lint one in-memory file; returns unsuppressed findings sorted by
    position. With no `project`, a single-file model is built so the
    interprocedural rules still see intra-file flows. Syntax errors
    yield a single RT000 finding instead of crashing the whole run."""
    from tools.rtlint.rules import ALL_RULES

    norm = path.replace(os.sep, "/")
    try:
        ctx = FileContext(source, path)
    except SyntaxError as e:
        return [Finding("RT000", norm, e.lineno or 0, e.offset or 0,
                        f"syntax error: {e.msg}", token="syntax")]
    if project is None:
        project = ProjectModel([_safe_summary(ctx.tree, norm)])
    ctx.project = project
    findings, _ = _check_file(ctx, rules if rules is not None
                              else ALL_RULES)
    return findings


def _safe_summary(tree: ast.AST, path: str) -> Dict:
    try:
        return summarize_module(tree, path)
    except Exception:
        return empty_summary(path)


def iter_py_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        matches = glob.glob(p) if any(c in p for c in "*?[") else [p]
        for m in sorted(matches):
            if os.path.isfile(m):
                yield m
            else:
                for root, dirs, files in os.walk(m):
                    dirs[:] = sorted(d for d in dirs
                                     if d not in {"__pycache__", ".git"})
                    for fn in sorted(files):
                        if fn.endswith(".py"):
                            yield os.path.join(root, fn)


# -- cache ----------------------------------------------------------------
class _Cache:
    """Content-hash cache: summaries keyed by file sha, findings keyed
    by (file sha, project digest, rule signature)."""

    def __init__(self, path: Optional[str]):
        self.path = path
        # The rule-pack digest is part of the cache version: editing any
        # linter source (rules OR summarizer) invalidates everything.
        # Summaries are keyed only by target-file sha, so without this a
        # summarizer change would silently serve stale pass-1 output.
        version = f"{ENGINE_VERSION}|{_rulepack_digest()}"
        self.data = {"version": version, "summaries": {},
                     "findings": {}}
        self.dirty = False
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    loaded = json.load(f)
                if loaded.get("version") == version:
                    self.data = loaded
            except Exception:
                pass  # corrupt cache == cold cache

    def summary(self, rel: str, sha: str) -> Optional[Dict]:
        ent = self.data["summaries"].get(rel)
        return ent["summary"] if ent and ent["sha"] == sha else None

    def put_summary(self, rel: str, sha: str, summary: Dict):
        self.data["summaries"][rel] = {"sha": sha, "summary": summary}
        self.dirty = True

    def findings(self, rel: str, key: str) -> Optional[Tuple[List, Dict]]:
        ent = self.data["findings"].get(rel)
        if ent and ent["key"] == key:
            return ([Finding.from_dict(d) for d in ent["findings"]],
                    dict(ent["suppressed"]))
        return None

    def put_findings(self, rel: str, key: str,
                     findings: List[Finding], suppressed: Dict):
        self.data["findings"][rel] = {
            "key": key, "findings": [f.to_dict() for f in findings],
            "suppressed": suppressed}
        self.dirty = True

    def save(self):
        if not (self.path and self.dirty):
            return
        try:
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self.data, f, separators=(",", ":"))
            os.replace(tmp, self.path)
        except Exception:
            pass  # cache is best-effort


def _sha(source: str) -> str:
    return hashlib.sha256(source.encode("utf-8", "replace")).hexdigest()


# -- parallel workers (module-level for picklability) ---------------------
_W: Dict = {}


def _pool_init(root: str, project: Optional[ProjectModel],
               rule_ids: Optional[List[str]]):
    from tools.rtlint.rules import ALL_RULES, rule_by_id
    _W["root"] = root
    _W["project"] = project
    _W["rules"] = (ALL_RULES if rule_ids is None
                   else [rule_by_id(r) for r in rule_ids])


def _p1_worker(rel: str) -> Tuple[str, str, Dict, Optional[Dict]]:
    """Parse + summarize one file. Returns (rel, sha, summary,
    rt000-note-or-None)."""
    fp = os.path.join(_W["root"], rel)
    with open(fp, encoding="utf-8") as f:
        source = f.read()
    sha = _sha(source)
    try:
        tree = ast.parse(source, filename=rel)
    except SyntaxError as e:
        note = Finding("RT000", rel.replace(os.sep, "/"), e.lineno or 0,
                       e.offset or 0, f"syntax error: {e.msg}",
                       token="syntax").to_dict()
        return rel, sha, empty_summary(rel.replace(os.sep, "/")), note
    return rel, sha, _safe_summary(tree, rel.replace(os.sep, "/")), None


def _p2_worker(rel: str) -> Tuple[str, List[Dict], Dict[str, int]]:
    fp = os.path.join(_W["root"], rel)
    with open(fp, encoding="utf-8") as f:
        source = f.read()
    try:
        ctx = FileContext(source, rel, project=_W["project"])
    except SyntaxError:
        return rel, [], {}   # already RT000'd in pass 1
    findings, suppressed = _check_file(ctx, _W["rules"])
    return rel, [f.to_dict() for f in findings], suppressed


# -- runner ---------------------------------------------------------------
@dataclass
class AnalysisResult:
    findings: List[Finding] = field(default_factory=list)
    suppressed: Dict[str, int] = field(default_factory=dict)
    files: int = 0
    project: Optional[ProjectModel] = None


def analyze_paths(paths: Sequence[str],
                  rules: Optional[Sequence] = None,
                  root: Optional[str] = None,
                  jobs: int = 1,
                  cache_path: Optional[str] = None,
                  only_files: Optional[Sequence[str]] = None,
                  ) -> AnalysisResult:
    """Two-pass analysis over every .py file under `paths`.

    `only_files` (repo-relative) restricts *pass 2* to those files —
    the project model is still built over the full target set, so
    --changed keeps interprocedural context. Finding paths are relative
    to `root` (default: cwd) so fingerprints are machine-independent.
    """
    from tools.rtlint.rules import ALL_RULES

    root = os.path.abspath(root or os.getcwd())
    # More workers than cores only adds fork/pickle overhead — on a
    # 1-core box `--jobs 4` would run *slower* than serial.
    jobs = min(jobs, os.cpu_count() or 1)
    rules = list(rules if rules is not None else ALL_RULES)
    rule_ids = [r.id for r in rules]
    cache = _Cache(cache_path)

    rels: List[str] = []
    for fp in iter_py_files([p if os.path.isabs(p)
                             else os.path.join(root, p) for p in paths]):
        rel = os.path.relpath(os.path.abspath(fp), root)
        if rel not in rels:
            rels.append(rel)

    # ---- pass 1: summaries ----------------------------------------------
    sources: Dict[str, str] = {}
    shas: Dict[str, str] = {}
    summaries: Dict[str, Dict] = {}
    rt000: List[Finding] = []
    trees: Dict[str, ast.AST] = {}
    misses: List[str] = []
    for rel in rels:
        with open(os.path.join(root, rel), encoding="utf-8") as f:
            sources[rel] = f.read()
        shas[rel] = _sha(sources[rel])
        hit = cache.summary(rel, shas[rel])
        if hit is not None:
            summaries[rel] = hit
        else:
            misses.append(rel)

    if jobs > 1 and len(misses) > 1:
        import multiprocessing as mp
        with mp.Pool(jobs, initializer=_pool_init,
                     initargs=(root, None, rule_ids)) as pool:
            for rel, sha, summary, note in pool.map(_p1_worker, misses):
                summaries[rel] = summary
                cache.put_summary(rel, sha, summary)
                if note:
                    rt000.append(Finding.from_dict(note))
    else:
        for rel in misses:
            norm = rel.replace(os.sep, "/")
            try:
                tree = ast.parse(sources[rel], filename=rel)
                trees[rel] = tree
                summaries[rel] = _safe_summary(tree, norm)
            except SyntaxError as e:
                rt000.append(Finding("RT000", norm, e.lineno or 0,
                                     e.offset or 0,
                                     f"syntax error: {e.msg}",
                                     token="syntax"))
                summaries[rel] = empty_summary(norm)
            cache.put_summary(rel, shas[rel], summaries[rel])

    try:
        project = ProjectModel([summaries[rel] for rel in rels])
        digest = hashlib.sha256(
            project.digest_src().encode()).hexdigest()[:16]
    except Exception as e:   # model build must never kill the run
        rt000.append(Finding(
            "RT000", "<project>", 0, 0,
            f"project model build failed ({type(e).__name__}: {e}) — "
            f"falling back to per-file analysis", token="model"))
        project = None
        digest = "no-model"

    # ---- pass 2: rules --------------------------------------------------
    lint_rels = (rels if only_files is None
                 else [r for r in rels
                       if r.replace(os.sep, "/") in set(only_files)])
    result = AnalysisResult(project=project, files=len(lint_rels))
    result.findings.extend(f for f in rt000
                           if only_files is None
                           or f.path in set(only_files)
                           or f.path == "<project>")
    key = (f"{digest}|{ENGINE_VERSION}|{_rulepack_digest()}"
           f"|{','.join(rule_ids)}")
    todo: List[str] = []
    for rel in lint_rels:
        hit = cache.findings(rel, f"{shas[rel]}|{key}")
        if hit is not None:
            fs, supp = hit
            result.findings.extend(fs)
            for r, n in supp.items():
                result.suppressed[r] = result.suppressed.get(r, 0) + n
        else:
            todo.append(rel)

    def absorb(rel: str, findings: List[Finding], suppressed: Dict):
        cache.put_findings(rel, f"{shas[rel]}|{key}", findings,
                           suppressed)
        result.findings.extend(findings)
        for r, n in suppressed.items():
            result.suppressed[r] = result.suppressed.get(r, 0) + n

    if jobs > 1 and len(todo) > 1 and project is not None:
        import multiprocessing as mp
        with mp.Pool(jobs, initializer=_pool_init,
                     initargs=(root, project, rule_ids)) as pool:
            for rel, fdicts, suppressed in pool.map(_p2_worker, todo):
                absorb(rel, [Finding.from_dict(d) for d in fdicts],
                       suppressed)
    else:
        for rel in todo:
            try:
                ctx = FileContext(sources[rel], rel, project=project,
                                  tree=trees.get(rel))
            except SyntaxError:
                continue  # RT000 already recorded in pass 1
            findings, suppressed = _check_file(ctx, rules)
            absorb(rel, findings, suppressed)

    cache.save()
    result.findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return result


def lint_paths(paths: Sequence[str], rules: Optional[Sequence] = None,
               root: Optional[str] = None) -> List[Finding]:
    """Lint every .py file under `paths` (back-compat wrapper around
    analyze_paths); finding paths are relative to `root` (default: cwd)
    so fingerprints are machine-independent."""
    return analyze_paths(paths, rules=rules, root=root).findings
