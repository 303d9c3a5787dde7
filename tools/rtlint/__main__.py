"""rtlint CLI.

    python -m tools.rtlint                        lint the default targets
    python -m tools.rtlint ray_tpu/ tools/        lint explicit paths
    python -m tools.rtlint --no-baseline PATH     report every finding
    python -m tools.rtlint --write-baseline       regenerate the baseline
    python -m tools.rtlint --changed              git-diff-scoped pass 2
    python -m tools.rtlint --jobs 8               parallel analysis
    python -m tools.rtlint --format json|sarif    machine-readable output
    python -m tools.rtlint --sarif-out FILE       sarif artifact alongside text
    python -m tools.rtlint --fix                  apply mechanical autofixes
    python -m tools.rtlint --stats                per-rule counts
    python -m tools.rtlint --list-rules           one-line rule catalog
    python -m tools.rtlint --explain RT003        full rule rationale

With no paths, the default target set is linted: ray_tpu/ and tools/,
resolved against the repo root (the directory holding tools/rtlint/).
Exit codes: 0 clean, 1 new findings
(or stale baseline with --strict-baseline), 2 usage error.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

from tools.rtlint.engine import (Baseline, DEFAULT_TARGETS, analyze_paths)
from tools.rtlint.formats import render_json, render_sarif, render_text
from tools.rtlint.rules import ALL_RULES, rule_by_id

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baseline.json")


def _changed_files(root: str):
    """Repo-relative .py files touched vs HEAD (staged, unstaged, and
    untracked). Returns None when git itself fails — callers fall back
    to a full pass 2 rather than silently linting nothing."""
    try:
        diff = subprocess.run(
            ["git", "diff", "--name-only", "HEAD", "--", "*.py"],
            capture_output=True, text=True, cwd=root, timeout=30)
        untracked = subprocess.run(
            ["git", "ls-files", "--others", "--exclude-standard",
             "--", "*.py"],
            capture_output=True, text=True, cwd=root, timeout=30)
        if diff.returncode != 0:
            return None
    except (OSError, subprocess.SubprocessError):
        return None
    out = set()
    for blob in (diff.stdout, untracked.stdout):
        for line in blob.splitlines():
            line = line.strip()
            if line.endswith(".py"):
                out.add(line)
    return sorted(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="rtlint", add_help=True)
    ap.add_argument("paths", nargs="*",
                    help="files or directories to lint (default: "
                         + ", ".join(DEFAULT_TARGETS) + ")")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file (default: tools/rtlint/baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline; report every finding")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write all current findings to the baseline file")
    ap.add_argument("--strict-baseline", action="store_true",
                    help="also fail when baselined entries no longer exist "
                         "(debt paid off: refresh the baseline)")
    ap.add_argument("--rules", default="",
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--format", default="text",
                    choices=["text", "json", "sarif"], dest="fmt",
                    help="output format (default: text)")
    ap.add_argument("--jobs", "-j", type=int, default=1, metavar="N",
                    help="worker processes for both analysis passes")
    ap.add_argument("--changed", action="store_true",
                    help="restrict findings to files changed vs HEAD "
                         "(+ untracked); the project model still covers "
                         "the full target set")
    ap.add_argument("--stats", action="store_true",
                    help="print per-rule finding/suppression/baseline "
                         "counts instead of findings")
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the content-hash cache")
    ap.add_argument("--cache", default=None, metavar="FILE",
                    help="cache file (default: <root>/.rtlint_cache.json)")
    ap.add_argument("--root", default=None,
                    help="repo root for relative finding paths "
                         "(default: the checkout containing rtlint)")
    ap.add_argument("--fix", action="store_true",
                    help="apply mechanical autofixes (RT004 leash, "
                         "RT013 boundary tuple-freeze) in place, then "
                         "re-lint")
    ap.add_argument("--sarif-out", default=None, metavar="FILE",
                    help="also write a SARIF artifact of the new "
                         "findings to FILE (independent of --format)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--explain", metavar="RTxxx")
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in ALL_RULES:
            first = (r.__doc__ or "").strip().splitlines()[0]
            print(f"{r.id}  {r.name:24s} {first}")
        return 0
    if args.explain:
        try:
            r = rule_by_id(args.explain)
        except KeyError:
            print(f"unknown rule {args.explain!r}", file=sys.stderr)
            return 2
        print(f"{r.id} ({r.name})\n")
        print((r.__doc__ or "").strip())
        return 0

    rules = None
    if args.rules:
        try:
            rules = [rule_by_id(x) for x in args.rules.split(",") if x]
        except KeyError as e:
            print(f"unknown rule {e.args[0]!r}", file=sys.stderr)
            return 2
    if args.jobs < 1:
        print("rtlint: --jobs must be >= 1", file=sys.stderr)
        return 2

    # Explicit paths are resolved against the cwd (so `rtlint pkg/`
    # works from anywhere); the default target set is anchored at the
    # repo root regardless of cwd.
    root = os.path.abspath(args.root or REPO_ROOT)
    if args.paths:
        paths = [os.path.abspath(p) for p in args.paths]
    else:
        paths = list(DEFAULT_TARGETS)

    only_files = None
    if args.changed:
        only_files = _changed_files(root)
        if only_files is not None and not only_files:
            print("rtlint: clean (no changed .py files)")
            return 0

    cache_path = None
    if not args.no_cache:
        cache_path = args.cache or os.path.join(root, ".rtlint_cache.json")

    result = analyze_paths(paths, rules=rules, root=root, jobs=args.jobs,
                           cache_path=cache_path, only_files=only_files)
    findings = result.findings

    if args.fix:
        nfixed = _apply_fixes(findings, root)
        if nfixed:
            # Re-lint so the report (and exit code) reflects the
            # post-fix tree; the content-hash cache skips the rest.
            result = analyze_paths(paths, rules=rules, root=root,
                                   jobs=args.jobs, cache_path=cache_path,
                                   only_files=only_files)
            findings = result.findings

    if args.write_baseline:
        bl = Baseline.from_findings(findings)
        bl.save(args.baseline)
        by_rule = {}
        for f in findings:
            by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
        summary = ", ".join(f"{r}:{n}" for r, n in sorted(by_rule.items()))
        print(f"wrote {len(findings)} findings to {args.baseline} "
              f"({summary or 'clean'})")
        return 0

    baseline = (Baseline() if args.no_baseline
                else Baseline.load(args.baseline))
    new = baseline.new_findings(findings)
    stale = [] if args.no_baseline else baseline.stale_entries(findings)

    if args.stats:
        _print_stats(findings, new, result.suppressed, baseline,
                     rules or ALL_RULES)
        return 1 if new else 0

    nrules = len(ALL_RULES) if rules is None else len(rules)
    meta = dict(total=len(findings), files=result.files, rules=nrules,
                baselined_absorbed=len(findings) - len(new), stale=stale)
    if args.sarif_out:
        docs = {r.id: (r.__doc__ or "").strip() for r in ALL_RULES}
        docs["RT000"] = "analyzer degradation note"
        with open(args.sarif_out, "w", encoding="utf-8") as fh:
            fh.write(render_sarif(new, rule_docs=docs))
    if args.fmt == "json":
        print(render_json(new, suppressed=result.suppressed, **meta))
    elif args.fmt == "sarif":
        docs = {r.id: (r.__doc__ or "").strip() for r in ALL_RULES}
        docs["RT000"] = "analyzer degradation note"
        print(render_sarif(new, rule_docs=docs))
    else:
        print(render_text(new, **meta))
    if new:
        return 1
    return 1 if (stale and args.strict_baseline) else 0


def _apply_fixes(findings, root: str) -> int:
    """Rewrite files for fixable findings; returns files changed.

    Driven by the analyzer's (suppression-filtered) findings rather
    than a raw re-scan, so `# rtlint:` suppressed sites — e.g. an
    intentional fire-and-forget — are never touched.
    """
    from tools.rtlint.fix import FIXABLE_RULES, fix_source
    by_path = {}
    for f in findings:
        if f.rule in FIXABLE_RULES:
            by_path.setdefault(f.path, {}).setdefault(
                f.rule, set()).add(f.line)
    changed = 0
    for rel, rule_lines in sorted(by_path.items()):
        abspath = os.path.join(root, rel.replace("/", os.sep))
        try:
            with open(abspath, "r", encoding="utf-8") as fh:
                src = fh.read()
        except OSError as e:
            print(f"rtlint: --fix cannot read {rel}: {e}",
                  file=sys.stderr)
            continue
        out, notes = fix_source(
            src, rel,
            rt004_lines=rule_lines.get("RT004", set()),
            rt013_lines=rule_lines.get("RT013", set()))
        for note in notes:
            print(f"rtlint: fix: {note}")
        if out != src:
            with open(abspath, "w", encoding="utf-8") as fh:
                fh.write(out)
            changed += 1
    if changed:
        print(f"rtlint: --fix rewrote {changed} file(s)")
    return changed


def _print_stats(findings, new, suppressed, baseline, rules):
    by_rule = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    base_by_rule = {}
    for fp, n in baseline.counts.items():
        rid = fp.split("|", 1)[0]
        base_by_rule[rid] = base_by_rule.get(rid, 0) + n
    new_by_rule = {}
    for f in new:
        new_by_rule[f.rule] = new_by_rule.get(f.rule, 0) + 1
    ids = sorted({r.id for r in rules} | set(by_rule) | set(base_by_rule)
                 | set(suppressed))
    print(f"{'rule':8s} {'found':>6s} {'new':>6s} {'baseline':>9s} "
          f"{'suppressed':>11s}")
    for rid in ids:
        print(f"{rid:8s} {by_rule.get(rid, 0):6d} "
              f"{new_by_rule.get(rid, 0):6d} "
              f"{base_by_rule.get(rid, 0):9d} "
              f"{suppressed.get(rid, 0):11d}")
    tot = (sum(by_rule.values()), sum(new_by_rule.values()),
           sum(base_by_rule.values()), sum(suppressed.values()))
    print(f"{'total':8s} {tot[0]:6d} {tot[1]:6d} {tot[2]:9d} "
          f"{tot[3]:11d}")


if __name__ == "__main__":
    sys.exit(main())
