"""Output renderers for rtlint: text (default), json, sarif.

``json`` is the machine interface for bots; ``sarif`` (2.1.0) is what
code-review UIs ingest. Both render the same
post-baseline view the text output shows: the findings that would fail
the gate, plus run metadata. Renderers are pure — they return a string
and never exit — so the CLI owns all exit-code policy.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Sequence

from tools.rtlint.engine import Finding

SARIF_VERSION = "2.1.0"
SARIF_SCHEMA = ("https://raw.githubusercontent.com/oasis-tcs/sarif-spec/"
                "master/Schemata/sarif-schema-2.1.0.json")


def render_text(new: Sequence[Finding], *, total: int, files: int,
                rules: int, baselined_absorbed: int,
                stale: Sequence[str] = ()) -> str:
    lines = [str(f) for f in new]
    if new:
        by_rule: Dict[str, int] = {}
        for f in new:
            by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
        summary = ", ".join(f"{r}:{n}" for r, n in sorted(by_rule.items()))
        lines.append(f"rtlint: {len(new)} new finding(s) [{summary}] "
                     f"({baselined_absorbed} baselined/suppressed "
                     f"absorbed)")
    else:
        lines.append(f"rtlint: clean ({baselined_absorbed} baselined "
                     f"finding(s), {rules} rules, {files} files)")
    if stale:
        lines.append(f"note: {len(stale)} baselined finding(s) no longer "
                     f"exist — debt paid; refresh with --write-baseline")
    return "\n".join(lines)


def render_json(new: Sequence[Finding], *, total: int, files: int,
                rules: int, baselined_absorbed: int,
                suppressed: Optional[Dict[str, int]] = None,
                stale: Sequence[str] = ()) -> str:
    payload = {
        "tool": "rtlint",
        "files": files,
        "rules": rules,
        "total_findings": total,
        "baselined_absorbed": baselined_absorbed,
        "suppressed": dict(sorted((suppressed or {}).items())),
        "stale_baseline_entries": list(stale),
        "new_findings": [f.to_dict() for f in new],
    }
    return json.dumps(payload, indent=1)


def render_sarif(new: Sequence[Finding], *, rule_docs: Dict[str, str],
                 **_meta) -> str:
    """SARIF 2.1.0 with one rule descriptor per rule that fired.

    RT000 (analyzer degradation notes) are emitted at level "note";
    everything else is "warning" — rtlint findings gate on the baseline,
    not on severity.
    """
    fired = sorted({f.rule for f in new})
    rules = [{
        "id": rid,
        "shortDescription": {
            "text": (rule_docs.get(rid) or rid).splitlines()[0]},
    } for rid in fired]
    index = {rid: i for i, rid in enumerate(fired)}
    results = [{
        "ruleId": f.rule,
        "ruleIndex": index[f.rule],
        "level": "note" if f.rule == "RT000" else "warning",
        "message": {"text": f.message},
        "locations": [{
            "physicalLocation": {
                "artifactLocation": {"uri": f.path,
                                     "uriBaseId": "SRCROOT"},
                "region": {"startLine": max(f.line, 1),
                           "startColumn": max(f.col, 0) + 1},
            },
            "logicalLocations": [{"fullyQualifiedName": f.scope}],
        }],
        "partialFingerprints": {"rtlint/v1": f.fingerprint},
    } for f in new]
    doc = {
        "$schema": SARIF_SCHEMA,
        "version": SARIF_VERSION,
        "runs": [{
            "tool": {"driver": {
                "name": "rtlint",
                "informationUri":
                    "tools/rtlint/RULES.md",
                "rules": rules,
            }},
            "results": results,
        }],
    }
    return json.dumps(doc, indent=1)
