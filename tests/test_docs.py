"""The documents name files that exist, and nothing that is kept names a
benchmark script or artifact of the rounds before the chip (PR 55: the one
benchmark is `BENCHMARK.json` and `bench/`, the numbers are the ledger's)."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = ("", "ray_tpu", "bench", "tests", "tools")
FILE_TOKEN = re.compile(r"^[\w./-]+\.(?:py|jsonl?|md|sh)$")
# Named by a document and in no checkout: written when the program runs.
ALLOWED = {".rtlint_cache.json"}
GONE = re.compile(r"bench_[a-z_]+\.py|BENCH_[A-Z_]+\.json|MULTICHIP_r0"
                  r"|PROGRESS\.jsonl|check_claims")


def files_under(top):
    """Paths below `top`, repo-relative; of the root, its own files alone."""
    for here, dirs, names in os.walk(os.path.join(REPO, top)):
        if not top:
            dirs.clear()
        for name in names:
            yield os.path.relpath(os.path.join(here, name), REPO)


@pytest.mark.parametrize("doc", ["README.md", "MIGRATION.md", "COMPONENTS.md"])
def test_every_file_a_document_names_exists(doc):
    known = {p for top in ROOTS for p in files_under(top)}
    basenames = {os.path.basename(p) for p in known}
    with open(os.path.join(REPO, doc)) as f:
        spans = re.findall(r"`([^`\n]+)`", f.read())
    tokens = {w.split("::")[0] for span in spans for w in span.split()}
    named = {t for t in tokens if FILE_TOKEN.match(t)} - ALLOWED
    assert named, f"{doc} names no file: the pattern has rotted"
    missing = sorted(
        t for t in named
        if (t not in basenames if "/" not in t else
            not any(os.path.join(top, t) in known for top in ROOTS)))
    assert not missing, f"{doc} names files that do not exist: {missing}"


def test_nothing_kept_names_a_pre_chip_benchmark():
    hits = []
    paths = ["Makefile"] + [p for top in ("ray_tpu", "tools", "tests", "examples")
                            for p in files_under(top)]
    for path in paths:
        if os.path.join(REPO, path) == os.path.abspath(__file__):
            continue
        try:
            with open(os.path.join(REPO, path), encoding="utf-8") as f:
                text = f.read()
        except UnicodeDecodeError:  # a built library
            continue
        hits += [f"{path}: {m}" for m in sorted(set(GONE.findall(text)))]
    assert not hits, hits
