"""The documents a newcomer reads first name no file that is not in the
checkout. PERF.md, ROADMAP.md and CHANGES.md are histories and are not
scanned."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIRS = ("tools/", "cometbft_tpu/", "benchmark/", "tests/", "spec/")
ROOT_RECORD = re.compile(r"[A-Z_]+\.(md|json|jsonl)")


def _named_paths(text):
    """Back-quoted words that are paths by their spelling: under one of
    this checkout's directories (`:line` / `::test` cut off), or a root
    record in capitals. Bare module names, example outputs and words
    with a placeholder or a call in them (`<key>`, `{a,b}`, `f(x`) are
    not."""
    for quoted in re.findall(r"`([^`\n]+)`", text):
        for word in quoted.split():
            word = word.split(":", 1)[0].rstrip(".,;)")
            if set(word) & set("<{("):
                continue
            if word.startswith(DIRS) or ROOT_RECORD.fullmatch(word):
                yield word


@pytest.mark.parametrize("doc", [
    "README.md", "COMPONENTS.md", ".claude/skills/verify/SKILL.md"])
def test_document_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        names = sorted(set(_named_paths(f.read())))
    assert names, f"{doc}: the scan found no path at all"
    missing = [n for n in names if not glob.glob(os.path.join(REPO, n))]
    assert not missing, f"{doc} names files that are not there: {missing}"
