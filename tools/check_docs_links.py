#!/usr/bin/env python
"""Docs link check: every relative markdown link must resolve.

Scans README.md and docs/**/*.md for [text](target) links, skips absolute
URLs and anchors, and fails if a relative target does not exist on disk.
Run from the repo root (CI does; tests/test_docs_links.py runs the same
check per file):

    python tools/check_docs_links.py
"""
import os
import re
import sys

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
SKIP_PREFIXES = ("http://", "https://", "mailto:", "#")


def md_files(root):
    yield os.path.join(root, "README.md")
    docs = os.path.join(root, "docs")
    if os.path.isdir(docs):
        for dirpath, _dirs, files in os.walk(docs):
            for f in files:
                if f.endswith(".md"):
                    yield os.path.join(dirpath, f)


def check_file(path):
    """(where, target) for each relative link in ``path`` that resolves
    to nothing on disk."""
    if not os.path.exists(path):
        return [(path, "<file missing>")]
    bad = []
    base = os.path.dirname(path)
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            for target in LINK_RE.findall(line):
                if target.startswith(SKIP_PREFIXES):
                    continue
                target = target.split("#", 1)[0]
                if not target:
                    continue
                resolved = os.path.normpath(os.path.join(base, target))
                if not os.path.exists(resolved):
                    bad.append((f"{path}:{lineno}", target))
    return bad


def check(root):
    return [b for path in md_files(root) for b in check_file(path)]


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bad = check(root)
    for where, target in bad:
        print(f"BROKEN LINK {where} -> {target}")
    print(f"[docs-linkcheck] {'FAIL' if bad else 'OK'} "
          f"({len(bad)} broken link(s))")
    sys.exit(1 if bad else 0)
