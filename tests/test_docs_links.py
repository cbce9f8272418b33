"""Every relative link in README.md and docs/*.md resolves on disk (the
check of tools/check_docs_links.py, one case per file)."""
import importlib.util
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "check_docs_links", os.path.join(_ROOT, "tools", "check_docs_links.py"))
links = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(links)


@pytest.mark.parametrize(
    "path", sorted(links.md_files(_ROOT)),
    ids=lambda p: os.path.relpath(p, _ROOT))
def test_every_relative_link_resolves(path):
    assert links.check_file(path) == []
