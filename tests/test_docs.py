"""Documentation integrity gates (a CI step, see .github/workflows/ci.yml).

Link checking: every relative ``.md``/file link in the docs tree (plus
README/REPORT) must resolve against the repo, and every backtick
``path:line`` reference in docs/ must point at a real file that is long
enough.  Docs that point nowhere rot silently; this makes a broken
pointer a red build instead.
"""
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
DOCS = REPO / "docs"

#: Markdown files whose relative links must resolve.
LINKED_PAGES = sorted(DOCS.rglob("*.md")) + [REPO / "README.md",
                                             REPO / "REPORT.md"]

_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
# `path/to/file.py:123`-style references inside backticks
_FILE_LINE = re.compile(r"`([\w./-]+\.(?:py|md|json|yml|toml)):(\d+)`")


def _relative_links(text):
    for target in _LINK.findall(text):
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        yield target.split("#", 1)[0]


@pytest.mark.parametrize("page", LINKED_PAGES,
                         ids=lambda p: str(p.relative_to(REPO)))
def test_relative_markdown_links_resolve(page):
    missing = []
    for target in _relative_links(page.read_text()):
        if not target:
            continue
        resolved = (page.parent / target).resolve()
        if not resolved.exists():
            missing.append(target)
    assert not missing, (
        f"{page.relative_to(REPO)}: dead relative link(s) {missing}")


@pytest.mark.parametrize("page", sorted(DOCS.rglob("*.md")),
                         ids=lambda p: str(p.relative_to(REPO)))
def test_file_line_references_resolve(page):
    bad = []
    for path, line in _FILE_LINE.findall(page.read_text()):
        target = REPO / path
        if not target.exists():
            bad.append(f"{path}:{line} (no such file)")
            continue
        if len(target.read_text().splitlines()) < int(line):
            bad.append(f"{path}:{line} (file is shorter)")
    assert not bad, (
        f"{page.relative_to(REPO)}: stale file:line reference(s) {bad}")
