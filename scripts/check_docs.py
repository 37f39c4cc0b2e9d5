#!/usr/bin/env python
"""Documentation-link audit: the design contract must stay citable.

    python scripts/check_docs.py

Code, tests, benchmarks and docs cite the design document as
``DESIGN §x.y`` (or ``DESIGN.md §x.y``) — that citation IS the contract
(DESIGN §4.4 and friends are load-bearing in docstrings). This script
verifies, with no third-party deps so it runs anywhere CI does:

  1. every ``DESIGN §...`` citation in ``src/``, ``tests/``,
     ``benchmarks/``, ``examples/``, ``scripts/``, ``README.md`` and
     ``docs/`` resolves to a real ``##``/``###`` heading in DESIGN.md;
  2. every bare ``§x.y`` cross-reference INSIDE DESIGN.md resolves to one
     of its own headings (bare § elsewhere may cite the *paper* — e.g.
     "the paper's §6.3" — so only DESIGN.md is held to the bare form);
  3. every ``examples/*.py`` script is referenced from README.md — an
     example nobody can discover is dead documentation;
  4. every lint rule registered in ``src/repro/analysis/`` (the
     ``Rule("name", ...)`` / ``SourceRule("name", ...)`` literals) is
     documented by name in DESIGN.md §6 — an enforced invariant nobody
     can look up is policy by surprise.

Exit 0 when everything resolves; exit 1 with a file:line listing of every
dangling citation / unreferenced example otherwise. Wired into CI between
``check_env.py`` and the test suite (.github/workflows/ci.yml).
"""

from __future__ import annotations

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# directories whose .py files carry DESIGN citations in docstrings/comments
PY_DIRS = ("src", "tests", "benchmarks", "examples", "scripts")
MD_FILES = ("README.md",)
MD_DIRS = ("docs",)

# "DESIGN §3.1", "DESIGN.md §4.4", "DESIGN  § Roofline" — the explicit form
_CITE = re.compile(r"DESIGN(?:\.md)?\s*§\s*([0-9]+(?:\.[0-9]+)*"
                   r"|Perf|Roofline)")
# bare "§3.1" (DESIGN-internal cross-references only)
_BARE = re.compile(r"§\s*([0-9]+(?:\.[0-9]+)*|Perf|Roofline)")
# "## §3 ..." / "### §3.1 ..." headings in DESIGN.md
_HEADING = re.compile(r"^#{2,3}\s+§([0-9]+(?:\.[0-9]+)*|\w+)\b")


def design_sections(design_path: str) -> set:
    sections = set()
    with open(design_path) as f:
        for line in f:
            m = _HEADING.match(line)
            if m:
                sections.add(m.group(1))
    return sections


def _iter_files():
    for d in PY_DIRS:
        for dirpath, _dirs, files in os.walk(os.path.join(ROOT, d)):
            for name in sorted(files):
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)
    for name in MD_FILES:
        path = os.path.join(ROOT, name)
        if os.path.exists(path):
            yield path
    for d in MD_DIRS:
        dpath = os.path.join(ROOT, d)
        if os.path.isdir(dpath):
            for name in sorted(os.listdir(dpath)):
                if name.endswith(".md"):
                    yield os.path.join(dpath, name)


def check_citations(sections: set):
    """-> (dangling [(relpath, lineno, citation)], total citation count).

    Scans whole-file text, not lines: docstring citations wrap —
    "DESIGN.md\\n    §3.1" is one citation, and a line-based scan would
    silently skip validating it."""
    dangling, n_cites = [], 0
    for path in _iter_files():
        rel = os.path.relpath(path, ROOT)
        with open(path, errors="replace") as f:
            text = f.read()
        for m in _CITE.finditer(text):
            n_cites += 1
            if m.group(1) not in sections:
                lineno = text.count("\n", 0, m.start()) + 1
                dangling.append((rel, lineno, f"DESIGN §{m.group(1)}"))
    # DESIGN.md's own bare cross-references
    dpath = os.path.join(ROOT, "DESIGN.md")
    with open(dpath) as f:
        for lineno, line in enumerate(f, 1):
            if _HEADING.match(line):
                continue                      # headings define, not cite
            for m in _BARE.finditer(line):
                if m.group(1) not in sections:
                    dangling.append(("DESIGN.md", lineno, f"§{m.group(1)}"))
    return dangling, n_cites


def check_examples() -> list:
    """Example scripts not referenced from README.md."""
    readme_path = os.path.join(ROOT, "README.md")
    if not os.path.exists(readme_path):
        return [("README.md", 0, "MISSING — examples cannot be referenced")]
    with open(readme_path) as f:
        readme = f.read()
    missing = []
    exdir = os.path.join(ROOT, "examples")
    for name in sorted(os.listdir(exdir)):
        if name.endswith(".py") and f"examples/{name}" not in readme:
            missing.append((f"examples/{name}", 0,
                            "not referenced from README.md"))
    return missing


# lint-rule registrations: the name is always the first (literal) argument
_RULE_DEF = re.compile(r"\b(?:Source)?Rule\(\s*\n?\s*\"([a-z0-9-]+)\"")


def _design_section_body(design_text: str, number: str) -> str:
    """Body of ``## §<number> ...`` up to the next ``## `` heading."""
    m = re.search(rf"^## §{re.escape(number)}\b.*$", design_text,
                  flags=re.M)
    if not m:
        return ""
    rest = design_text[m.end():]
    nxt = re.search(r"^## ", rest, flags=re.M)
    return rest[:nxt.start()] if nxt else rest


def check_lint_rules() -> list:
    """Registered lint rules (``Rule("name", ...)`` literals in
    src/repro/analysis/) missing from DESIGN.md §6."""
    adir = os.path.join(ROOT, "src", "repro", "analysis")
    if not os.path.isdir(adir):
        return [("src/repro/analysis", 0, "MISSING — rule registry gone")]
    with open(os.path.join(ROOT, "DESIGN.md")) as f:
        body = _design_section_body(f.read(), "6")
    if not body:
        return [("DESIGN.md", 0, "no §6 section to document lint rules in")]
    problems = []
    n_rules = 0
    for name in sorted(os.listdir(adir)):
        if not name.endswith(".py"):
            continue
        path = os.path.join(adir, name)
        with open(path, errors="replace") as f:
            text = f.read()
        for m in _RULE_DEF.finditer(text):
            n_rules += 1
            rule = m.group(1)
            if f"`{rule}`" not in body:
                lineno = text.count("\n", 0, m.start()) + 1
                problems.append(
                    (f"src/repro/analysis/{name}", lineno,
                     f"lint rule `{rule}` not documented in DESIGN.md §6"))
    if not n_rules:
        problems.append(("src/repro/analysis", 0,
                         "no Rule(...) registrations found — the "
                         "extraction regex or the registry moved"))
    return problems


def main() -> int:
    sections = design_sections(os.path.join(ROOT, "DESIGN.md"))
    if not sections:
        print("check_docs: FAIL — no §-headings found in DESIGN.md")
        return 1
    dangling, n_cites = check_citations(sections)
    problems = dangling + check_examples() + check_lint_rules()
    if problems:
        print("check_docs: FAIL")
        for rel, lineno, what in problems:
            loc = f"{rel}:{lineno}" if lineno else rel
            print(f"  {loc}: {what}")
        print(f"  ({len(problems)} problem(s); DESIGN.md defines: "
              f"{', '.join(sorted(sections))})")
        return 1
    print(f"check_docs: OK — {n_cites} DESIGN §-citations across the repo "
          f"all resolve ({len(sections)} sections); every examples/*.py is "
          f"referenced from README.md; every registered lint rule is "
          f"documented in DESIGN §6")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
