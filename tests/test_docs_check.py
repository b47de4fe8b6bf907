"""Docs hygiene gate (CI's `docs-check` job).

Greps the maintained markdown set — the root README, `docs/`, and
in-tree `README.md`s under `src/` — and fails on:

- intra-repo markdown links whose target file does not exist;
- `#anchor` fragments that match no heading in the target file
  (GitHub's slug rules: lowercase, punctuation stripped, spaces to
  hyphens — so renaming a heading breaks the build, not the reader);
- backtick code spans that look like repo file paths (optionally with a
  `::symbol` suffix) but point at nothing — paths resolve against the
  doc's own directory, the repo root, `src/`, and `src/repro/`;
- `--flag` tokens that no argparse definition in `src/repro/launch/`,
  `src/repro/analysis/`, `benchmarks/` or `chip_smoke.py` declares
  (docs describing nonexistent CLI flags).

Pure stdlib + grep-style regexes: no markdown parser dependency.
"""
from __future__ import annotations

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

DOC_FILES = sorted(
    [REPO / "README.md"]
    + list((REPO / "docs").glob("*.md"))
    + list((REPO / "src").rglob("README.md"))
)

# resolution roots for backtick path references, in order
PATH_ROOTS = [REPO, REPO / "src", REPO / "src" / "repro"]

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
HEADING_RE = re.compile(r"^#{1,6}\s+(.*)$", re.MULTILINE)
CODE_PATH_RE = re.compile(
    r"`([A-Za-z0-9_][A-Za-z0-9_./]*\.(?:py|md|json|jsonl))"
    r"(?:::([A-Za-z_][A-Za-z0-9_.]*))?`")
FLAG_RE = re.compile(r"(?<![\w-])(--[a-z][a-z0-9_-]+)")
ARGPARSE_FLAG_RE = re.compile(r"add_argument\(\s*[\"'](--[a-z0-9-]+)[\"']")
# underscore-style --xla_* tokens are XLA runtime flags (passed via the
# XLA_FLAGS env var, e.g. the forced host-device count in
# docs/sharding.md), not repo argparse flags — out of scope for this gate
EXTERNAL_FLAG_PREFIXES = ("--xla_",)


def github_slug(heading: str) -> str:
    """GitHub's heading -> anchor slug: strip markup, lowercase, drop
    punctuation (keeping word chars, spaces, hyphens), spaces->hyphens."""
    h = heading.strip().lower()
    h = h.replace("`", "")                       # inline code markup
    h = re.sub(r"[^\w\- ]", "", h)
    return h.replace(" ", "-")


def anchors_of(md_path: Path) -> set:
    text = md_path.read_text()
    return {github_slug(m.group(1)) for m in HEADING_RE.finditer(text)}


def _strip_code_fences(text: str) -> str:
    """Links/paths inside fenced code blocks are examples, not promises
    (e.g. `/tmp/...` output paths); check prose only — EXCEPT flags,
    which are checked fences-in (see test_cli_flags_exist)."""
    return re.sub(r"```.*?```", "", text, flags=re.DOTALL)


def test_doc_set_is_nonempty():
    assert len(DOC_FILES) >= 6, DOC_FILES


@pytest.mark.parametrize("md", DOC_FILES, ids=lambda p: str(p.relative_to(REPO)))
def test_intra_repo_links_resolve(md):
    bad = []
    for target in LINK_RE.findall(_strip_code_fences(md.read_text())):
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        path_part, _, anchor = target.partition("#")
        if path_part:
            dest = (md.parent / path_part).resolve()
            if not dest.exists():
                bad.append(f"{target}: file {path_part} not found")
                continue
        else:
            dest = md
        if anchor:
            if dest.suffix != ".md":
                continue
            if anchor not in anchors_of(dest):
                bad.append(f"{target}: no heading slugs to '{anchor}' "
                           f"in {dest.name}")
    assert not bad, "\n".join(bad)


@pytest.mark.parametrize("md", DOC_FILES, ids=lambda p: str(p.relative_to(REPO)))
def test_backtick_paths_exist(md):
    bad = []
    for m in CODE_PATH_RE.finditer(_strip_code_fences(md.read_text())):
        ref, symbol = m.group(1), m.group(2)
        roots = [md.parent] + PATH_ROOTS
        hits = [r / ref for r in roots if (r / ref).exists()]
        if not hits:
            bad.append(f"`{ref}`: not found relative to {md.parent.name}/,"
                       f" repo root, src/, or src/repro/")
            continue
        if symbol and symbol not in hits[0].read_text():
            bad.append(f"`{ref}::{symbol}`: symbol not in {hits[0].name}")
    assert not bad, "\n".join(bad)


def _declared_cli_flags() -> set:
    flags = set()
    for src_dir in [REPO / "src" / "repro" / "launch",
                    REPO / "src" / "repro" / "analysis",
                    REPO / "benchmarks"]:
        for py in src_dir.glob("*.py"):
            flags.update(ARGPARSE_FLAG_RE.findall(py.read_text()))
    flags.update(ARGPARSE_FLAG_RE.findall(
        (REPO / "chip_smoke.py").read_text()))
    return flags


@pytest.mark.parametrize("md", DOC_FILES, ids=lambda p: str(p.relative_to(REPO)))
def test_cli_flags_exist(md):
    """Every --flag a doc mentions must be declared by some argparse in
    launch/, analysis/, benchmarks/ or chip_smoke.py — docs referencing removed or
    misspelled flags fail here (checked inside code fences too: that's
    where the copy-paste commands live)."""
    declared = _declared_cli_flags()
    bad = [f for f in FLAG_RE.findall(md.read_text())
           if f not in declared
           and not f.startswith(EXTERNAL_FLAG_PREFIXES)]
    assert not bad, (f"{sorted(set(bad))} not declared by any argparse in "
                     f"src/repro/launch/, src/repro/analysis/, or "
                     f"benchmarks/")


def test_launch_serve_flags_documented():
    """The reverse direction for the serving CLI: every serve.py flag
    appears somewhere in the maintained docs (the handbook's CLI section
    or the README quickstart)."""
    serve_src = (REPO / "src" / "repro" / "launch" / "serve.py").read_text()
    corpus = "\n".join(p.read_text() for p in DOC_FILES)
    missing = [f for f in ARGPARSE_FLAG_RE.findall(serve_src)
               if f not in corpus]
    assert not missing, missing
