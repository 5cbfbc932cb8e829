"""graftcheck core: source loading, findings, inline suppressions, baseline.

The framework half of the analyzer — rule families live in
``tools/graftcheck/rules/``; this module gives them a parsed view of the
tree and owns everything about *reporting*: one-line-per-finding output,
the ``# graftcheck: disable=...`` inline suppression contract, and the
checked-in baseline that lets the CI gate start at zero findings without
rewriting history in one sitting.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Iterable

# directories never worth parsing (caches, VCS, build junk)
_SKIP_DIRS = {"__pycache__", ".git", ".venv", "node_modules", "build",
              "media", "benchmarks"}

# `# graftcheck: disable=GC101,GC202 -- reason`  (reason optional but
# strongly encouraged: the suppression IS the documentation of why the
# flagged pattern is safe here)
_SUPPRESS_RE = re.compile(
    r"#\s*graftcheck:\s*disable=([A-Za-z0-9_,\s]+?)(?:\s*--\s*(.*))?$"
)

# `# graftcheck: hot-region decode` ... `# graftcheck: end-hot-region`
_REGION_OPEN_RE = re.compile(r"#\s*graftcheck:\s*hot-region\s+([\w./+-]+)")
_REGION_CLOSE_RE = re.compile(r"#\s*graftcheck:\s*end-hot-region")


@dataclass(frozen=True)
class Finding:
    """One diagnostic: ``file:line: rule message`` (file repo-relative)."""

    file: str
    line: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.file}:{self.line}: {self.rule} {self.message}"

    def baseline_key(self, project: "Project") -> tuple[str, str, str]:
        """Line-number-independent identity: (file, rule, stripped source
        text of the flagged line) — survives unrelated edits above it."""
        sf = project.by_rel.get(self.file)
        context = ""
        if sf is not None and 1 <= self.line <= len(sf.lines):
            context = sf.lines[self.line - 1].strip()
        return (self.file, self.rule, context)


@dataclass
class HotRegion:
    name: str
    start: int  # 1-based line of the opening marker
    end: int    # 1-based line of the closing marker (inclusive span)


@dataclass
class SourceFile:
    path: str          # absolute
    rel: str           # repo-relative, '/'-separated
    source: str
    tree: ast.Module
    lines: list[str] = field(default_factory=list)
    # line -> set of suppressed rule ids ("all" wildcard allowed)
    suppressions: dict[int, set[str]] = field(default_factory=dict)
    regions: list[HotRegion] = field(default_factory=list)

    def suppressed(self, line: int, rule: str) -> bool:
        """A suppression comment covers its own physical line and the line
        directly below it (so a comment-only line annotates the statement
        it precedes, and a trailing comment annotates its own statement)."""
        for cand in (line, line - 1):
            ids = self.suppressions.get(cand)
            if ids and ("all" in ids or rule in ids
                        or any(rule.startswith(i) for i in ids)):
                return True
        return False

    def region_at(self, line: int) -> HotRegion | None:
        for r in self.regions:
            if r.start <= line <= r.end:
                return r
        return None


def _scan_comments(sf: SourceFile) -> None:
    open_stack: list[tuple[str, int]] = []
    for i, raw in enumerate(sf.lines, start=1):
        if "graftcheck" not in raw:
            continue
        m = _SUPPRESS_RE.search(raw)
        if m:
            ids = {s.strip() for s in m.group(1).split(",") if s.strip()}
            sf.suppressions.setdefault(i, set()).update(ids)
        m = _REGION_OPEN_RE.search(raw)
        if m:
            open_stack.append((m.group(1), i))
            continue
        if _REGION_CLOSE_RE.search(raw) and open_stack:
            name, start = open_stack.pop()
            sf.regions.append(HotRegion(name, start, i))
    # unterminated region: runs to EOF (still checked, never silently off)
    for name, start in open_stack:
        sf.regions.append(HotRegion(name, start, len(sf.lines)))


@dataclass
class Project:
    """Parsed view of the repo the rule families share."""

    root: str
    files: list[SourceFile] = field(default_factory=list)
    by_rel: dict[str, SourceFile] = field(default_factory=dict)
    errors: list[str] = field(default_factory=list)

    def get(self, rel: str) -> SourceFile | None:
        return self.by_rel.get(rel.replace(os.sep, "/"))

    def in_dir(self, *prefixes: str) -> list[SourceFile]:
        return [
            sf for sf in self.files
            if any(sf.rel == p or sf.rel.startswith(p.rstrip("/") + "/")
                   for p in prefixes)
        ]


def load_file(root: str, path: str) -> SourceFile | None:
    rel = os.path.relpath(path, root).replace(os.sep, "/")
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source, filename=rel)
    # ValueError covers UnicodeDecodeError (non-UTF-8 bytes) and ast's
    # null-byte rejection — an unreadable file must surface as ONE
    # 'unparseable' warning, never crash the whole gate
    except (OSError, SyntaxError, ValueError):
        return None
    sf = SourceFile(path=path, rel=rel, source=source, tree=tree,
                    lines=source.splitlines())
    _scan_comments(sf)
    return sf


def load_project(root: str, extra_rel: Iterable[str] = ()) -> Project:
    """Parse every ``.py`` under the package + tools + the repo-root entry
    points; ``extra_rel`` adds consumer files outside the default walk
    (tests the telemetry rule cross-checks against)."""
    project = Project(root=root)
    wanted: list[str] = []
    for top in ("distrl_llm_tpu", "tools"):
        base = os.path.join(root, top)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    wanted.append(os.path.join(dirpath, fn))
    entry = os.path.join(root, "train_distributed.py")
    if os.path.exists(entry):
        wanted.append(entry)
    for rel in extra_rel:
        p = os.path.join(root, rel)
        if os.path.exists(p):
            wanted.append(p)
    for path in wanted:
        sf = load_file(root, path)
        if sf is None:
            project.errors.append(f"unparseable: {path}")
            continue
        project.files.append(sf)
        project.by_rel[sf.rel] = sf
    return project


# ------------------------------------------------------------------ baseline


def load_baseline(path: str) -> list[dict]:
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return []
    entries = doc.get("entries", []) if isinstance(doc, dict) else []
    return [e for e in entries if isinstance(e, dict)]


def save_baseline(path: str, findings: list[Finding],
                  project: Project) -> None:
    entries = []
    for f in sorted(findings, key=lambda x: (x.file, x.rule, x.line)):
        file, rule, context = f.baseline_key(project)
        entries.append({"file": file, "rule": rule, "context": context})
    doc = {
        "_comment": (
            "graftcheck baseline: grandfathered findings the CI gate "
            "tolerates. Regenerate with "
            "`python -m tools.graftcheck --update-baseline`; keep this "
            "shrinking — new code must land clean."
        ),
        "entries": entries,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


def split_baselined(
    findings: list[Finding], baseline: list[dict], project: Project,
) -> tuple[list[Finding], list[Finding]]:
    """(fresh, grandfathered): each baseline entry absorbs at most one
    finding (a multiset match), so a *second* instance of a baselined
    pattern still fails the gate."""
    budget: dict[tuple[str, str, str], int] = {}
    for e in baseline:
        key = (str(e.get("file", "")), str(e.get("rule", "")),
               str(e.get("context", "")))
        budget[key] = budget.get(key, 0) + 1
    fresh: list[Finding] = []
    grandfathered: list[Finding] = []
    for f in findings:
        key = f.baseline_key(project)
        if budget.get(key, 0) > 0:
            budget[key] -= 1
            grandfathered.append(f)
        else:
            fresh.append(f)
    return fresh, grandfathered


# ----------------------------------------------------------------- execution


RuleFn = Callable[[Project], "list[Finding]"]


def run_project(
    project: Project, rules: dict[str, RuleFn],
) -> tuple[list[Finding], int]:
    """Run rule families; returns (active findings, suppressed count).
    Inline suppressions are resolved here so every rule stays a pure
    ``Project -> findings`` function."""
    active: list[Finding] = []
    suppressed = 0
    for _name, fn in sorted(rules.items()):
        for f in fn(project):
            sf = project.by_rel.get(f.file)
            if sf is not None and sf.suppressed(f.line, f.rule):
                suppressed += 1
                continue
            active.append(f)
    active.sort(key=lambda f: (f.file, f.line, f.rule))
    return active, suppressed


# ---------------------------------------------------------------- ast helpers


def dotted_name(node: ast.AST) -> str | None:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def module_constants(sf: SourceFile) -> dict[str, tuple[str, int]]:
    """Module-level ``NAME = "literal"`` string assignments:
    name -> (value, line)."""
    out: dict[str, tuple[str, int]] = {}
    for node in sf.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target, value = node.targets[0], node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            target, value = node.target, node.value
        else:
            continue
        if (isinstance(target, ast.Name)
                and isinstance(value, ast.Constant)
                and isinstance(value.value, str)):
            out[target.id] = (value.value, node.lineno)
    return out
