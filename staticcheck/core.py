"""Framework core: findings, suppressions, baseline, project loading.

A *pass* is a function `(Project) -> list[Finding]` registered under a
family name via `@register_pass`. The runner executes every pass (or a
`--only` subset), filters findings through inline suppressions and the
committed baseline, and reports what is left. Everything is stdlib-only
(`ast` + `json`): the gate must run in tier-1 on a CPU box in seconds.

Suppression grammar (same line as the finding, or a comment-only line
immediately above it):

    # staticcheck: ignore[rule-a,rule-b] reason text

The reason is mandatory — a reasonless suppression does not suppress
(the whole point is that every grandfathered hazard carries its "why").

Baseline entries are line-number-free fingerprints
(rule, path, context, message) so unrelated edits to a file do not
invalidate them; `--write-baseline` regenerates the file.
"""

from __future__ import annotations

import ast
import io
import json
import os
import re
import tokenize
from dataclasses import dataclass, field

SEVERITIES = ("error", "warning")

# Rules that never gate (informational hygiene about the tool itself).
ADVISORY_RULES = frozenset({"unused-suppression"})

_SUPPRESS_RE = re.compile(
    r"#\s*staticcheck:\s*ignore\[([A-Za-z0-9_,\- ]+)\]\s*(.*)"
)


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative, forward slashes
    line: int
    message: str
    severity: str = "error"
    # Enclosing def/class qualname — part of the baseline fingerprint so
    # entries survive line drift from unrelated edits.
    context: str = ""

    @property
    def fingerprint(self) -> tuple:
        return (self.rule, self.path, self.context, self.message)

    def render(self) -> str:
        return (
            f"{self.path}:{self.line}: {self.severity}[{self.rule}] "
            f"{self.message}"
        )


@dataclass
class Suppression:
    path: str
    target: int  # the ONE line this suppression covers
    comment_line: int  # where the comment itself sits (for reporting)
    rules: tuple[str, ...]
    reason: str
    used: bool = False

    def covers(self, finding: Finding) -> bool:
        # Exactly one line: an inline comment covers its own line, a
        # comment-only line covers the next — never a neighbor (a
        # wider window would silently exempt the unannotated hazard one
        # line above a suppression).
        if finding.path != self.path or not self.reason:
            return False
        if finding.line != self.target:
            return False
        return finding.rule in self.rules or "all" in self.rules


class SourceFile:
    """One parsed module: text, AST, suppressions, dotted module name."""

    def __init__(self, root: str, rel: str):
        self.rel = rel.replace(os.sep, "/")
        self.abspath = os.path.join(root, rel)
        with open(self.abspath, "r", encoding="utf-8") as f:
            self.text = f.read()
        self.lines = self.text.splitlines()
        self.tree = ast.parse(self.text, filename=self.rel)
        mod = self.rel[:-3] if self.rel.endswith(".py") else self.rel
        if mod.endswith("/__init__"):
            mod = mod[: -len("/__init__")]
        self.module = mod.replace("/", ".")
        self.suppressions = self._parse_suppressions()
        # line -> qualname of the innermost def/class starting there (for
        # finding context); filled lazily.
        self._context_spans: list[tuple[int, int, str]] | None = None

    def _parse_suppressions(self) -> list[Suppression]:
        # Real COMMENT tokens only: a suppression example inside a
        # docstring must not register.
        out = []
        try:
            tokens = tokenize.generate_tokens(
                io.StringIO(self.text).readline
            )
            comments = [
                (tok.start[0], tok.string, tok.start[1])
                for tok in tokens
                if tok.type == tokenize.COMMENT
            ]
        except tokenize.TokenError:
            comments = []
        for lineno, comment, col in comments:
            m = _SUPPRESS_RE.search(comment)
            if not m:
                continue
            rules = tuple(
                r.strip() for r in m.group(1).split(",") if r.strip()
            )
            line_text = self.lines[lineno - 1] if lineno <= len(
                self.lines
            ) else ""
            comment_only = line_text.strip().startswith("#")
            out.append(
                Suppression(
                    path=self.rel,
                    # A comment-only line covers the NEXT line; an inline
                    # trailing comment covers its own.
                    target=lineno + 1 if comment_only else lineno,
                    comment_line=lineno,
                    rules=rules,
                    reason=m.group(2).strip(),
                )
            )
        return out

    def context_at(self, line: int) -> str:
        """Qualname of the innermost function/class containing `line`."""
        if self._context_spans is None:
            spans: list[tuple[int, int, str]] = []

            def visit(node, prefix):
                for child in ast.iter_child_nodes(node):
                    if isinstance(
                        child,
                        (
                            ast.FunctionDef,
                            ast.AsyncFunctionDef,
                            ast.ClassDef,
                        ),
                    ):
                        qual = f"{prefix}{child.name}"
                        end = getattr(child, "end_lineno", child.lineno)
                        spans.append((child.lineno, end, qual))
                        visit(child, qual + ".")

            visit(self.tree, "")
            self._context_spans = spans
        best = ""
        best_size = None
        for lo, hi, qual in self._context_spans:
            if lo <= line <= hi and (best_size is None or hi - lo < best_size):
                best, best_size = qual, hi - lo
        return best


# Default scan roots for the real repo layout. Tests are excluded on
# purpose: they exercise hazards (fault injection, deliberate blocking)
# that are the *subject* of the rules, not violations of them.
_REPO_SCAN = ("elasticsearch_tpu", "scripts", "staticcheck")
_REPO_SINGLE_FILES = ("bench.py", "chip_smoke.py")


class Project:
    """The analyzed file set, parsed once and shared by every pass."""

    def __init__(self, root: str, rel_paths: list[str] | None = None):
        self.root = os.path.abspath(root)
        if rel_paths is None:
            rel_paths = self._discover()
        self.files: dict[str, SourceFile] = {}
        errors: list[Finding] = []
        for rel in sorted(rel_paths):
            try:
                sf = SourceFile(self.root, rel)
            except SyntaxError as e:
                errors.append(
                    Finding(
                        rule="parse-error",
                        path=rel.replace(os.sep, "/"),
                        line=e.lineno or 1,
                        message=f"file does not parse: {e.msg}",
                    )
                )
                continue
            self.files[sf.rel] = sf
        self.parse_errors = errors

    def _discover(self) -> list[str]:
        rels: list[str] = []
        scan_dirs = [
            d
            for d in _REPO_SCAN
            if os.path.isdir(os.path.join(self.root, d))
        ]
        if not scan_dirs:
            # Fixture/mini-project layout: everything under root.
            scan_dirs = ["."]
        for d in scan_dirs:
            base = os.path.join(self.root, d)
            for dirpath, dirnames, filenames in os.walk(base):
                dirnames[:] = [
                    n
                    for n in dirnames
                    if n != "__pycache__" and not n.startswith(".")
                ]
                for name in sorted(filenames):
                    if name.endswith(".py"):
                        rels.append(
                            os.path.relpath(
                                os.path.join(dirpath, name), self.root
                            )
                        )
        for name in _REPO_SINGLE_FILES:
            if os.path.isfile(os.path.join(self.root, name)):
                rels.append(name)
        return rels

    def get(self, rel: str) -> SourceFile | None:
        return self.files.get(rel)

    def suppressions(self) -> list[Suppression]:
        return [s for sf in self.files.values() for s in sf.suppressions]


# --------------------------------------------------------------- registry

@dataclass
class PassInfo:
    name: str
    fn: object
    rules: dict[str, str] = field(default_factory=dict)  # rule -> rationale


PASSES: dict[str, PassInfo] = {}


def register_pass(name: str, rules: dict[str, str]):
    """Register a pass under a family name with its rule glossary."""

    def deco(fn):
        PASSES[name] = PassInfo(name=name, fn=fn, rules=rules)
        return fn

    return deco


def all_rules() -> dict[str, str]:
    out = {"parse-error": "analyzed file must parse"}
    for info in PASSES.values():
        out.update(info.rules)
    out["unused-suppression"] = (
        "a staticcheck ignore comment that suppresses nothing is stale"
    )
    return out


# ----------------------------------------------------------------- runner

@dataclass
class Report:
    findings: list[Finding]  # post-suppression, post-baseline (the news)
    baselined: list[Finding]
    suppressed: list[Finding]
    unused_suppressions: list[Suppression]
    per_rule: dict[str, int]

    @property
    def failed(self) -> bool:
        return any(f.rule not in ADVISORY_RULES for f in self.findings)

    def summary_lines(self) -> list[str]:
        lines = []
        for rule in sorted(self.per_rule):
            lines.append(f"  {rule:32s} {self.per_rule[rule]}")
        lines.append(
            f"{len(self.findings)} finding(s) "
            f"({len(self.baselined)} baselined, "
            f"{len(self.suppressed)} suppressed)"
        )
        return lines


def load_baseline(path: str) -> set[tuple]:
    if not path or not os.path.isfile(path):
        return set()
    with open(path, "r", encoding="utf-8") as f:
        entries = json.load(f)
    return {
        (e["rule"], e["path"], e.get("context", ""), e["message"])
        for e in entries
    }


def write_baseline(path: str, findings: list[Finding]) -> None:
    entries = [
        {
            "rule": f.rule,
            "path": f.path,
            "context": f.context,
            "message": f.message,
        }
        for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule))
    ]
    with open(path, "w", encoding="utf-8") as f:
        json.dump(entries, f, indent=1)
        f.write("\n")


def run_project(
    project: Project,
    baseline: set[tuple] | None = None,
    only: list[str] | None = None,
) -> Report:
    # Import-for-effect: pass modules self-register.
    from . import passes  # noqa: F401

    raw: list[Finding] = list(project.parse_errors)
    active_rules: set[str] = set()
    for name, info in sorted(PASSES.items()):
        if only and name not in only:
            continue
        active_rules.update(info.rules)
        raw.extend(info.fn(project))

    # Attach contexts (cheap, needed for fingerprints).
    fixed: list[Finding] = []
    for f in raw:
        if not f.context:
            sf = project.get(f.path)
            if sf is not None:
                f = Finding(
                    rule=f.rule,
                    path=f.path,
                    line=f.line,
                    message=f.message,
                    severity=f.severity,
                    context=sf.context_at(f.line),
                )
        fixed.append(f)

    sups = project.suppressions()
    by_path: dict[str, list[Suppression]] = {}
    for s in sups:
        by_path.setdefault(s.path, []).append(s)

    kept: list[Finding] = []
    suppressed: list[Finding] = []
    baselined: list[Finding] = []
    baseline = baseline or set()
    for f in sorted(fixed, key=lambda f: (f.path, f.line, f.rule)):
        hit = None
        for s in by_path.get(f.path, ()):
            if s.covers(f):
                hit = s
                break
        if hit is not None:
            hit.used = True
            suppressed.append(f)
        elif f.fingerprint in baseline:
            baselined.append(f)
        else:
            kept.append(f)

    # A suppression is only stale if every rule it names actually ran
    # this invocation (a --only subset must not flag the other families'
    # suppressions).
    unused = [
        s
        for s in sups
        if not s.used and all(r in active_rules for r in s.rules)
    ]
    for s in unused:
        kept.append(
            Finding(
                rule="unused-suppression",
                path=s.path,
                line=s.comment_line,
                message=(
                    "suppression "
                    f"ignore[{','.join(s.rules)}] matches no finding"
                    + ("" if s.reason else " (and has no reason text)")
                ),
                severity="warning",
            )
        )

    per_rule: dict[str, int] = {}
    for f in kept:
        per_rule[f.rule] = per_rule.get(f.rule, 0) + 1
    return Report(
        findings=kept,
        baselined=baselined,
        suppressed=suppressed,
        unused_suppressions=unused,
        per_rule=per_rule,
    )
