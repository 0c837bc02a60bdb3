"""Analyzer core: sources, suppressions, the rule registry, the runner.

Design constraints (the module docstring of :mod:`dllama_tpu.analysis`
has the why): stdlib-only, sub-5s on the whole tree, one ``ast.parse``
per file shared by every rule, and diagnostics that are plain data so
``--json`` is a dump, not a second code path.
"""

from __future__ import annotations

import ast
import io
import re
import tokenize
from dataclasses import dataclass

#: rule-id -> one-line description. The SINGLE definition site of the rule
#: catalog: the README table is drift-checked against this (rule
#: ``doc-rules``), and a suppression naming an unknown rule is itself a
#: finding (``suppress-unknown``).
RULE_CATALOG = {
    "jit-scope": "cached-jit dispatch in dllama_tpu/engine/ outside a "
                 "LEDGER.scope(...) bracket",
    "jit-label": "LEDGER.scope(fn, ...) whose fn label is not an "
                 "obs/compile.COMPILE_FNS literal",
    "dev-state": "whole-array rebind of a device-authoritative engine "
                 "array (_pos_dev/_last_dev/_keys_dev) outside the "
                 "sanctioned boundary sites",
    "catalog-metric": "metric family created outside obs/instruments.py",
    "catalog-span": "span name not in obs/trace.SPAN_CATALOG",
    "catalog-event": "event name not in obs/trace.EVENT_CATALOG",
    "catalog-fault": "faults.fire/flag point not in utils/faults.POINTS",
    "transfer-note": "host<->device transfer in a steady-state decode/spec "
                     "path without note_transfer accounting",
    "lock-order": "static lock-graph edge that descends or re-enters "
                  "utils/locks.LOCK_RANKS",
    "lock-leaf": "lock acquired while holding a leaf lock (metrics "
                 "registry / tracer)",
    "lock-unranked": "named lock whose name is missing from LOCK_RANKS "
                     "(or a rank no lock uses)",
    "gate-routes": "engine/kernel_select.PAGED_ROUTES drifted from the "
                   "README paged-routing table",
    "gate-aot": "experiments/aot_check.py lost the paged-kernel AOT "
                "inventory",
    "gate-scripts": "a gated smoke script is missing or not executable",
    "doc-rules": "README rule-catalog table drifted from "
                 "analysis.RULE_CATALOG",
    "doc-ranks": "README lock-rank table drifted from "
                 "utils/locks.LOCK_RANKS",
    "suppress-reason": "# dllama: allow[...] suppression without a reason",
    "suppress-unknown": "# dllama: allow[...] naming an unknown rule id",
    "parse-error": "a .py file under analysis does not parse (the file is "
                   "excluded from every other rule)",
}


@dataclass(frozen=True)
class Diagnostic:
    path: str  # repo-relative, forward slashes
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


_SUPPRESS_RE = re.compile(
    r"#\s*dllama:\s*allow\[([A-Za-z0-9_,\s-]+)\]\s*(.*?)\s*$")


class Source:
    """One analyzed file: text + (for .py) a lazily-parsed AST, the
    suppression map, and the function-extent index that lets a suppression
    on a ``def`` line cover the whole function body."""

    def __init__(self, rel: str, text: str):
        self.rel = rel
        self.text = text
        self.lines = text.splitlines()
        self._tree: ast.Module | None = None
        self._def_spans: list[tuple[int, int]] | None = None
        # line -> set of allowed rule ids; bare entries recorded separately
        self.suppressions: dict[int, set[str]] = {}
        self.bare_suppressions: list[tuple[int, str]] = []
        self.unknown_suppressions: list[tuple[int, str]] = []
        for i, ln in self._comments():
            m = _SUPPRESS_RE.search(ln)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            for r in rules:
                if r not in RULE_CATALOG:
                    self.unknown_suppressions.append((i, r))
            self.suppressions[i] = rules
            if not m.group(2):
                self.bare_suppressions.append((i, ",".join(sorted(rules))))

    def _comments(self):
        """(line, comment_text) for REAL comment tokens only — a
        suppression spelled inside a docstring or string literal is prose,
        not policy (tokenize, not a line regex)."""
        if not self.rel.endswith(".py"):
            return
        try:
            toks = tokenize.generate_tokens(io.StringIO(self.text).readline)
            for tok in toks:
                if tok.type == tokenize.COMMENT:
                    yield tok.start[0], tok.string
        except (tokenize.TokenError, SyntaxError,
                IndentationError):  # broken source: no comments to scan
            return

    @property
    def is_py(self) -> bool:
        return self.rel.endswith(".py")

    @property
    def tree(self) -> ast.Module:
        if self._tree is None:
            self._tree = ast.parse(self.text, filename=self.rel)
        return self._tree

    def parse_error(self) -> SyntaxError | None:
        """The file's SyntaxError, or None when it parses — broken files
        become ONE ``parse-error`` diagnostic instead of an analyzer
        traceback (the documented file:line / --json contracts must
        degrade per file, never abort the run)."""
        try:
            self.tree
        except SyntaxError as e:
            return e
        return None

    def _spans(self) -> list[tuple[int, int]]:
        if self._def_spans is None:
            spans = []
            for node in ast.walk(self.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    spans.append((node.lineno,
                                  node.end_lineno or node.lineno))
            self._def_spans = spans
        return self._def_spans

    def suppressed(self, rule: str, line: int) -> bool:
        """True when `rule` is allowed at `line` — by a comment on the line
        itself or on the ``def`` line of any enclosing function."""
        s = self.suppressions.get(line)
        if s and rule in s:
            return True
        if not self.is_py or not self.suppressions:
            return False
        for start, end in self._spans():
            if start <= line <= end:
                s = self.suppressions.get(start)
                if s and rule in s:
                    return True
        return False


class Project:
    """The analyzed file set: repo-relative path -> :class:`Source`.

    ``from_disk`` walks the real tree; tests build in-memory projects from
    ``{relpath: text}`` mappings so every red fixture is a tiny literal.
    ``root`` (optional for in-memory projects) lets filesystem-facts rules
    (executable bits) run."""

    #: non-package files some rules read (gates/docs); missing entries are
    #: each rule's problem to report
    EXTRA_FILES = ("README.md", "experiments/aot_check.py")

    def __init__(self, files: dict[str, str], root: str | None = None):
        self.root = root
        self.sources: dict[str, Source] = {
            rel.replace("\\", "/"): Source(rel.replace("\\", "/"), text)
            for rel, text in files.items()
        }

    @classmethod
    def from_disk(cls, root: str) -> "Project":
        import os

        files: dict[str, str] = {}
        pkg = os.path.join(root, "dllama_tpu")
        for dirpath, dirnames, filenames in os.walk(pkg):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fn)
                rel = os.path.relpath(full, root).replace(os.sep, "/")
                with open(full, encoding="utf-8") as f:
                    files[rel] = f.read()
        for rel in cls.EXTRA_FILES:
            full = os.path.join(root, rel)
            if os.path.exists(full):
                with open(full, encoding="utf-8") as f:
                    files[rel] = f.read()
        return cls(files, root=root)

    def source(self, rel: str) -> Source | None:
        return self.sources.get(rel)

    def py_sources(self, prefix: str = "dllama_tpu/") -> list[Source]:
        """Parseable .py sources under `prefix` — files with syntax errors
        are excluded here and reported once by run() as ``parse-error``."""
        return [s for rel, s in sorted(self.sources.items())
                if s.is_py and rel.startswith(prefix)
                and s.parse_error() is None]


# --------------------------------------------------------------- helpers

def dotted(node: ast.AST) -> str | None:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def str_arg(call: ast.Call, index: int = 0) -> str | None:
    """The index-th positional argument when it is a string literal."""
    if len(call.args) > index:
        a = call.args[index]
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            return a.value
    return None


def parent_map(tree: ast.Module) -> dict[ast.AST, ast.AST]:
    parents: dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


# ---------------------------------------------------------------- runner

def run(project: Project) -> list[Diagnostic]:
    """Run every rule; returns unsuppressed diagnostics sorted by
    (path, line, rule). Suppressions without a reason, or naming unknown
    rules, are findings themselves — a silent blanket allow is exactly
    the drift this analyzer exists to stop."""
    from dllama_tpu.analysis import rules_catalog, rules_gates, rules_jit
    from dllama_tpu.analysis import rules_locks, rules_state

    diags: list[Diagnostic] = []
    for rel, src in sorted(project.sources.items()):
        if src.is_py:
            err = src.parse_error()
            if err is not None:
                diags.append(Diagnostic(
                    rel, err.lineno or 1, "parse-error",
                    f"file does not parse ({err.msg}); excluded from every "
                    "other rule"))
    for checker in (rules_jit.check, rules_state.check, rules_catalog.check,
                    rules_locks.check, rules_gates.check):
        diags.extend(checker(project))
    out = []
    for d in diags:
        src = project.source(d.path)
        if src is not None and src.suppressed(d.rule, d.line):
            continue
        out.append(d)
    for rel, src in sorted(project.sources.items()):
        for line, rules in src.bare_suppressions:
            out.append(Diagnostic(
                rel, line, "suppress-reason",
                f"suppression allow[{rules}] has no reason — say why the "
                "rule does not apply here"))
        for line, rule in src.unknown_suppressions:
            out.append(Diagnostic(
                rel, line, "suppress-unknown",
                f"suppression names unknown rule {rule!r} "
                f"(catalog: {', '.join(sorted(RULE_CATALOG))})"))
    out.sort(key=lambda d: (d.path, d.line, d.rule))
    return out
