"""Repo-contract gates (``gate-*``) and doc drift (``doc-*``) — the
checks scripts/checks.sh used to enforce with greps, upgraded to real
``file:line`` diagnostics, plus the analyzer's own documentation loop.

checks.sh keeps only what genuinely needs a live import (the metric/span/
event/fault/ledger-state/compile-fn README syncs read the registry);
everything textual moved here:

* ``gate-routes`` — ``engine/kernel_select.PAGED_ROUTES`` and the README
  "Paged KV cache" routing table must match both directions (a route the
  docs don't name, or a doc row for a route kernel_select cannot
  resolve, is the operator-facing contract lying).
* ``gate-aot`` — the paged-kernel AOT inventory of
  ``experiments/aot_check.py`` must keep existing: deleting it un-gates a
  shipped invariant silently.
* ``gate-scripts`` — the smoke entry points stay present and executable.
* ``doc-rules`` / ``doc-ranks`` — the README rule-catalog table matches
  :data:`~dllama_tpu.analysis.core.RULE_CATALOG` and the README lock-rank
  table matches ``utils/locks.LOCK_RANKS``, both directions — the same
  discipline LEDGER_STATES already gets.
"""

from __future__ import annotations

import ast
import re

from dllama_tpu.analysis.core import RULE_CATALOG, Diagnostic
from dllama_tpu.utils.locks import LOCK_RANKS

_KSEL = "dllama_tpu/engine/kernel_select.py"

#: routes that must keep EXISTING (the old checks.sh loop pinned these by
#: name — a commit deleting a shipped route from both the tuple and the
#: README must still fail, not pass as "consistent")
REQUIRED_ROUTES = ("paged_kernel", "paged_gather")

#: aot_check.py markers: the paged flash-decode op inventory + its fused-
#: scatter cases (ISSUE 8)
AOT_MARKERS = ("paged_decode_attention", "fused scatter")

#: smoke scripts the gates cite (path, must-be-executable)
GATED_SCRIPTS = ("scripts/hybrid_smoke.sh", "scripts/compile_smoke.sh",
                 "scripts/analysis_smoke.sh", "scripts/router_smoke.sh",
                 "scripts/failover_smoke.sh", "scripts/chaos_soak.sh",
                 "scripts/fleet_smoke.sh")


def _line_of(src, needle: str, default: int = 1) -> int:
    for i, ln in enumerate(src.lines, 1):
        if needle in ln:
            return i
    return default


def _table_rows(src, header_prefix: str) -> list[tuple[int, str]]:
    """(line, id) rows of the first README table whose header row starts
    with `header_prefix` — same parse as checks.sh's ledger-state check."""
    rows, in_table = [], False
    for i, line in enumerate(src.lines, 1):
        if line.startswith(header_prefix):
            in_table = True
            continue
        if in_table:
            if not line.startswith("|"):
                break
            m = re.match(r"^\| `([a-zA-Z0-9_.-]+)` \|", line)
            if m:
                rows.append((i, m.group(1)))
    return rows


def _check_routes(project, diags):
    ksel = project.source(_KSEL)
    readme = project.source("README.md")
    if ksel is None or ksel.parse_error() is not None:
        return  # a broken file is reported once as parse-error
    routes: list[str] = []
    route_line = 1
    for node in ksel.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "PAGED_ROUTES":
            route_line = node.lineno
            if isinstance(node.value, (ast.Tuple, ast.List)):
                routes = [e.value for e in node.value.elts
                          if isinstance(e, ast.Constant)
                          and isinstance(e.value, str)]
    if not routes:
        diags.append(Diagnostic(
            _KSEL, route_line, "gate-routes",
            "PAGED_ROUTES tuple missing — it is the single definition "
            "site of the paged attention routes"))
        return
    for r in REQUIRED_ROUTES:
        if r not in routes:
            diags.append(Diagnostic(
                _KSEL, route_line, "gate-routes",
                f"shipped route {r!r} missing from PAGED_ROUTES — "
                "kernel_select can no longer resolve it (ISSUE 8's "
                "serving contract)"))
    if readme is None:
        diags.append(Diagnostic(
            "README.md", 1, "gate-routes",
            "README.md missing — the paged-routing table cannot be "
            "drift-checked"))
        return
    readme_rows = re.findall(r"^\| `([a-z_]+)` \|", readme.text, re.M)
    for r in routes:
        if r not in readme_rows:
            diags.append(Diagnostic(
                "README.md", _line_of(readme, "Paged KV cache"),
                "gate-routes",
                f"README 'Paged KV cache' routing table lost its "
                f"`{r}` row (kernel_select.PAGED_ROUTES names it)"))
    for r in set(readme_rows):
        if r.startswith("paged_") and r not in routes:
            diags.append(Diagnostic(
                "README.md", _line_of(readme, f"| `{r}` |"),
                "gate-routes",
                f"README routing row `{r}` names a route "
                "kernel_select.PAGED_ROUTES cannot resolve"))


def _check_aot(project, diags):
    aot = project.source("experiments/aot_check.py")
    if aot is None:
        diags.append(Diagnostic("experiments/aot_check.py", 1, "gate-aot",
                                "aot_check.py missing"))
    else:
        for marker in AOT_MARKERS:
            if marker not in aot.text:
                diags.append(Diagnostic(
                    "experiments/aot_check.py", 1, "gate-aot",
                    f"AOT gate lost its {marker!r} cases — a Mosaic "
                    "rejection could reach a live window unflagged"))


def _check_scripts(project, diags):
    if project.root is None:
        return  # in-memory fixture projects have no filesystem facts
    import os

    for rel in GATED_SCRIPTS:
        full = os.path.join(project.root, rel)
        if not os.path.exists(full):
            diags.append(Diagnostic(rel, 1, "gate-scripts",
                                    f"{rel} missing"))
        elif not os.access(full, os.X_OK):
            diags.append(Diagnostic(rel, 1, "gate-scripts",
                                    f"{rel} is not executable"))


def _check_docs(project, diags):
    readme = project.source("README.md")
    if readme is None:
        return
    rule_rows = _table_rows(readme, "| Rule |")
    doc_rules = {r for _, r in rule_rows}
    cat = set(RULE_CATALOG)
    anchor = _line_of(readme, "| Rule |")
    for r in sorted(cat - doc_rules):
        diags.append(Diagnostic(
            "README.md", anchor, "doc-rules",
            f"analyzer rule `{r}` has no row in the README rule-catalog "
            "table"))
    for line, r in rule_rows:
        if r not in cat:
            diags.append(Diagnostic(
                "README.md", line, "doc-rules",
                f"README rule-catalog row `{r}` names no analyzer rule "
                "(analysis.RULE_CATALOG is the definition site)"))
    rank_rows = _table_rows(readme, "| Lock |")
    doc_ranks = {r for _, r in rank_rows}
    anchor = _line_of(readme, "| Lock |")
    for name in sorted(set(LOCK_RANKS) - doc_ranks):
        diags.append(Diagnostic(
            "README.md", anchor, "doc-ranks",
            f"lock `{name}` (rank {LOCK_RANKS[name]}) has no row in the "
            "README lock-rank table"))
    for line, name in rank_rows:
        if name not in LOCK_RANKS:
            diags.append(Diagnostic(
                "README.md", line, "doc-ranks",
                f"README lock-rank row `{name}` names no "
                "utils/locks.LOCK_RANKS entry"))


def check(project) -> list[Diagnostic]:
    diags: list[Diagnostic] = []
    _check_routes(project, diags)
    _check_aot(project, diags)
    _check_scripts(project, diags)
    _check_docs(project, diags)
    return diags
