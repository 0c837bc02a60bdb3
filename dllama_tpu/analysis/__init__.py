"""Repo-native static invariant analyzer (ISSUE 14).

A dependency-free (stdlib ``ast`` only, no jax import) rule engine that
machine-checks the conventions the serving stack's correctness rests on —
run as ``python -m dllama_tpu.analysis`` (wired into scripts/checks.sh as
a hard CI gate) with ``file:line: rule-id message`` diagnostics, inline
suppressions (``# dllama: allow[rule-id] reason``) and a ``--json`` mode.

Rule families (the README "Static analysis & lock discipline" table is
drift-checked against :data:`RULE_CATALOG` both directions):

* **jit** — every cached-jit dispatch in ``engine/`` is bracketed in
  ``LEDGER.scope(fn, key)`` with a label from ``obs/compile.COMPILE_FNS``
  (PR 12's ledger only catches an unattributed compile if that path runs;
  this fails CI at the callsite).
* **dev** — the device-authoritative decode arrays (``_pos_dev``,
  ``_last_dev``, ``_keys_dev``) are written per-row (``.at[...]``) or from
  jit carries, never bulk-rebuilt from host mirrors outside the sanctioned
  boundary sites (the PR 10 bug class).
* **catalog** — metrics families, span/event names and fault points
  register only through their single-site catalogs.
* **transfer** — host<->device transfers inside the steady-state
  decode/spec paths only at ``note_transfer``-annotated sites.
* **lock** — the static cross-module lock-order graph (named locks from
  ``utils/locks``) must strictly ascend ``LOCK_RANKS``; nothing is ever
  acquired under the metrics/tracer leaf locks. The runtime half is the
  ``DLLAMA_LOCK_AUDIT=1`` sanitizer in ``utils/locks``.
* **gate** — the repo contracts scripts/checks.sh used to grep for
  (paged-route README table, the AOT inventory), now with real
  ``file:line`` diagnostics.
* **doc** — the README rule-catalog and lock-rank tables match the code's
  definition sites exactly, both directions.
"""

from dllama_tpu.analysis.core import (  # noqa: F401
    Diagnostic,
    Project,
    RULE_CATALOG,
    run,
)
