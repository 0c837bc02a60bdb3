#!/usr/bin/env bash
# checks.sh — static hygiene gate for CI and pre-commit:
#
#   1. `python -m compileall` over the package, tests and scripts — syntax
#      errors fail here in milliseconds instead of mid-suite;
#   2. observability catalog drift check — every metric registered in
#      dllama_tpu/obs/instruments.py, every span/event name in
#      dllama_tpu/obs/trace.{SPAN,EVENT}_CATALOG, and every fault-injection
#      point in dllama_tpu/utils/faults.POINTS must appear in README.md.
#      The catalogs are the single definition sites; this keeps the docs
#      from silently rotting when an instrument, a trace point, or a fault
#      point is added. (These syncs genuinely need the live registry
#      import, so they stay here.)
#   3. the repo-native invariant analyzer (ISSUE 14) as a HARD gate:
#      `python -m dllama_tpu.analysis` — jit-dispatch discipline,
#      device-state writes, single-site catalogs, the steady-state
#      transfer lint, the static lock-order graph, and the textual
#      contracts this script used to grep for (paged routes, the AOT
#      inventory), all with file:line diagnostics. scripts/analysis_smoke.sh drills that the gate can
#      actually fail.
#
# Pure host: imports only dllama_tpu.obs/analysis (stdlib-only — no jax,
# no model), so it runs anywhere in seconds. Exit 0 = PASS.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m compileall -q dllama_tpu tests scripts
echo "checks: compileall OK"

python - <<'PY'
import sys

from dllama_tpu.obs import metrics  # noqa: F401  (registry core)
from dllama_tpu.obs import instruments  # noqa: F401  (registers every metric)
from dllama_tpu.obs import trace

with open("README.md", encoding="utf-8") as f:
    readme = f.read()

missing = []
for name in metrics.REGISTRY.names():
    if name not in readme:
        missing.append(f"metric:{name}")
# series that a smoke script, a drill or the benchmark asserts on (the
# paged-KV pool gauges, the radix / speculative / hybrid / compile-ledger /
# router / failover / fleet series, and the launch counters the `capture`
# block of /debug/perf reads): their REMOVAL from the registry must fail
# here too, not just their absence from the README
for name in ("dllama_kv_pages_total", "dllama_kv_pages_used",
             "dllama_kv_pages_shared",
             "dllama_radix_lookups_total", "dllama_radix_hit_tokens_total",
             "dllama_radix_nodes", "dllama_radix_pages",
             "dllama_spec_cycles_total", "dllama_spec_tokens_total",
             "dllama_spec_accepted_length",
             "dllama_prefill_budget_tokens", "dllama_preemptions_total",
             "dllama_resumed_total",
             "dllama_jit_compiles_total", "dllama_jit_compile_seconds_total",
             "dllama_jit_unexpected_compiles_total",
             "dllama_transfers_total", "dllama_transfer_bytes_total",
             "dllama_device_live_buffers", "dllama_device_live_bytes",
             "dllama_router_requests_total",
             "dllama_router_affinity_hits_total",
             "dllama_replica_healthy", "dllama_frontend_connections",
             "dllama_router_failovers_total",
             "dllama_kv_host_pages_total", "dllama_kv_host_pages_used",
             "dllama_kv_spill_total",
             "dllama_replica_clock_offset_seconds",
             "dllama_replica_clock_uncertainty_seconds",
             "dllama_router_federation_scrape_seconds",
             "dllama_fleet_scrape_age_seconds",
             "dllama_router_ttft_seconds", "dllama_router_itl_seconds",
             "dllama_router_slo_attainment",
             "dllama_launches_total", "dllama_slot_steps_total",
             "dllama_launch_kv_rows_total",
             "dllama_launch_prefill_rows_total"):
    if name not in metrics.REGISTRY.names():
        missing.append(f"unregistered:{name}")
for name in sorted(trace.SPAN_CATALOG):
    if name not in readme:
        missing.append(f"span:{name}")
for name in sorted(trace.EVENT_CATALOG):
    if name not in readme:
        missing.append(f"event:{name}")

# fault-injection points (utils/faults.POINTS is the single definition
# site, armed sites call fire()/flag() with these names): each must be
# documented in the README Operations section AND in the faults.py
# docstring table — an undrillable failure path is not a failure path
from dllama_tpu.utils import faults
for name in sorted(faults.POINTS):
    if name not in readme:
        missing.append(f"fault:{name}")
    if name not in (faults.__doc__ or ""):
        missing.append(f"fault-docstring:{name}")

if missing:
    sys.exit("README observability-catalog drift — document these in the "
             "README tables: " + ", ".join(missing))

# scheduler time-ledger states: the README ledger table must match
# obs/perf.LEDGER_STATES EXACTLY (both directions — a renamed state with a
# stale doc row is attribution lying to the operator). The table is the one
# whose header row is "| Ledger state |".
import re

from dllama_tpu.obs import perf

rows, in_table = [], False
for line in readme.splitlines():
    if line.startswith("| Ledger state |"):
        in_table = True
        continue
    if in_table:
        if not line.startswith("|"):
            break
        m = re.match(r"^\| `([a-z_]+)` \|", line)
        if m:
            rows.append(m.group(1))
readme_states, catalog_states = set(rows), set(perf.LEDGER_STATES)
if readme_states != catalog_states:
    sys.exit("ledger state-label drift between obs/perf.LEDGER_STATES and "
             f"the README ledger table: catalog-only="
             f"{sorted(catalog_states - readme_states)} readme-only="
             f"{sorted(readme_states - catalog_states)}")

# compile-fn catalog (ISSUE 13): the README "Compile fn" bucket table must
# match obs/compile.COMPILE_FNS EXACTLY (both directions, like the ledger
# check) — a renamed dispatch-site label with a stale doc row is a contract
# lying to the operator. The table is the one whose header row starts
# "| Compile fn |".
from dllama_tpu.obs import compile as compile_obs

rows, in_table = [], False
for line in readme.splitlines():
    if line.startswith("| Compile fn |"):
        in_table = True
        continue
    if in_table:
        if not line.startswith("|"):
            break
        m = re.match(r"^\| `([a-z_]+)` \|", line)
        if m:
            rows.append(m.group(1))
readme_fns, catalog_fns = set(rows), set(compile_obs.COMPILE_FNS)
if readme_fns != catalog_fns:
    sys.exit("compile-fn label drift between obs/compile.COMPILE_FNS and "
             f"the README bucket table: catalog-only="
             f"{sorted(catalog_fns - readme_fns)} readme-only="
             f"{sorted(readme_fns - catalog_fns)}")

print(f"checks: catalog drift OK ({len(metrics.REGISTRY.names())} metrics, "
      f"{len(trace.SPAN_CATALOG)} spans, {len(trace.EVENT_CATALOG)} events, "
      f"{len(faults.POINTS)} fault points, "
      f"{len(perf.LEDGER_STATES)} ledger states, "
      f"{len(compile_obs.COMPILE_FNS)} compile fns all documented)")
PY

# everything textual that used to be grep'd here — the paged-route README
# table (ISSUE 8), the AOT inventory — plus the invariant rules (ISSUE 14)
# run as ONE analyzer pass with real file:line diagnostics
python -m dllama_tpu.analysis
echo "checks: invariant analyzer OK (jit/device-state/catalog/transfer/lock rules + repo gates)"
