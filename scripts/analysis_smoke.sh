#!/usr/bin/env bash
# analysis_smoke.sh — proves the invariant analyzer (ISSUE 14) actually
# gates: a pristine copy of the tree passes, then a seeded violation in a
# temp file is caught with the CORRECT file:line and exit 1. A gate that
# cannot fail is decoration; this script is the analyzer's own drill.
#
# Pure host, stdlib-only, seconds: copies the analyzed file set to a temp
# root, runs `python -m dllama_tpu.analysis --root` twice.
set -euo pipefail
cd "$(dirname "$0")/.."

tmp="$(mktemp -d)"
trap 'rm -rf "$tmp"' EXIT

# the analyzed file set (Project.from_disk + the gate/doc extras)
cp -r dllama_tpu "$tmp/dllama_tpu"
rm -rf "$tmp"/dllama_tpu/__pycache__ "$tmp"/dllama_tpu/*/__pycache__ \
       "$tmp"/dllama_tpu/*/*/__pycache__ 2>/dev/null || true
cp README.md "$tmp/"
mkdir -p "$tmp/experiments" "$tmp/scripts"
cp experiments/aot_check.py "$tmp/experiments/"
cp scripts/hybrid_smoke.sh scripts/compile_smoke.sh \
   scripts/analysis_smoke.sh scripts/router_smoke.sh \
   scripts/failover_smoke.sh scripts/chaos_soak.sh scripts/fleet_smoke.sh \
   "$tmp/scripts/"

echo "analysis_smoke: pristine copy must pass"
python -m dllama_tpu.analysis --root "$tmp"

# seed one violation per family shape: an off-catalog fault point (line 3
# of the seeded file) and an unscoped cached-jit dispatch (line 11)
seed="$tmp/dllama_tpu/engine/_seeded_violation.py"
cat > "$seed" <<'PY'
import jax
from dllama_tpu.utils import faults
faults.fire("not.a.real.point")


class Seeded:
    def __init__(self):
        self._decode = jax.jit(lambda x: x)

    def decode(self, x):
        return self._decode(x)
PY

echo "analysis_smoke: seeded violations must be caught at file:line"
set +e
out="$(python -m dllama_tpu.analysis --root "$tmp" 2>&1)"
rc=$?
set -e
echo "$out"
[ "$rc" -eq 1 ] || {
    echo "analysis_smoke: expected exit 1 on a seeded violation, got $rc" >&2
    exit 1; }
echo "$out" | grep -q "_seeded_violation.py:3: catalog-fault" || {
    echo "analysis_smoke: catalog-fault not reported at line 3" >&2
    exit 1; }
echo "$out" | grep -q "_seeded_violation.py:11: jit-scope" || {
    echo "analysis_smoke: jit-scope not reported at line 11" >&2
    exit 1; }
echo "analysis_smoke: PASS (pristine clean; seeded catalog-fault + jit-scope caught, exit 1)"
