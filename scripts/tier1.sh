#!/usr/bin/env bash
# Tier-1 verify — the command the driver holds every PR to, verbatim (its
# last run's `commands` in TESTS_LAST_RUN.json: six xdist workers, `--dist
# loadfile`, a 1,470 s limit, a junit file), wrapped so builders run the same
# line. ROADMAP.md's "Tier-1 verify" line is the older serial 870 s form of
# it and has not finished since PR 40; this is the one that counts. Prints
# DOTS_PASSED (passes, from the junit file) and WORKERS_DOWN, and exits with
# pytest's status (124 = the limit cut the run: it then counts only as far
# as it got). tests/conftest.py hands the heaviest files out first.
cd "$(dirname "$0")/.." || exit 1
set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); exit $rc
