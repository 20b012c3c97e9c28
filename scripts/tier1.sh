#!/usr/bin/env bash
# Tier-1 verify as the driver runs it after a PR (the `commands` of
# /root/TESTS_LAST_RUN.json): six xdist workers, a file to a worker, a
# 1,470 s limit (the suite takes about 600 s), and the count of passes read
# from the junit report (the dots of the log where there is none). The
# driver also exports ALLOW_MULTIPLE_LIBTPU_LOAD=1 for its own run; no file
# of the repo sets it (only tests/test_tpu_lowering.py loads libtpu, in one
# worker).
# ROADMAP.md's "Tier-1 verify" line is the older serial form, which does not
# finish inside its 870 s.
#
# Usage: scripts/tier1.sh            (from the repo root)
# Log:   ${TMPDIR:-/tmp}/_t1.log, junit report beside it as _t1.xml
set -o pipefail
log=${TMPDIR:-/tmp}/_t1.log xml=${TMPDIR:-/tmp}/_t1.xml
rm -rf "$log" "$xml"
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml="$xml" -p no:randomly 2>&1 | tee "$log"
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' "$xml" 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$log" | tr -cd . | wc -c)}
echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' "$log" 2>/dev/null)
exit $rc
