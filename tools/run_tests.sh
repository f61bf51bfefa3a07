#!/bin/bash
# Tier-1 as the driver runs it (its `commands` in /root/TESTS_LAST_RUN.json):
# tests/ without @slow on six xdist workers with --dist load, cut at 1,470 s.
# Prints DOTS_PASSED (junit's tests less errors, failures and skips) and
# WORKERS_DOWN (xdist workers that died) and exits with pytest's code.
# The driver also sets ALLOW_MULTIPLE_LIBTPU_LOAD=1 (several processes may
# then load libtpu for the v5e AOT tests); this file does not set it: put it
# in front of the call here in the sandbox, never on the machine with the chip.
# Further arguments go to pytest. The benchmark's own suite is not in it:
#   python3 -m pytest chipbench/tests -q
set -o pipefail
cd "$(dirname "$0")/.."
out=$(mktemp -d)
timeout -k 10 1470 env JAX_PLATFORMS=cpu \
  python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
  -p no:cacheprovider -p xdist -n 6 --dist load --junitxml="$out/t1.xml" \
  -p no:randomly "$@" 2>&1 | tee "$out/t1.log"
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' "$out/t1.xml" 2>/dev/null \
  | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo "DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$out/t1.log" | tr -cd . | wc -c)}"
echo "WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' "$out/t1.log")"
echo "junit and log: $out"
exit $rc
