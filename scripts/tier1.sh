#!/usr/bin/env bash
# Tier-1 verify — the ONE blessed entrypoint for builders and CI: the lint
# gate, then the test command the driver runs after every PR (`commands` in
# its record of the last run: six xdist workers, one file per worker at a
# time, 1,470 s). ROADMAP.md's D11 has the history of that command.
set -o pipefail
cd "$(dirname "$0")/.."

# fast pre-test gate: jaxlint + compileall fail in seconds where a broken
# import would cost minutes of pytest collection
bash scripts/lint.sh || exit 1

rm -rf /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}
echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null)
exit $rc
