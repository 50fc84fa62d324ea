#!/usr/bin/env bash
# Tier-1: the test command the driver runs, then the three gate steps.
# Usage: tools/tier1.sh            (from anywhere; a parity sweep is the
#        TFDE_PAGED_KV=on tools/tier1.sh   shell's own variable passing)
# No test runs longer than tests/conftest.py's TEST_LIMIT_S, and the
# subprocess drills assert on counts they control: a second suite beside
# this one makes it slower (about twice), not red.
set -o pipefail
cd "$(dirname "$0")/.." || exit 1
rm -rf /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
said=$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' \
    /tmp/_t1.xml 2>/dev/null | head -n 1 |
    awk '{n=$1-$2-$3-$4; print (n<0 ? 0 : n)}')
echo DOTS_PASSED=${said:-$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)}
echo WORKERS_DOWN=$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null)

# The gates pin compile counts, which a persistent cache hit would change.
gate() {  # gate NAME WANT_EXIT_0(0|1) command...
    local name=$1 want_pass=$2; shift 2
    if timeout -k 10 420 env JAX_PLATFORMS=cpu \
        JAX_ENABLE_COMPILATION_CACHE=false "$@" >/tmp/_t1.gate 2>&1
    then local passed=1; else local passed=0; fi
    if [ $passed -eq "$want_pass" ]; then echo "$name=pass"
    else echo "$name=fail"; tail -n 20 /tmp/_t1.gate; [ $rc -eq 0 ] && rc=1
    fi
}
gate MEMGATE 1 python tools/memgate.py --check
gate LINTGATE 1 python tools/lintgate.py --check
# the seeded violations must fail the gate, or it gates nothing
gate LINTGATE_INJECT 0 env TFDE_LINTGATE_INJECT=1 python tools/lintgate.py --check
exit $rc
