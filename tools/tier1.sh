#!/usr/bin/env bash
# Tier-1 verify: the exact gate from ROADMAP.md. CPU-only, excludes
# @pytest.mark.slow, survives collection errors, and prints DOTS_PASSED
# (count of '.' in pytest progress lines) so a harness can diff pass
# counts across revisions even when the exit code is nonzero.
#
# Usage: tools/tier1.sh            (from the repo root)
#        TFDE_GRAD_TRANSPORT=int8 tools/tier1.sh
#                                  (re-run the whole suite with the
#                                   quantized gradient exchange as the
#                                   default transport — parallel/comms.py;
#                                   non-DP meshes warn-fallback to fp32)
#        TFDE_OPT_SHARDING=shard tools/tier1.sh
#                                  (re-run with ZeRO weight-update
#                                   sharding as the default —
#                                   parallel/zero.py; ineligible meshes/
#                                   optimizers warn-fallback to
#                                   replicated, and parity-pinning tests
#                                   request 'replicated' explicitly)
#        TFDE_PREFIX_CACHE=on tools/tier1.sh
#                                  (re-run with the serving prefix-KV
#                                   cache enabled by default on every
#                                   ContinuousBatcher —
#                                   inference/prefix_cache.py; greedy
#                                   outputs are pinned bit-identical, so
#                                   the whole suite doubles as the
#                                   cache-on parity sweep. Also accepts
#                                   an integer byte budget.)
#        TFDE_TRACE=on tools/tier1.sh
#                                  (re-run with per-request distributed
#                                   tracing recording into every
#                                   process's ring —
#                                   observability/trace.py; greedy
#                                   outputs are unaffected by design, so
#                                   the whole suite doubles as the
#                                   tracing-on parity sweep. Also
#                                   accepts an integer ring capacity.)
#        TFDE_MEMWATCH=full tools/tier1.sh
#                                  (re-run with the memory ledger in
#                                   AOT-measured mode — every registered
#                                   program is lowered+compiled for XLA's
#                                   memory_analysis instead of the free
#                                   eval_shape estimate —
#                                   observability/memwatch.py; 'off'
#                                   disables the ledger entirely)
#        TFDE_ELASTIC=on tools/tier1.sh
#                                  (re-run with elastic topology-change
#                                   handling enabled by default in every
#                                   Supervisor — resilience/elastic.py;
#                                   the dedicated drills in
#                                   tests/test_elastic.py and
#                                   tests/test_multiprocess.py enable it
#                                   explicitly either way)
#        TFDE_ADMIT_MAX_QUEUE=8 tools/tier1.sh
#                                  (re-run the whole suite with serving
#                                   admission caps armed by default —
#                                   inference/admission.py; 0 = off.
#                                   TFDE_ADMIT_MAX_QUEUED_TOKENS and
#                                   TFDE_ADMIT_TTFT_DEADLINE_MS forward
#                                   the same way; the overload drills in
#                                   tests/test_server.py and
#                                   tests/test_multiprocess.py arm them
#                                   explicitly either way)
#        TFDE_BROWNOUT_BURN=2 tools/tier1.sh
#                                  (router brownout burn-rate thresholds
#                                   — inference/router.py; _BATCH is the
#                                   level-2 threshold that also sheds
#                                   the batch class)
#        TFDE_ADMIT_KV_HEADROOM=2 tools/tier1.sh
#                                  (re-run with the KV-headroom admission
#                                   gate armed by default — reject with
#                                   429 + a kv payload once the capacity
#                                   model says fewer than N free rows
#                                   remain; observability/capacity.py +
#                                   inference/admission.py; 0 = off. The
#                                   dedicated drills in
#                                   tests/test_server.py arm it
#                                   explicitly either way.)
#        TFDE_USAGE_LOG=on tools/tier1.sh
#                                  (re-run with per-request usage
#                                   metering journaled to
#                                   model_dir/metrics/usage_<host>.jsonl
#                                   on every router replica —
#                                   observability/capacity.py; counters
#                                   publish either way, only the JSONL
#                                   is gated. TFDE_CAPACITY_BUDGET_BYTES
#                                   forwards the same way and pins the
#                                   headroom model's memory budget.)
#        TFDE_PAGED_KV=on tools/tier1.sh
#                                  (re-run with the block-granular paged
#                                   KV pool enabled by default on every
#                                   ContinuousBatcher — inference/paged.py;
#                                   greedy outputs are pinned
#                                   bit-identical to the dense slab, so
#                                   the whole suite doubles as the
#                                   paged-on parity sweep.
#                                   TFDE_KV_BLOCK forwards the same way
#                                   and must match the prefix trie's
#                                   chunk size.)
#        TFDE_KV_QUANT=int8 tools/tier1.sh
#                                  (re-run with the int8 quantized KV
#                                   cache enabled by default on every
#                                   ContinuousBatcher — ops/quant.py +
#                                   inference/decode.py; blockwise int8
#                                   payload + fp32 scale sidecars,
#                                   dequantized inside the fused
#                                   attention tick. Greedy parity is
#                                   statistical (>=0.98), not
#                                   bit-exact, so the parity-pinning
#                                   tests request 'fp' explicitly.
#                                   TFDE_KV_DEFRAG_THRESHOLD forwards
#                                   the same way: pool fragmentation
#                                   fraction above which an admission
#                                   stall triggers a compaction pass
#                                   (default 0.5; 0 = off).)
#        TFDE_BOOT_READY_REQUIRE=off tools/tier1.sh
#                                  (re-run with the router's readiness
#                                   gate disabled — traffic places on
#                                   any live replica regardless of its
#                                   boot state, the pre-PR-17 behaviour;
#                                   observability/boot.py +
#                                   inference/router.py.
#                                   TFDE_BOOT_READY_GRACE_S forwards
#                                   the same way: seconds a never-ready
#                                   booting replica is shielded from
#                                   the staleness down-marker.)
#
# Also prints DOTS_DELTA (this run's DOTS_PASSED minus the previous
# run's, from /tmp/_t1.passed) so a regression is visible at a glance
# without diffing logs by hand.
set -o pipefail
cd "$(dirname "$0")/.." || exit 1
# no persistent compile cache under the gates: memgate and the suite pin
# compile counts and seconds, which a cache hit would change
export JAX_ENABLE_COMPILATION_CACHE=false

rm -f /tmp/_t1.log
# 30 min: the suite has grown a subsystem per PR — PR 10's memwatch
# default-on registrations pushed a loaded box past the old 1140s
# budget (a fully-green run was killed at 93%), and the boot/readiness
# drills (a third cold-booting replica child in the kill drill) pushed
# a loaded box past 1440s (killed at ~70%)
timeout -k 10 1800 env JAX_PLATFORMS=cpu \
    TFDE_GRAD_TRANSPORT="${TFDE_GRAD_TRANSPORT:-fp32}" \
    TFDE_OPT_SHARDING="${TFDE_OPT_SHARDING:-replicated}" \
    TFDE_PREFIX_CACHE="${TFDE_PREFIX_CACHE:-off}" \
    TFDE_TRACE="${TFDE_TRACE:-off}" \
    TFDE_MEMWATCH="${TFDE_MEMWATCH:-on}" \
    TFDE_ELASTIC="${TFDE_ELASTIC:-off}" \
    TFDE_ADMIT_MAX_QUEUE="${TFDE_ADMIT_MAX_QUEUE:-0}" \
    TFDE_ADMIT_MAX_QUEUED_TOKENS="${TFDE_ADMIT_MAX_QUEUED_TOKENS:-0}" \
    TFDE_ADMIT_TTFT_DEADLINE_MS="${TFDE_ADMIT_TTFT_DEADLINE_MS:-0}" \
    TFDE_BROWNOUT_BURN="${TFDE_BROWNOUT_BURN:-8}" \
    TFDE_BROWNOUT_BURN_BATCH="${TFDE_BROWNOUT_BURN_BATCH:-16}" \
    TFDE_ADMIT_KV_HEADROOM="${TFDE_ADMIT_KV_HEADROOM:-0}" \
    TFDE_USAGE_LOG="${TFDE_USAGE_LOG:-off}" \
    TFDE_CAPACITY_BUDGET_BYTES="${TFDE_CAPACITY_BUDGET_BYTES:-0}" \
    TFDE_PAGED_KV="${TFDE_PAGED_KV:-off}" \
    TFDE_KV_QUANT="${TFDE_KV_QUANT:-fp}" \
    TFDE_KV_DEFRAG_THRESHOLD="${TFDE_KV_DEFRAG_THRESHOLD:-0.5}" \
    TFDE_BOOT_READY_REQUIRE="${TFDE_BOOT_READY_REQUIRE:-on}" \
    TFDE_BOOT_READY_GRACE_S="${TFDE_BOOT_READY_GRACE_S:-120}" \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    --durations=10 \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
passed=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
echo DOTS_PASSED=$passed

# Roofline tile-visit gate: pins the flash kernels' executed tile schedule
# (forward pl.when predication + backward in-band pair scan) against the
# analytic band, so an attention tile-count regression fails tier-1 the
# same way a collective-count regression does (tools/roofline.py).
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python tools/roofline.py --check-tiles; then
    echo "ROOFLINE_TILE_GATE=fail"
    [ $rc -eq 0 ] && rc=1
else
    echo "ROOFLINE_TILE_GATE=pass"
fi
# Memory & compile gate: one deterministic train+serve workload, per-site
# jit-cache-miss counts and per-program peak bytes pinned against the
# checked-in baseline (tools/memgate_baseline.json). A pad-ladder compile
# regression or an HBM blow-up fails tier-1 here; re-baseline a
# deliberate change with: python tools/memgate.py --update
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
    TFDE_MEMWATCH="${TFDE_MEMWATCH:-on}" \
    python tools/memgate.py --check; then
    echo "MEMGATE=fail"
    [ $rc -eq 0 ] && rc=1
else
    echo "MEMGATE=pass"
fi
# Static-analysis gate: hlolint census of every hot program (train-step
# transport x sharding matrix, decode scan, cold/warm/primed prefill)
# diffed exactly against tools/lintgate_baseline.json, plus the project
# lint (lock discipline, greedy-split ban, TFDE_* knob audit). An extra
# collective, a dropped donation alias, a stray host callback, an
# unlocked threaded write or an unregistered knob fails tier-1 here;
# re-baseline a deliberate change with: python tools/lintgate.py --update
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python tools/lintgate.py --check; then
    echo "LINTGATE=fail"
    [ $rc -eq 0 ] && rc=1
else
    echo "LINTGATE=pass"
fi
# Injection self-test: seed a host-callback program and a dropped
# donation through the real linter — the gate must FAIL, proving it bites
# (the memgate TFDE_MEMGATE_INJECT drill's static-analysis sibling).
if timeout -k 10 420 env JAX_PLATFORMS=cpu TFDE_LINTGATE_INJECT=1 \
    python tools/lintgate.py --check >/dev/null 2>&1; then
    echo "LINTGATE_INJECT=fail (seeded violations did not fail the gate)"
    [ $rc -eq 0 ] && rc=1
else
    echo "LINTGATE_INJECT=pass"
fi
# Perf trendline gate: every committed BENCH_*.json parsed in round order
# and the latest comparable capture diffed per-metric against the
# direction/slack policy (tools/trendgate_policy.json). A hardware capture
# that regressed a gated metric past its slack fails tier-1 here;
# re-render the report after a deliberate change with:
# python tools/trendgate.py --update
if ! timeout -k 10 120 env JAX_PLATFORMS=cpu \
    python tools/trendgate.py --check; then
    echo "TRENDGATE=fail"
    [ $rc -eq 0 ] && rc=1
else
    echo "TRENDGATE=pass"
fi
# Injection self-test: synthesize a latest capture with every gated metric
# regressed past 2x slack — the gate must FAIL, proving it bites.
if timeout -k 10 120 env JAX_PLATFORMS=cpu TFDE_TRENDGATE_INJECT=1 \
    python tools/trendgate.py --check >/dev/null 2>&1; then
    echo "TRENDGATE_INJECT=fail (seeded regression did not fail the gate)"
    [ $rc -eq 0 ] && rc=1
else
    echo "TRENDGATE_INJECT=pass"
fi
if [ -f /tmp/_t1.passed ]; then
    prev=$(cat /tmp/_t1.passed)
    echo DOTS_DELTA=$((passed - prev))
fi
echo "$passed" > /tmp/_t1.passed
exit $rc
