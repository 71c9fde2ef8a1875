#!/usr/bin/env sh
# Test tiers (run from anywhere; cd's to the repo root).
#
#   scripts/test.sh          tier-1 verify: the full suite, fail-fast
#                            (the ROADMAP command, run before every PR)
#   scripts/test.sh fast     fast tier: skips @pytest.mark.slow
#                            (compile dry-runs, end-to-end pipelines);
#                            includes the fault/migration suite
#                            (tests/test_faults.py — fault replay
#                            determinism, cross-node settlement, rescue
#                            policies); finishes in well under a minute
#   scripts/test.sh perf     perf tier: benchmarks/perf_suite.py --quick —
#                            correctness gates for the vectorized hot paths
#                            (closed-form decode vs chunked reference, fast
#                            capacitated solver vs min-cost-flow oracle,
#                            warm-start reschedule vs cold solve,
#                            DVFS closed-form frequency choice vs a brute-
#                            force frequency grid, gated-sim energy
#                            conservation: busy+idle+gated+transition ==
#                            total to 1e-9, and decode-boundary preemption:
#                            split additivity of the decode integral plus
#                            end-to-end conservation and the replica-oracle
#                            bound on a preempting multi-replica run, the
#                            migration_settlement gate: a scripted crash
#                            storm under the live auditor — six-bucket
#                            busy+idle+gated+transition+shipping+wasted ==
#                            total to 1e-9, the shipping bucket on the
#                            interconnect closed form, and no-survivor
#                            crashes booking waste instead of leaking —
#                            the checkpoint_settlement gate: checkpointed
#                            prefills telescope exactly onto the unchunked
#                            run, the checkpoint bucket follows the
#                            storage closed form in aggregate, and a
#                            scripted mid-prefill crash restores from the
#                            last durable boundary with seven-bucket
#                            conservation at 1e-9 — the
#                            prefix_cache_settlement gate: warm session
#                            turns charged exactly the telescoped prefix
#                            difference, the cache_read bucket on the
#                            byte closed form, cache-equipped fleets
#                            byte-identical on sessionless traffic, and a
#                            tight-capacity session storm with crash
#                            invalidation holding eight-bucket
#                            conservation under the live auditor —
#                            and the telemetry metrics_overhead gate: with full
#                            telemetry on a governed fleet the ClusterReport
#                            is byte-identical, the Prometheus dump parses,
#                            the live auditor passes every settlement, and
#                            instrumentation costs ≤20% CPU time — the one
#                            timing-sensitive gate, measured min-of-N with
#                            GC paused and retried with backoff so only a
#                            real regression fails every window);
#                            fails on disagreement, not on slow runners
set -e
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

tier="${1:-tier1}"
[ $# -gt 0 ] && shift

case "$tier" in
  fast)  exec python -m pytest -x -q -m "not slow" "$@" ;;
  tier1) exec python -m pytest -x -q "$@" ;;
  perf)  export PYTHONPATH=".:$PYTHONPATH"
         # expose N host-platform XLA devices so jitted kernels and the
         # sharded-engine gates see a multi-device topology even on CPU
         # (REPRO_XLA_DEVICES=N to override; matches the shard counts the
         # sharded_replay gate replays)
         export XLA_FLAGS="--xla_force_host_platform_device_count=${REPRO_XLA_DEVICES:-8}${XLA_FLAGS:+ $XLA_FLAGS}"
         exec python benchmarks/perf_suite.py --quick "$@" ;;
  *)     echo "usage: scripts/test.sh [tier1|fast] [pytest args...]" >&2
         echo "       scripts/test.sh perf [perf_suite args...]" >&2
         exit 2 ;;
esac
