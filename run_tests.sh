#!/usr/bin/env bash
# Test harness entry point (reference test/run_tests.sh).
#
# The reference started a real 2-worker Spark Standalone cluster, ran
# `python -m unittest discover`, and tore it down (reference
# test/run_tests.sh:15-22).  Here the equivalents are built into the suite
# itself: tests/conftest.py arms an 8-device virtual CPU mesh, the
# process-backed pyspark shim (tests/sparkshim) provides separate executor
# processes, and tests/test_multiprocess.py spawns real multi-process
# jax.distributed worlds.
#
# Usage:
#   ./run_tests.sh            # full suite (~27 min on 8 CPU cores, incl.
#                             # all example-CLI integration runs)
#   ./run_tests.sh -m 'not slow'   # fast subset, ~5 min — every framework
#                                  # module; 'slow' marks the example/cluster
#                                  # integration runs (each boots multi-
#                                  # process clusters in subprocesses)
#   ./run_tests.sh tests/test_cluster.py   # one file
set -euo pipefail
cd "$(dirname "$0")"

# capture the exit code without tripping `set -e` (a bare `rc=$?` after a
# failing pytest would never run: -e aborts the script on the failure, and
# the gates below must execute either way)
rc=0
python -m pytest tests/ -q --durations=10 "$@" || rc=$?

# the driver gates: compile-check the graft entry + the multi-chip dry run,
# prove the elastic-recovery loop closes on a real 3-node cluster, prove
# the telemetry plane produces parseable traces + HBEAT counters, prove
# the data service keeps its exactly-once guarantee through a worker
# SIGKILL (dispatcher + 2 worker subprocesses + 2 consumers), prove the
# step loop overlaps: guard-clean device-resident dispatches, async
# checkpoint saves, and dispatch-gap counters reaching the driver, then
# prove the observatory answers live: /metrics + /status scrapeable
# mid-run with the MFU/goodput accountant, counters monotone, and trace
# flow events linking a data-service split to a consumer-side dispatch,
# then prove the device plane hands over its traces: a mid-run GET
# /profile collecting every node's device trace to the driver, and
# analyze_profile.py merging them
# with the host traces into one Perfetto timeline, and finally prove the
# watchtower catches an injected straggler and an injected NaN loss live
# (correctly attributed on /alerts, /metrics, /status and as trace
# instants) and that metrics_replay.py re-derives the same alerts from
# the on-disk journal after the cluster is gone, and prove the caching
# tier pays: 2 cache-armed worker subprocesses serving a 2-epoch job with
# >=90% epoch-2 cache hits, compressed colv1 frames, and a nonzero
# wire-compression ratio on a live /metrics scrape, and prove the serving
# gateway survives chaos: 2 replica subprocesses under concurrent client
# load, the pinned replica SIGKILLed mid-run and fenced by heartbeat
# timeout, zero accepted requests lost across the failover, and the
# serving telemetry (nonzero tfos_serving_p99_us / tfos_serving_batch_fill
# plus a live latency_slo_burn alert) on /metrics and /alerts, and prove
# the warm-start compile plane: a SIGKILLed worker's replacement rejoins
# with a deserialized (never retraced) step executable, compile debt a
# small fraction of the cold nodes', exact element totals preserved, and
# nonzero tfos_compile_cache_hit_total on a live /metrics scrape, and
# prove the multi-tenant tier survives chaos: two consumer runs attached
# to ONE shared 2-epoch job, the journaled dispatcher subprocess
# SIGKILLed and restarted mid-run on the same port, exact element totals
# with zero duplicates across the crash, and nonzero
# tfos_dataservice_cache_hit_total plus the affinity hit-rate on a live
# /metrics scrape, and finally prove the autopilot closes the loop live:
# a 2-node cluster with prefetch pinned low gets its depth raised by the
# controller mid-run, the measured starvation wall-fraction drops, every
# action lands in the journal and on /autopilot, and metrics_replay.py
# re-derives the action stream offline, and prove the control plane
# itself survives: the primary reservation server is stalled then
# SIGKILLed mid-run, the warm standby promotes off the journal under a
# bumped fencing epoch, the zombie's writes are rejected by epoch, nodes
# re-home via endpoint-list redial with exact item totals and no healthy
# node false-fenced during the takeover grace window, and prove the
# megastep engine amortizes: a 2-node cluster under
# TFOS_TRANSFER_GUARD=disallow runs guard-clean K=4 grouped dispatches
# with device-side stack assembly and donated stacks, a live
# train_steps_per_call=8 push through node.apply_knobs lands exactly on a
# group boundary (whole-group step deltas, steps_per_call gauge), every
# row trains exactly once, and warm host+dispatch wall per step through
# multi_step(8) is measurably below the single-step path's, and prove
# the remediator closes the detect→act loop: a 3-node cluster with an
# injected straggler and a saturated data plane sees the watchtower
# name both, the remediator evict the straggler (graceful SIGTERM
# drain, slot release, elastic replacement admitted) and scale out a
# feed worker, with exact consumer totals and zero operator input, the
# journal holding the full proposed→applied→effect chain re-derivable
# by metrics_replay.py — then a NaN batch injected mid-train trips the
# nonfinite rule and the remediator rolls back past the poisoned step
# (quarantined .corrupt) to completion — and prove the request plane
# explains itself: two traced replicas (one with an injected 50ms
# dispatch stall) serve four concurrent clients, per-stage latency
# histograms re-add to the e2e sum on /metrics, /slow names the stalled
# requests by client-minted id, slo_budget_burn pages the slow replica
# only, the merged timeline stitches cross-process request flows, and
# metrics_replay.py re-derives the identical verdicts from the journal,
# and finally prove the model fleet holds: a 3-model registry-resolved
# fleet (2 replicas each) under concurrent multi-model clients sees a
# poisoned beta@2 (finite params, overflowing matmuls) canaried onto one
# replica and auto-rolled-back off the version-labeled nonfinite signal,
# then a real fit_supervised run publishes beta@3 through the
# train-to-serve handoff and the canary controller walks it to live on
# every replica — zero accepted requests lost, every answer numerically
# traceable to a published version, serving_compiles flat through both
# swaps (weight flips never recompile), client p99 flat, /fleet serving
# the control-plane state, and fleet.replay_journal re-deriving the
# exact promote/rollback stream from the canary journal
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
python scripts/ci_assert_elastic.py
python scripts/ci_assert_telemetry.py
python scripts/ci_assert_dataservice.py
python scripts/ci_assert_cache.py
python scripts/ci_assert_overlap.py
python scripts/ci_assert_observatory.py
python scripts/ci_assert_profiling.py
python scripts/ci_assert_watchtower.py
python scripts/ci_assert_serving.py
python scripts/ci_assert_warmstart.py
python scripts/ci_assert_shared.py
python scripts/ci_assert_autopilot.py
python scripts/ci_assert_ha.py
python scripts/ci_assert_megastep.py
python scripts/ci_assert_remediator.py
python scripts/ci_assert_reqtrace.py
python scripts/ci_assert_fleet.py

exit $rc
