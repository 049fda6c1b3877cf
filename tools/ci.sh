#!/usr/bin/env bash
# CI driver (reference: .travis.yml:23-40 runs go test -> C++ unit/
# integration -> strategy sweep -> python op/optimizer/train tests; the
# cluster workflow adds a two-node elastic test).  This is the one entry
# point that runs this repo's whole pyramid:
#
#   0. kfcheck static analysis (SPMD/TPU hazard rules, tools/kfcheck;
#      fails on any non-baselined finding)     (~1 s)
#   1. native build + C++ selftest            (~20 s)
#   2. pytest suite, sharded across N workers (~15-20 min at -j2 on the
#      1-core dev VM; ~35 min serial — the suite is full of sleeps and
#      subprocess waits, so sharding pays even without cores), then the
#      serial perf tier and the kfchaos smoke scenario (full run only)
#   3. the driver's dryrun_multichip on a virtual 8-device CPU mesh
#      (multi-chip shardings compile + execute, incl. the multi-process
#      elastic resize)                        (~3-5 min)
#
# Wall-clock budget: ~25 min at the default -j2.  Usage:
#
#   tools/ci.sh            # everything
#   tools/ci.sh -j4        # more pytest shards
#   tools/ci.sh --fast     # native + one smoke shard + dryrun (~8 min)
set -u
set -o pipefail
cd "$(dirname "$0")/.."

JOBS=2
FAST=0
for a in "$@"; do
  case "$a" in
    -j*) JOBS="${a#-j}" ;;
    --fast) FAST=1 ;;
    *) echo "unknown arg $a" >&2; exit 2 ;;
  esac
done

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"

fail=0
say() { printf '\n==== %s ====\n' "$*"; }

say "0/3 kfcheck static analysis"
# --fast scopes the per-file rules to git-changed files; the
# whole-program passes (lock/knob/metrics/chaos, the phase-3 dataflow
# family: use-after-donate, sharding-mismatch, host-roundtrip-traced,
# and the phase-4 protocol family: lock-ordering, wal-discipline,
# version-fence, seqlock-shape, thread-lifecycle) always cover the
# full tree via the fact cache
if [ "$FAST" = 1 ]; then
  python -m tools.kfcheck --fast || exit 1
else
  python -m tools.kfcheck || exit 1
fi
# docs/knobs.md is generated from the typed registry
# (kungfu_tpu/utils/knobs.py); a stale commit means someone edited one
# without the other — `make knobs-docs` regenerates
python tools/gen_knob_docs.py --check || exit 1

# metrics/trace/doctor smoke (`make doctor-smoke`): a real /metrics
# endpoint scraped over HTTP, the kftrace merger over a 2-worker
# fixture, a watcher /findings endpoint attributing a step-time skew,
# and the kft-doctor CLI over a saved history (~5 s; docs/monitoring.md)
say "0b/3 metrics + trace + doctor smoke"
python tools/metrics_trace_smoke.py || exit 1

# kfsnap micro-bench smoke: the async zero-copy commit path must hold
# >= 3x the legacy per-leaf path's end-to-end throughput with a
# bit-identical restore (~5 s; docs/elastic.md "Async commit pipeline")
say "0c/3 kfsnap snapshot micro-bench"
python tools/bench_snapshot.py --smoke || exit 1

# kfprof smoke (`make prof-smoke`): the device-time attribution plane
# on CPU — published phases must sum to wall time within 10%, a
# /profile capture must round-trip artifacts, and the breakdown table +
# BENCH-compatible JSON block must render (~15 s; docs/monitoring.md
# "Profiling (kfprof)")
say "0d/3 kfprof report smoke"
python tools/kfprof_report.py --smoke || exit 1

# kfsim smoke (`make sim-smoke`): a 20-fake-worker rolling preemption
# wave under the REAL watcher + config server + invariant sweep — the
# control-plane chaos tier.  Runs the lite (no-jax) worker, so unlike
# 2c-2e it has NO data-plane gate and must never self-skip: a red here
# is a red on every image (~10 s; docs/chaos.md "Simulation tier")
say "0e/3 kfsim control-plane smoke"
python -m kungfu_tpu.chaos.runner --scenario sim-smoke || exit 1

# kfload smoke (`make load-smoke`): spawn a tiny CPU serving server,
# sweep 3 open-loop Poisson rungs with client-side TTFT/TPOT timing,
# and assert the whole serving observability loop — SERVING_BENCH.json
# shape, SLO gauges on /metrics, the /requests journal, and a
# kftrace+kfrequests Chrome-trace merge round-trip.  Single-process
# CPU jax: no data-plane gate, must never self-skip (~45 s;
# docs/serving.md "SLOs, the request journal and kfload")
say "0f/3 kfload serving SLO smoke"
python tools/kfload.py --smoke || exit 1

# kfnet smoke (`make net-smoke`): two in-process workers with real
# MetricsServers, a real ModelStore save/load for the state-movement
# ledger, per-peer transfers both directions — asserts the aggregated
# /cluster_metrics matrix carries nonzero egress AND ingress links,
# the ledger families render, and the --history path round-trips.
# Pure CPU, no data-plane gate, must never self-skip (~5 s;
# docs/monitoring.md "Transport (kfnet)")
say "0g/3 kfnet transport observability smoke"
python tools/kfnet_report.py --smoke || exit 1

# kfpolicy smoke (`make policy-smoke`): two live workers with a 10x
# step-time skew behind a real watcher debug server — asserts exactly
# one shadow exclusion proposal naming the slow worker (hysteresis
# build-up logged, no flapping), the fsync'd JSONL ledger, the
# /decisions endpoint shape, and `kft-policy --history` replay
# identity (the actuation gate).  Pure CPU, no data-plane gate, must
# never self-skip (~10 s; docs/policy.md)
say "0h/3 kfpolicy shadow-decision smoke"
python tools/kfpolicy.py --smoke || exit 1
# the shadow->act contract (docs/policy.md) requires every
# control-plane write to be version-fenced; run the focused pass here,
# next to the policy smoke, so a fencing regression is named at the
# step that owns the contract (warm fact cache: ~0.3 s)
python -m tools.kfcheck --program --pass version-fence || exit 1

# kfact smoke (`make act-smoke`): the policy plane ACTING, not
# shadowing — an 8-proc sim where the executor excludes the one
# straggler through a real fenced CAS (exactly one executed action,
# config churn bounded at 2 versions, decision-replay bit-identity
# preserved), then the kill-mid-action chaos scenario: SIGKILL between
# the action-WAL intent append and the CAS, restart idempotently
# completes under the ORIGINAL fence (exactly once), and a concurrent
# membership move fences the stale intent into a journaled no-op.
# Pure CPU, no data-plane gate, must never self-skip (~60 s;
# docs/policy.md "Actuation")
say "0h2/3 kfact actuation + kill-mid-action smoke"
python -m kungfu_tpu.chaos.runner --scenario sim-policy-act-smoke || exit 1
python -m kungfu_tpu.chaos.runner --scenario policy-act-kill || exit 1

# kffleet smoke (`make serve-sim-smoke`): a 4-replica fake serving
# fleet under the REAL watcher + config server, driven by a seeded
# diurnal arrival trace with forced preempt/re-admit — asserts the
# serving-journal conservation invariants (finished + evicted ==
# submitted, no open requests at drain), the fleet gauges on the
# aggregator, and the min_served floor.  Lite (no-jax) replicas: NO
# data-plane gate, must never self-skip (~15 s; docs/serving.md
# "Fleet observability")
say "0i/3 kffleet sim-serving fleet smoke"
python -m kungfu_tpu.chaos.runner --scenario sim-serve-smoke || exit 1

say "1/3 native build + selftest"
make -C native all selftest || exit 1
./native/selftest || exit 1

# kffast + kftree smoke (`make p2p-smoke`): one small 2-worker p2p
# bench pass over the just-built native plane — asserts the shm lane
# engaged (shm_lane_bytes > 0), the segment-mapped copy beats the
# legacy socket wire, chunk streaming holds against per-chunk RPCs,
# the buffer-pool fresh-alloc regression pin — plus one 4-puller
# fanout wave over an emulated finite link pinning the kftree relay
# tree at >= 1.5x faster than the direct star (~30 s; docs/elastic.md
# "Store fast lane" / "Distribution trees")
say "1b/3 kffast p2p fast-lane + kftree fanout smoke"
python tools/bench_p2p.py --smoke || exit 1

say "2/3 pytest (${JOBS} shards)"
if [ "$FAST" = 1 ]; then
  python -m pytest tests/test_end_to_end.py tests/test_session.py \
      tests/test_plan.py -q || fail=1
else
  # shard by file, round-robin after sorting by size (crude balance:
  # big files spread across shards)
  mapfile -t FILES < <(ls -S tests/test_*.py)
  pids=()
  for ((s = 0; s < JOBS; s++)); do
    shard=()
    for ((i = s; i < ${#FILES[@]}; i += JOBS)); do
      shard+=("${FILES[$i]}")
    done
    # per-shard port window, OFF the library default (31100) so shards
    # collide neither with each other nor with a concurrent manual run
    # using defaults: the windows of tests/testutil.py, which the xdist
    # workers of a plain pytest run get too (tests/conftest.py)
    ( KFT_BASE_PORT=$(python -c "import sys; sys.path.insert(0, 'tests')
from testutil import window_base_port
print(window_base_port('gw$s'))") \
        python -m pytest "${shard[@]}" -q \
        > "/tmp/kft-ci-shard-$s.log" 2>&1 ) &
    pids+=($!)
  done
  for ((s = 0; s < JOBS; s++)); do
    if ! wait "${pids[$s]}"; then
      fail=1
      echo "shard $s FAILED:"
    fi
    tail -3 "/tmp/kft-ci-shard-$s.log"
  done

  # perf tier, SERIAL on the now-quiet box: timing assertions that
  # self-skip under shard load (they would otherwise be unenforced
  # exactly when CI is busiest); KFT_PERF_ENFORCE makes the load gate
  # wait-then-measure instead of skip
  say "2b/3 perf tier (serial)"
  KFT_PERF_ENFORCE=1 python -m pytest \
      tests/test_pipeline.py::test_pp_bubble_sweep_harness -q || fail=1

  # kfchaos smoke: SIGKILL a rank inside the collective commit, assert
  # every elastic contract (docs/chaos.md).  Full run only; self-skips
  # (rc 0) on images whose jax lacks the multiprocess CPU data plane.
  say "2c/3 kfchaos smoke scenario"
  python -m kungfu_tpu.chaos.runner --scenario smoke || fail=1

  # kfguard proof: SIGKILL + restart the WAL-backed config server
  # mid-resize; version/epoch must strictly continue
  # (check_version_monotonic_across_epochs) and --replay-check requires
  # two runs with identical fault journals.  Same data-plane self-skip
  # as the rest of the matrix.
  say "2d/3 kfchaos config-server crash-restart (kfguard WAL)"
  python -m kungfu_tpu.chaos.runner \
      --scenario config-server-crash-restart-mid-resize \
      --replay-check || fail=1

  # kfdoctor proof: delay ONE rank at every fence; the doctor sampler
  # scraping live worker /metrics must raise a straggler finding naming
  # exactly that rank — and its clean twin must stay silent (the
  # false-positive guard).  Same data-plane self-skip as above.
  say "2e/3 kfchaos straggler-doctor attribution (+ clean twin)"
  python -m kungfu_tpu.chaos.runner --scenario straggler-doctor || fail=1
  python -m kungfu_tpu.chaos.runner \
      --scenario straggler-doctor-clean || fail=1

  # SLO doctor proof: delay every serving admission on a LIVE CPU
  # serving server; the doctor scraping its /metrics must raise an
  # slo-violation finding naming the instance (queue-dominated burn),
  # and the clean twin must stay silent.  Serving tier = single-process
  # CPU jax: no data-plane gate, never self-skips (docs/serving.md).
  say "2f/3 kfchaos slo-doctor (+ clean twin)"
  python -m kungfu_tpu.chaos.runner --scenario slo-doctor || fail=1
  python -m kungfu_tpu.chaos.runner --scenario slo-doctor-clean || fail=1
fi

say "3/3 dryrun_multichip(8)"
DRYRUN_DEVICES=8 python __graft_entry__.py || fail=1

if [ "$fail" = 0 ]; then
  say "CI PASSED"
else
  say "CI FAILED"
fi
exit $fail
