#!/usr/bin/env bash
# Tier-1 CI: test suite, plus one optional smoke run.
#
#   scripts/ci.sh                     # non-slow tests
#   scripts/ci.sh --full              # include the slow multi-device subprocess tests
#   scripts/ci.sh --sweep-smoke       # also run a 16-seed chaos sweep (vmapped jit, CPU)
#   scripts/ci.sh --colocation-smoke  # also run a 4-job 16-seed sharded co-location sweep
#   scripts/ci.sh --config-smoke      # also run a small (seeds × configs) resiliency grid
#   scripts/ci.sh --sparse-smoke      # also run a sharded config grid through the COMPACT
#                                     # (sparse-phase) tick over a deep-pipeline arena
#   scripts/ci.sh --ha-smoke          # also run the hybrid-replication-vs-checkpoint cube
#                                     # (brownouts + MQ outage + region burst, compact tick,
#                                     # non-zero exit on any timeline-rebuild fallback)
#   scripts/ci.sh --drill-smoke       # also run the deployment-drill cube (canary/rolling
#                                     # upgrades + in-trace auto-rollback, compact tick;
#                                     # non-zero exit on timeline-rebuild fallback OR on the
#                                     # induced regression failing to fire the rollback)
#   scripts/ci.sh --traffic-smoke     # also run the traffic-dynamics cube (diurnal/flash
#                                     # rate schedules + in-trace DS2 autoscaling, compact
#                                     # tick; non-zero exit on timeline-rebuild fallback OR
#                                     # on the oscillation drill failing to latch the
#                                     # thrash guard)
#   scripts/ci.sh --serve-smoke       # also boot the in-process SweepService: two
#                                     # concurrent deployment-drill requests + a traffic
#                                     # sweep with incremental chunk results; non-zero exit
#                                     # if the first chunk fails to land before the slowest
#                                     # request completes, if the requests fail to share a
#                                     # compiled trace (zero cache hits), or on any
#                                     # chunked-vs-monolithic parity drift
#
# Smoke targets fail LOUDLY on silent lowering fallbacks: the sparse
# smoke exports REPRO_REQUIRE_PHASE_MODE=compact (the engine refuses to
# lower dense under it) and examples/sparse_sweep.py exits non-zero if
# the auto selector or the ckpt-grid refit degrade.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests =="
if [[ "${1:-}" == "--full" ]]; then
  python -m pytest -x -q
else
  python -m pytest -x -q -m "not slow"
fi

if [[ "${1:-}" == "--sweep-smoke" ]]; then
  echo "== chaos-sweep smoke: 16 seeds, one vmapped jit call =="
  python examples/chaos_sweep.py --seeds 16 --duration 60
fi

if [[ "${1:-}" == "--colocation-smoke" ]]; then
  echo "== co-location smoke: 4 jobs, 16 seeds, 2 device shards =="
  python examples/colocation_sweep.py --jobs 4 --seeds 16 --duration 60 --devices 2
fi

if [[ "${1:-}" == "--config-smoke" ]]; then
  echo "== config-grid smoke: 2x2 resiliency grid x 8 seeds, one (C,S) jit call =="
  python examples/config_sweep.py --restarts 2 --intervals 2 --seeds 8 --duration 60
fi

if [[ "${1:-}" == "--sparse-smoke" ]]; then
  echo "== sparse smoke: compact-phase ckpt grid x 8 seeds, 2 device shards =="
  REPRO_REQUIRE_PHASE_MODE=compact \
    python examples/sparse_sweep.py --jobs 18 --configs 2 --seeds 8 \
      --duration 60 --devices 2 --ckpt
fi

if [[ "${1:-}" == "--ha-smoke" ]]; then
  echo "== HA smoke: replication-vs-checkpoint cube with brownouts, compact tick =="
  REPRO_REQUIRE_PHASE_MODE=compact \
    python examples/replication_sweep.py --seeds 8 --intervals 2 \
      --brownouts 2 --duration 60
fi

if [[ "${1:-}" == "--drill-smoke" ]]; then
  echo "== drill smoke: deployment cube (canary upgrades + auto-rollback), compact tick =="
  REPRO_REQUIRE_PHASE_MODE=compact \
    python examples/deployment_drill.py --seeds 8 --jobs 4 --duration 60
fi

if [[ "${1:-}" == "--traffic-smoke" ]]; then
  echo "== traffic smoke: rate-schedule cube (DS2 autoscaling + thrash drill), compact tick =="
  REPRO_REQUIRE_PHASE_MODE=compact \
    python examples/traffic_sweep.py --seeds 8 --duration 90
fi

if [[ "${1:-}" == "--serve-smoke" ]]; then
  echo "== serve smoke: SweepService, 2 concurrent drills + traffic sweep, chunked =="
  python examples/serve_sweep.py --seeds 8 --chunk 4 --duration 60
fi

echo "CI OK"
