#!/usr/bin/env bash
# Graph-lint runner (ISSUE 7; concurrency tier ISSUE 11; memory tier
# ISSUE 12).
#
#   scripts/run_lint.sh            # AST-lint the package (tracer/wallclock/
#                                  # chaos-site rules + the concurrency tier:
#                                  # guarded-by, lock-order cycles, hold
#                                  # hazards, leaf/unused/reach-in + the
#                                  # memory tier's donation-missed rebind
#                                  # check repo-wide); non-zero exit on any
#                                  # unsuppressed error finding
#   scripts/run_lint.sh --full     # also run the analysis pytest marker
#                                  # (golden fixtures + clean-repo gate +
#                                  # graph_checks hooks + the lock and
#                                  # memory witnesses)
#
# The graph-layer rules need a traced computation, so they run where one
# exists: TrainConfig.graph_checks at fit() start (now incl. hbm-budget /
# donation-missed / peak-temporary), InferenceModel/serving warmup at
# model-load time (hbm-budget + cache-alias on the decode step). This
# script is the host-layer CI gate. The dynamic halves are gated by
# scripts/run_chaos_suite.sh via `python -m analytics_zoo_tpu.analysis
# --witness` (locks) and `--mem-witness` (allocations).
set -euo pipefail
cd "$(dirname "$0")/.."

LINT_TIMEOUT="${LINT_TIMEOUT:-300}"
timeout -k 10 "$LINT_TIMEOUT" env JAX_PLATFORMS=cpu \
    python -m analytics_zoo_tpu.analysis

if [[ "${1:-}" == "--full" ]]; then
    exec timeout -k 10 900 env JAX_PLATFORMS=cpu \
        python -m pytest tests/ -q -m analysis -p no:cacheprovider
fi
