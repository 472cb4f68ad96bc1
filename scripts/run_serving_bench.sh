#!/usr/bin/env bash
# Serving data-plane benchmark runner.
#
#   scripts/run_serving_bench.sh            # full artifact -> SERVING_BENCH.json
#   scripts/run_serving_bench.sh --quick    # CI smoke: small CPU run that
#                                           # asserts dispatch_rtt_ms under
#                                           # $ZOO_SERVING_QUICK_RTT_MS (15),
#                                           # 0 failed requests, compiled
#                                           # shapes bounded by the bucket
#                                           # ladder, AND that a live /metrics
#                                           # scrape parses as Prometheus text
#                                           # format and contains the
#                                           # request-span histogram
#                                           # (zoo_span_duration_seconds);
#                                           # then gates the int8 kernel tier
#                                           # structurally (bench.py
#                                           # --int8-dispatch --quick: fused
#                                           # dispatch contains pallas calls,
#                                           # no standalone quantize ops / no
#                                           # int8 HBM intermediates — the
#                                           # shape of the 0.72x dispatch
#                                           # regression); and gates the
#                                           # generation decode path
#                                           # (bench.py --generation --quick:
#                                           # zero failed streams at N=8, one
#                                           # compiled decode shape, empty
#                                           # decode-lint findings,
#                                           # continuous >= 1.5x RTC, flat
#                                           # per-token cost, KV-pool
#                                           # donation — static peak one pool
#                                           # under the undonated estimate,
#                                           # compiled alias >= pool, flat
#                                           # witnessed device bytes — with
#                                           # the ZOO_TPU_MEM_WITNESS dump
#                                           # re-checked offline); and gates the
#                                           # replica fleet (bench.py --fleet
#                                           # --quick: one of 4 replicas
#                                           # chaos-killed mid-burst loses
#                                           # ZERO requests, >= 2.5x req/s
#                                           # scaling 1 -> 4 replicas);
#                                           # never writes the artifacts
#
# SERVING_BENCH_TIMEOUT (seconds, default 900) caps the run so a hung
# benchmark can never hang CI.
set -euo pipefail
cd "$(dirname "$0")/.."

TIMEOUT="${SERVING_BENCH_TIMEOUT:-900}"
if [[ "${1:-}" == "--quick" ]]; then
    # host-layer graph-lint gate: the package must carry zero unsuppressed
    # error-severity findings (scripts/run_lint.sh exits non-zero otherwise)
    scripts/run_lint.sh
    timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
        python serving_bench.py --quick
    # generation decode-path gate: N=8 concurrent streams with zero failed
    # streams, ONE compiled decode shape (bucket invariant), empty
    # decode-shape-stability findings, continuous >= 1.5x run-to-completion
    # on mixed-length traffic, flat per-token decode cost. The run carries
    # the memory witness (ISSUE 12): every decode step samples live device
    # bytes, the bench gates flatness + KV-pool donation (static peak drops
    # by one pool; the compiled executable aliases it input->output), and
    # the dump is re-checked offline below
    # --spec (ISSUE 14): speculative-decode + fused paged-attention gates —
    # kernel-vs-plain-dot parity on CPU interpret mode, greedy self-draft
    # acceptance >= floor, >=1.3x tokens advanced per decode dispatch
    # (the TPU wall-clock >=2x gate's host-independent proxy), greedy
    # streams token-identical to the single-token baseline, ONE verify
    # executable per (k, slot-count), decode+cache-alias lints empty
    # --prefix (ISSUE 17): shared-prefix KV-cache gates — on a multi-tenant
    # trace (>=50% of every prompt a shared tenant prefix) warm prefill
    # >=5x faster than cold, peak pool occupancy <=0.6x the sharing-
    # disabled baseline across concurrent same-prefix streams (prefix
    # pages mapped once, not copied per stream), measured hit rate 1.0,
    # and warm streams token-identical to the cold baseline
    # --longprompt (ISSUE 20): chunked-prefill gates — a long prompt
    # injected into 8 running short streams inflates short-stream ITL p95
    # <= 1.5x the no-long-prompt baseline (whole-prompt prefill stalls
    # them an order of magnitude harder), chunked end-to-end long-prompt
    # latency >= 0.8x whole-prompt, ONE compiled chunk shape, the long
    # stream's tokens bit-identical across whole-prompt / chunked-idle /
    # chunked-interleaved arms, and a chaos kill mid-chunk replays the
    # chunk idempotently (same tokens, pool conserved)
    MEM_WITNESS="$(mktemp -t zoo_mem_witness.XXXXXX.jsonl)"
    timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
        ZOO_TPU_MEM_WITNESS="$MEM_WITNESS" \
        python bench.py --generation --spec --prefix --longprompt --quick
    timeout -k 10 120 env JAX_PLATFORMS=cpu \
        python -m analytics_zoo_tpu.analysis --mem-witness "$MEM_WITNESS"
    # replica-fleet gate: zero lost requests with one of 4 replicas chaos-
    # killed mid-burst (requeue + dedup-on-uri verified), fleet reconverges,
    # and routed throughput scales >= 2.5x from 1 to 4 replicas.
    # --hosts 2 (ISSUE 16) adds the cross-host arm: replicas spread over 2
    # host agents, ONE ENTIRE HOST killed mid-burst — zero loss, exactly
    # one fleet.host_failed decision whose trace stitches spans from both
    # hosts, survivors absorb the respawns, and a dial to the dead host
    # fails fast through the per-host breaker with a computed Retry-After
    timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
        python bench.py --fleet --hosts 2 --quick
    # overload gate (ISSUE 13 + the ISSUE-15 observability plane): bimodal
    # traffic at 2x capacity — the critical class holds its SLO (p99 <=
    # deadline) while bulk traffic is shed with a COMPUTED Retry-After
    # (never queued to timeout) — plus the autoscale 1->4->1 drill. The
    # drill scrapes /debug/slo and /debug/events over HTTP WHILE
    # overloaded and gates on: every scrape valid JSON, the bulk-class
    # burn-rate alert firing then resolving after load drops, the
    # critical-class SLO never firing, shed/slo decision events emitted,
    # and every autoscale action on the event stream with a trace that
    # exports as a complete Perfetto trace
    timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
        python bench.py --overload --quick
    # hot-swap gate: sustained load through >= 3 consecutive canary-rolled
    # version swaps on a 4-replica fleet, one canary chaos-killed mid-
    # rollout, one NaN-poisoned publish — zero failed client requests,
    # every response tagged with the serving model version AND the value
    # matching its tag (no mixed weights), automatic rollback observed,
    # fleet converged on the last good version, bounded p95 inflation
    timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
        python bench.py --hotswap --quick
    # flight-recorder replay determinism gate (ISSUE 18): record an
    # overload trace with the always-on flight recorder, then replay it —
    # the incumbent policy must reproduce the live decision sequence
    # EXACTLY (kinds, order, fields modulo timestamps), a candidate
    # watermark policy must be deterministic across two replays of the
    # same recording and must diverge from the incumbent on >= 1 decision
    timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
        python bench.py --replay --quick
    # int8 kernel-tier structural gate (writes KERNEL_BENCH.json for the
    # CPU leg; the TPU run overwrites it with real ratios + MFU)
    exec timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
        python bench.py --int8-dispatch --quick
fi
exec timeout -k 10 "$TIMEOUT" python serving_bench.py "$@"
