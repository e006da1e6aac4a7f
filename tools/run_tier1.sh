#!/usr/bin/env bash
# Tier-1 verification — THE command builders and CI run (keep identical to
# the "Tier-1 verify" line in ROADMAP.md; edit both together).
#
# Counts pass dots from the pytest progress line so a partial hang still
# reports how far it got; exits with pytest's own status.
#
# Suites of note: tests/test_fleet_telemetry.py (exporter endpoints, fleet
# metric/trace merge, flight recorder, obsctl) runs its fast half here; its
# `slow`-marked end-to-end drills (2-worker launch -> rank-0 merged
# /metrics + Perfetto trace; chaos-kill -> black box) run under
# tools/run_chaos.sh / -m slow. tools/check_obs_overhead.py gates the
# off/flight-on/exporter-idle/perf-on hot-path budgets separately.
#
# Sharding-plan suite: tests/test_shard_plan.py (plan spec resolution,
# QuantizedWeight placement, tp=2 token-exact decode, dp=2 loss parity,
# tp-replica router drill) runs on the 8-device virtual CPU platform
# tests/conftest.py forces; on a box with < 2 visible devices and no
# host-device override the module SKIPS (not errors) — CI without the
# override stays green, it just doesn't exercise the mesh.
#
# Cold-start suite: tests/test_compile_plan.py runs its fast half here
# (plan enumeration, warmup -> compile-free serve window, bundle
# round-trip + mismatch fallback, persistent-cache hit labeling, router
# pre-warm); the int8+prefix bundle e2e is `slow`-marked. The full
# restart-to-first-token measurement needs fresh processes and runs as
# `python tools/coldstart_bench.py` (its {"coldstart": …} line feeds
# perf_gate's coldstart.* lower-is-better metrics; use --preset tiny as
# the quick smoke).
#
# Elastic-fleet suite: tests/test_fleet.py runs its fast half here
# (policy hysteresis/cooldown/bounds units, dynamic router membership
# with bounded rendezvous key movement, scale-up/down over fake static
# engines, the scale-cycle provider-leak + stale-breaker pin, deploy
# promote/reject/rollback pins incl. the rollback-on-mid-rollout-
# regression acceptance test, obsctl fleet rendering, open-loop traffic
# helpers, perf_gate fleet.* fields — ~10 s, all fake-replica based);
# the real-engine 4x-step-during-rollout + preemption drill is
# chaos+slow-marked (tools/run_chaos.sh). The measured artifact comes
# from `python tools/serving_bench.py --traffic step:4@10 --autoscale
# MIN:MAX`.
#
# Speculative-decoding suite: tests/test_speculative.py runs its fast
# half here (token-exact greedy parity weak-draft + self-draft, rollback
# page accounting, cancel mid-speculation, warmup -> compile-free serve
# window with spec programs, bundle round trip + draft-swap fingerprint
# fallback, honest multi-token TPOT); the int8-draft and k-sweep parity
# variants are `slow`-marked and the breaker-storm drill is
# `chaos`-marked (tools/run_chaos.sh). The A/B artifact comes from
# `python tools/serving_bench.py --spec-k N --draft <preset>` (gated by
# perf_gate's serving.spec_tok_s).
#
# Request-journey suite: tests/test_reqtrace.py (one stitched trace per
# request: mid-flight-kill failover stitching, per-attempt queue-wait
# stamps, speculative-round spans, ring-bounded soak, /requests endpoint
# + obsctl requests + histogram exemplars, SLO burn-rate gauges, flight
# in-flight journeys) runs here — all static-fake or one-layer-tiny, a
# few seconds total. The reqtrace-on hot-path budget (<5% vs off,
# retry-once-on-noise) is gated by tools/check_obs_overhead.py gate 5.
#
# Kernel suites: tests/test_gather_gemm_kernel.py (gather-GEMM vs
# einsum/sorted dispatch parity incl. empty experts + capacity overflow,
# MoELayer's loud fallback, the perf_gate smoke for moe.dispatch_ms) and
# tests/test_paged_latent_kernel.py (the latent decode kernel against the
# gathered view) run here via Pallas INTERPRET mode on this CPU tier;
# tests/test_int8_kv_view.py pins int8 KV pages behind the paged view.
# The dispatch A/B artifact comes from `python tools/moe_dispatch_bench.py`
# (docs/kernels.md).
#
# History-and-alerting suite: tests/test_tsdb_alerts.py (in-process TSDB
# ring/downsample/rate units, window quantiles, multi-window burn-rate
# alert hold-down, alert -> one flight dump with slowest journeys,
# /query + 2-rank /fleet/query over a real TCPStore, obsctl
# top/alerts/query) runs here — synthetic clocks, seconds total; the
# injected-latency-storm acceptance drill is `chaos`-marked
# (tools/run_chaos.sh). The tsdb-on hot-path budget (<5%) is gate 6 of
# tools/check_obs_overhead.py.
#
# Profiling-and-goodput suite: tests/test_profiler_goodput.py (sampling
# profiler seam classification + decode-seam pin over a synthetic busy
# thread, goodput-ledger reconciliation chaos drill — useful + attributed
# waste == tokens_out EXACTLY with speculation + mid-flight cancel +
# stop, zero leaked KV pages —, memory-ledger buckets/leak check,
# /profile + /mem endpoints, obsctl profile/mem rendering, waste_burn +
# hbm_headroom default rules, flight hot_stacks record, perf_gate
# goodput fields) runs here — manual-drive sampling, seconds total. The
# prof-on hot-path budget (<5%) is gate 7 of tools/check_obs_overhead.py
# and the prof-on serving leg of tools/check_serving_overhead.py.
#
# Perf regression gate (not run here — needs a bench artifact): after a
# bench run on the chip, `python tools/perf_gate.py --baseline <old>.json
# --current <new>.json` exits nonzero on a tokens/s / MFU / TTFT
# regression beyond tolerance; `--baseline <old>.json --dry-run` is
# the wiring smoke (always exit 0) and is covered by
# tests/test_perf_attribution.py in this tier. The --serving pair also
# gates the paged-KV serving_bench fields (mixed_tok_s, prefix_hit_rate,
# concurrency_peak higher-is-better; kv_occupancy_peak lower-is-better).
# serving_bench/coldstart_bench `--out BENCH_serving_r<NN>.json` write
# the perf_gate-ready artifact (body + meta block with git sha + unix
# stamp); `perf_gate --json` emits the machine verdict the fleet deploy
# gate (fleet.perf_verdict_gate) consumes.
set -o pipefail
cd "$(dirname "$0")/.."
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
