#!/usr/bin/env bash
# CI entrypoint (ref: the reference's buildkite pipeline,
# .buildkite/gen-pipeline.sh + docker test matrix [V], SURVEY.md §2.7 —
# scaled to this repo: one host, no docker matrix, same three gates).
#
#   1. lint        — compile-level hygiene over the package and tests
#   2. native+TSAN — csrc/ builds clean AND passes a ThreadSanitizer
#                    stress of its concurrent pieces (SURVEY.md §5.2)
#   3. tests       — the full CPU suite on the virtual 8-device mesh
#   4. telemetry-smoke — 5-step CPU loop with the live /metrics
#                    endpoint on an ephemeral port: Prometheus scrape
#                    (step p50/p95 + registry gauges) and the
#                    flight-recorder JSON-lines dump must both work
#   5. serve-smoke — scripts/serve_smoke.py: a 2-worker inference
#                    fleet on a toy transformer — concurrent
#                    mixed-length prompts routed through the
#                    rendezvous-KV capacity announcements, TTFT/TPOT
#                    quantiles + slot gauges asserted on the live
#                    /metrics scrape; then a role-split fleet (1
#                    prefill + 2 decode workers) streams KV pages over
#                    the transfer wire with per-role routing asserted
#                    on live scrapes — this is also the TRACE-SMOKE
#                    gate: with HOROVOD_TRACE=1 a crafted traceparent
#                    must round-trip as X-Trace-Id and one routed
#                    request must assemble (trace_assemble over live
#                    /traces scrapes) into a single skew-corrected
#                    trace covering router->prefill->transfer->decode
#                    in monotonic order — then one decode worker is
#                    SIGTERMed mid-burst (reservations fail over);
#                    finally SIGTERM the unified workers and assert
#                    the drain completed every accepted request (exit
#                    143) — the serving plane can't silently rot
#   6. audit-smoke — scripts/hlo_audit.py: the lowered-program
#                    invariant catalog over the canonical roster
#                    (fused fp32/int8 wire, overlap buckets, ZeRO-2/3,
#                    guard overhead, two-level + MoE routing, serve
#                    donation/compile budget) must run green AND the
#                    auditor must exit nonzero on a deliberately
#                    broken invariant (int8 forced onto an intra hop)
#                    — an auditor that cannot fail is not evidence
#   7. chaos-smoke — scripts/chaos_smoke.py: an integrity drill (one
#                    injected NaN training step that the grad guard
#                    must SKIP and count, one injected checkpoint
#                    bitflip that digest verification must bypass via
#                    fallback restore, both asserted over the live
#                    /metrics scrape) followed by a short
#                    multi-process elastic job under a seeded
#                    FaultPlan (one KV connection reset per worker +
#                    one mid-run worker SIGKILL) that must complete
#                    with exactly one gang restart and nonzero
#                    retry.* counters scraped from the live /metrics
#                    endpoint — neither the chaos hardening nor the
#                    integrity plane can silently rot; the serve
#                    failover drill runs with tracing ON and asserts
#                    hedge/replay legs as tagged sibling spans plus a
#                    live-migrated request assembling into one
#                    connected trace spanning >= 3 processes
#
# Usage: ./ci.sh [lint|native|tests|telemetry-smoke|serve-smoke|audit-smoke|chaos-smoke|all]
# (default: all)

set -euo pipefail
cd "$(dirname "$0")"

step() { printf '\n=== %s ===\n' "$*"; }

lint() {
  step "lint: AST-based convention lint (scripts/lint.py)"
  # scripts/lint.py parses every file (so it subsumes compileall's
  # syntax check) and enforces the repo conventions: no os.environ
  # reads outside common/config.py (the basics.live_config() contract),
  # no bare except, no unused imports, no jax.debug.callback outside
  # the approved guard/telemetry sites.
  python scripts/lint.py
  # Import must succeed without TPU hardware.
  JAX_PLATFORMS=cpu python -c "import horovod_tpu"
}

native() {
  step "native: release build"
  make -C csrc clean >/dev/null
  make -C csrc
  step "native: ThreadSanitizer stress (kvstore + timeline)"
  local tsan_bin
  tsan_bin="$(mktemp -d)/tsan_stress"
  g++ -std=c++17 -g -O1 -fsanitize=thread -pthread \
    csrc/timeline.cc csrc/kvstore.cc csrc/sha256.cc csrc/tsan_stress.cc \
    -o "$tsan_bin"
  TSAN_OPTIONS="halt_on_error=1" "$tsan_bin"
  step "native: AddressSanitizer stress (same driver)"
  local asan_bin
  asan_bin="$(mktemp -d)/asan_stress"
  g++ -std=c++17 -g -O1 -fsanitize=address,undefined -pthread \
    csrc/timeline.cc csrc/kvstore.cc csrc/sha256.cc csrc/tsan_stress.cc \
    -o "$asan_bin"
  ASAN_OPTIONS="halt_on_error=1" "$asan_bin"
}

tests() {
  step "tests: full CPU suite (8-device virtual mesh)"
  python -m pytest tests/ -q
}

serve_smoke() {
  step "serve-smoke: routed fleet (unified + role-split prefill/decode), SLO + transfer scrapes, trace-plane assembly, SIGTERM drains"
  python scripts/serve_smoke.py
}

telemetry_smoke() {
  step "telemetry-smoke: /metrics scrape + flight-recorder dump"
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python scripts/telemetry_smoke.py
}

chaos_smoke() {
  step "chaos-smoke: integrity drill (NaN skip + ckpt bitflip) + seeded FaultPlan gang drill (KV reset + SIGKILL) + traced failover/migration drill"
  python scripts/chaos_smoke.py
}

audit_smoke() {
  step "audit-smoke: lowered-program invariant roster (scripts/hlo_audit.py)"
  local art_dir
  art_dir="$(mktemp -d)"
  JAX_PLATFORMS=cpu python scripts/hlo_audit.py \
    --json "$art_dir/hlo_audit.json"
  test -s "$art_dir/hlo_audit.json" \
    || { echo "missing artifact: hlo_audit.json" >&2; exit 1; }
  step "audit-smoke: auditor must FAIL a deliberately broken invariant"
  # assert the SPECIFIC rejection (rule finding + violation exit), not
  # just any nonzero exit — a breaker that crashes before evaluating
  # the rule must not pass as "the auditor can fail"
  local break_out
  break_out="$art_dir/break_int8_intra.log"
  if JAX_PLATFORMS=cpu python scripts/hlo_audit.py --break int8-intra \
      >"$break_out" 2>&1; then
    echo "hlo_audit accepted int8 on an intra hop — the auditor cannot fail" >&2
    exit 1
  fi
  grep -q "invariant violation(s) found" "$break_out" \
    && grep -q "WireDtype" "$break_out" \
    || { echo "hlo_audit --break exited nonzero WITHOUT a WireDtype finding (crash, not rejection):" >&2
         tail -20 "$break_out" >&2; exit 1; }
  echo "audit-smoke OK: roster green, broken invariant rejected ($art_dir)"
}

case "${1:-all}" in
  lint)        lint ;;
  native)      native ;;
  tests)       tests ;;
  telemetry-smoke) telemetry_smoke ;;
  serve-smoke) serve_smoke ;;
  audit-smoke) audit_smoke ;;
  chaos-smoke) chaos_smoke ;;
  all)         lint; native; tests; telemetry_smoke; serve_smoke; audit_smoke; chaos_smoke ;;
  *) echo "usage: $0 [lint|native|tests|telemetry-smoke|serve-smoke|audit-smoke|chaos-smoke|all]" >&2; exit 2 ;;
esac
