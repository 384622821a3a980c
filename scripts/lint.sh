#!/usr/bin/env bash
# graftlint gate: the repo's own shard-safety analyzer over the gate scope
# (rule catalog: docs/ANALYSIS.md; engine: rocm_mpi_tpu/analysis/).
#
# Run it BEFORE the test suite: the whole-program interprocedural pass
# (GL08 collective divergence, GL10 concurrency discipline, cross-module
# GL01 donation, GL09 sidecar atomicity, plus the per-file families)
# catches the bug classes unit tests only see under the exact
# interleaving — or the exact multi-host topology — that bites. Compared against the committed baseline
# (analysis/baseline.json: accepted findings never gate, NEW findings
# always do), and the machine-readable artifact is banked at
# output/lint/findings.json (schema-checked below).
# `scripts/lint.sh --changed` is the fast dev
# loop (git-dirty files + import-graph neighbors only).
#
# Also validates the committed measurement baselines still parse as known
# formats (telemetry regress --check-schema, docs/TELEMETRY.md): a
# hand-edited BASELINE/MULTICHIP file must fail here, not silently brick
# the perf-regression gate that reads it.
#
# Exit codes: 0 clean, 1 non-suppressed findings or schema problems,
# 2 usage/internal error. Extra args pass through to the analyzer
# (e.g. scripts/lint.sh --json, --select GL03, --changed).
# Whole AST stage (interprocedural engine included) is bounded well
# under 60 s; the two compiled stages at the end (lowered audit +
# traffic gate) lower small CPU programs and stay inside the same
# budget.
set -u
cd "$(dirname "$0")/.."
# The gate never needs a device.
# --strict-suppressions: a `# graftlint: disable…` directive that
# covers no finding is itself a GL99 error (a dead directive silently
# blesses the next finding at its site).
env JAX_PLATFORMS=cpu python -m rocm_mpi_tpu.analysis \
  rocm_mpi_tpu apps bench.py chip_smoke.py \
  --baseline --strict-suppressions \
  --output output/lint/findings.json "$@" || exit $?
# Schema stage's ok-line goes to stderr so `scripts/lint.sh --json | jq`
# (the documented analyzer usage) still receives pure JSON on stdout;
# problems already print to stderr.
# BENCH_r*.json only exists once bench.py --suite has banked a suite on a
# chip — an empty trajectory must not read as a missing file. nullglob is
# scoped to THIS expansion only: the other baseline families must keep
# failing loudly (exit 2 "missing") if their files disappear.
shopt -s nullglob
bench_records=(BENCH_r*.json)
# Health-plane sidecars (heartbeat-rank*.json, postmortem-rank*.json,
# postmortem/bundle.json — docs/TELEMETRY.md "Health plane") are runtime
# artifacts: they exist only after a --health run or a watchdog verdict,
# under the default sink. When
# present they must parse as their committed schema — a drifted writer
# would brick every watchdog/monitor reader at the next real incident.
health_records=(
  output/telemetry/heartbeat-rank*.json
  output/telemetry/postmortem-rank*.json
  output/telemetry/postmortem/postmortem-rank*.json
  output/telemetry/postmortem/bundle*.json
  docs/telemetry_r*/heartbeat-rank*.json
  docs/telemetry_r*/postmortem/postmortem-rank*.json
  docs/telemetry_r*/postmortem/bundle*.json
)
# Elastic-recovery artifacts (docs/RESILIENCE.md "Elastic recovery" and
# §7), still inside the same nullglob scope: the supervisor's
# elastic.jsonl event sidecars (shrink AND grow records, drill
# sidecars as elastic-*.jsonl) and the checkpoint
# manifests' v2 topology metadata. A drifted elastic record bricks the
# monitor's SHRUNK/GROWN badges; drifted manifest metadata bricks every
# template-less resume that plans a mesh from it — catch both here, not
# at the next real incident. The archived telemetry rank streams ride
# along for the preempt.*/ckpt.* event families (the preemption decision
# trail and the storage-fault plane's retry/degraded records).
# (wildcard-bearing paths only: a literal path would survive nullglob
# and report "missing" when the artifact legitimately doesn't exist)
health_records+=(
  output/*/elastic*.jsonl
  docs/telemetry_r*/elastic*.jsonl
  output/*/manifest-*.json
  docs/telemetry_r*/manifest-*.json
  docs/telemetry_r*/telemetry-rank*.jsonl
)
# Serving sidecars (docs/SERVING.md): the bin manifest + request trace
# apps/serve.py banks per run,
# plus the request-plane hardening artifacts — the append-only
# quarantine.jsonl poison ledger and the chaos soak's soak-report.json
# (docs/RESILIENCE.md §8). A drifted writer bricks the schema-checked
# serving accounting the next time anyone audits a trace's compile
# count — or reads a poisoned service's incident ledger — catch it
# here. (wildcard-bearing paths only, same nullglob discipline)
health_records+=(
  output/*/serve-manifest*.json
  output/*/serve-requests*.jsonl
  output/*/quarantine*.jsonl
  output/*/soak-report*.json
  docs/telemetry_r*/serve-manifest*.json
  docs/telemetry_r*/serve-requests*.jsonl
  docs/telemetry_r*/quarantine*.jsonl
  docs/telemetry_r*/soak-report*.json
)
# Fleet sidecars (docs/SERVING.md "The fleet"): the router's durable
# ticket journal and the merged fleet report apps/fleet.py banks. The
# journal is the replay-reconciliation record — a drifted writer means
# a replica kill can no longer be reconciled from disk; same stakes,
# same gate.
health_records+=(
  output/*/fleet-journal*.jsonl
  output/*/fleet-report*.json
  docs/telemetry_r*/fleet-journal*.jsonl
  docs/telemetry_r*/fleet-report*.json
)
# Request-tracing artifacts (docs/TELEMETRY.md "Request tracing"): the
# per-request rmt-trace-report documents `telemetry trace --out` (and
# the fleet/soak drills) bank. A drifted report writer bricks the
# tail-latency triage the next time anyone decomposes a slow request.
health_records+=(
  output/*/trace-report*.json
  docs/telemetry_r*/trace-report*.json
)
# The graftlint artifacts: the findings document stage 1 just banked
# and the committed baseline.
# A drifted reporter or a hand-mangled baseline must fail HERE, not
# silently mis-gate the next analysis run. (findings*.json stays in the
# nullglob group: a --baseline-write invocation exits before writing
# one, and that must not read as "missing".)
health_records+=(
  output/lint/findings*.json
  docs/telemetry_r*/lint-findings*.json
)
shopt -u nullglob
env JAX_PLATFORMS=cpu python -m rocm_mpi_tpu.telemetry regress \
  --check-schema BASELINE.json MULTICHIP_r0*.json \
  rocm_mpi_tpu/analysis/baseline.json \
  rocm_mpi_tpu/perf/budgets.json \
  ${bench_records[@]+"${bench_records[@]}"} \
  ${health_records[@]+"${health_records[@]}"} \
  docs/weak_scaling_*mechanics*.jsonl 1>&2 || exit $?
# Autotuner caches (docs/PERF.md "Autotuning"): the runtime cache and
# any archived snapshots must parse as the committed schema
# AND every entry must clear the tuning traffic gate — a drifted writer
# (or a doctored over-budget "winner") fails here, not as a silent
# trace-time miss (or worse, a silently adopted waste config). Same
# nullglob discipline: caches exist only after a search ran.
shopt -s nullglob
tuning_caches=(
  output/tuning/cache*.json
  docs/telemetry_r*/tuning-cache*.json
)
shopt -u nullglob
if [ "${#tuning_caches[@]}" -gt 0 ]; then
  env JAX_PLATFORMS=cpu python -m rocm_mpi_tpu.tuning validate \
    "${tuning_caches[@]}" 1>&2 || exit $?
fi
# Lowered-program audit (docs/ANALYSIS.md "The lowered-program audit"):
# compiles all three workloads' steady-state drivers on virtual CPU
# devices and proves (a) the collective sequence is identical across
# rank-roles (no collective under a lowered conditional, channel-pinned
# order, sane permute pair structure) and (b) every GL01-declared
# donation actually aliased — the ground truth the AST engine's GL08/
# GL01 verdicts approximate.
env JAX_PLATFORMS=cpu python -m rocm_mpi_tpu.analysis.lowered 1>&2 \
  || exit $?
# Compiled HBM-traffic gate (docs/PERF.md): lowers + audits every
# distributed step driver against perf/budgets.json on virtual CPU
# devices — the static roofline check; no accelerator, no timing.
exec env JAX_PLATFORMS=cpu python -m rocm_mpi_tpu.perf 1>&2
