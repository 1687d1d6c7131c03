#!/usr/bin/env bash
# Tier-1 CI gate: unit tests + model-only benchmark smoke.
# Usage: scripts/ci.sh [--full]   (from anywhere; cds to the repo root)
#   --full  additionally runs the kernel interpret-mode validation:
#           benchmarks/run.py without --smoke executes every Pallas
#           kernel against its ref.py oracle on CPU — slower, so gated
#           behind the flag (ROADMAP "once runtime is budgeted" item).
set -euo pipefail
cd "$(dirname "$0")/.."

FULL=0
for arg in "$@"; do
  case "$arg" in
    --full) FULL=1 ;;
    *) echo "unknown flag: $arg" >&2; exit 2 ;;
  esac
done

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

python -m pytest -q

# public-API smoke: the CLI front door must compile + emit end to end
# (exercises repro.api: builder suite -> CompileOptions -> artifact)
CLI_OUT="$(mktemp -d)"
python -m repro list > /dev/null
python -m repro compile conv_relu_32 --target kv260 --emit "$CLI_OUT" --quiet
test -s "$CLI_OUT/conv_relu_32_g0.cpp"
test -s "$CLI_OUT/host_schedule.cpp"
rm -rf "$CLI_OUT"

# importer smoke (ISSUE 5): a zoo model card must compile -> emit -> run
# end to end through `python -m repro compile <file>` (repro.frontends)
ZOO_OUT="$(mktemp -d)"
python -m repro zoo > /dev/null
RUN_LOG="$(python -m repro compile examples/lenet5.json --target kv260 \
  --emit "$ZOO_OUT" --run --quiet)"
echo "$RUN_LOG" | grep -q "ran OK"
test -s "$ZOO_OUT/lenet5_g0.cpp"
test -s "$ZOO_OUT/host_schedule.cpp"
rm -rf "$ZOO_OUT"

# strided-ONNX smoke (ISSUE 8): the strided+BN golden fixture must
# import -> compile -> emit -> run end to end through the CLI (stride-2
# downsamples, BatchNorm folds, GlobalAveragePool head), traced; the
# trace is kept as trace_onnx_smoke.json for the artifact upload like
# the lenet5 one below
ONNX_OUT="$(mktemp -d)"
RUN_LOG="$(python -m repro compile tests/golden/resnet_tiny.onnx \
  --target kv260 --emit "$ONNX_OUT" --run --quiet \
  --trace /tmp/trace_onnx.json)"
echo "$RUN_LOG" | grep -q "ran OK"
test -s "$ONNX_OUT/resnet_tiny_g0.cpp"
test -s "$ONNX_OUT/host_schedule.cpp"
rm -rf "$ONNX_OUT"
python - /tmp/trace_onnx.json <<'PY'
import json, sys
from repro.instrument import validate_chrome_trace
validate_chrome_trace(json.load(open(sys.argv[1])))
print("onnx trace OK")
PY
cp /tmp/trace_onnx.json trace_onnx_smoke.json

# instrumentation smoke (ISSUE 6): a traced compile+run must produce a
# valid Chrome trace-event JSON; kept as trace_smoke.json for the
# workflow artifact upload alongside the provenance-stamped BENCH rows
python -m repro compile lenet5 --trace /tmp/trace.json --run --quiet > /dev/null
python - /tmp/trace.json <<'PY'
import json, sys
from repro.instrument import validate_chrome_trace
obj = validate_chrome_trace(json.load(open(sys.argv[1])))
names = [e["name"] for e in obj["traceEvents"]]
assert any(n.startswith("pass:") for n in names), "no pass spans in trace"
assert {"ming:run", "ming:dispatch", "ming:sync"} <= set(names), \
    "no runtime spans in trace"
assert "provenance" in obj.get("otherData", {}), "trace missing provenance"
print(f"trace OK ({len(names)} events)")
PY
cp /tmp/trace.json trace_smoke.json

# lint gate (ISSUE 9): the static analyzer must find zero ERROR-severity
# diagnostics across the whole named suite (paper suite + showcases +
# zoo) on both device presets.  The full JSON diagnostics document is
# kept as lint_diagnostics.json for the workflow artifact upload.
python -m repro lint --all --target kv260 --target zu3eg \
  --json lint_diagnostics.json --quiet
python - lint_diagnostics.json <<'PY'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["version"] == 1 and doc["counts"]["error"] == 0, doc["counts"]
print(f"lint OK ({sum(doc['counts'].values())} diagnostics, 0 errors "
      f"across {len(doc['meta']['graphs'])} graph/target pairs)")
PY

if [ "$FULL" = 1 ]; then
  python -m benchmarks.run          # includes kernel interpret-mode checks
else
  python -m benchmarks.run --smoke  # model-only sections + BENCH_smoke.json
fi

# perf-trajectory gate: diff BENCH_smoke.json against the archived
# previous snapshot (fail-soft: only a >10% cycle regression hard-fails;
# a missing archive just seeds the trajectory), then refresh the archive.
python scripts/smoke_diff.py BENCH_smoke.json

# profiler smoke (ISSUE 10): the modeled-vs-measured join must produce
# a per-group table and a schema-valid JSON document; kept as
# profile_smoke.json for the workflow artifact upload.  Wall-clock
# ratios on shared runners are noise — the gate is structural (groups
# present, modeled cycles joined, ratio computed), never a threshold.
python -m repro profile lenet5 --reps 1 --json profile_smoke.json --quiet
python - profile_smoke.json <<'PY'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["version"] == 1 and doc["profiles"], "empty profile document"
for prof in doc["profiles"]:
    assert prof["groups"], f"{prof['model']}: no group rows"
    for g in prof["groups"]:
        assert g["modeled_cycles"] > 0 and g["measured_ms"] > 0, g
        assert "ratio" in g and "implied_clock_mhz" in g, g
    assert prof["layers"], f"{prof['model']}: no layer rows"
print(f"profile OK ({len(doc['profiles'])} target(s), "
      f"{sum(len(p['groups']) for p in doc['profiles'])} group rows)")
PY

# serving smoke (ISSUE 7): a short fixed-seed load test on lenet5
# produces BENCH_serve.json for the workflow artifact.  Bit-exactness
# (vmap vs loop) is the hard gate; the wall-clock numbers — the 5x
# speedup and the p99/QPS trajectory diff — are *informational* here
# (--min-speedup 0, --warn-only) because timing on shared CI runners
# is noisy-neighbor flaky.  Dev invocations without those flags keep
# the full-threshold gates.  The engine's metrics snapshot (ISSUE 10)
# rides along as serve_metrics.json and must validate + carry the
# lifecycle series the load test exercised.
python -m benchmarks.serve_bench --models lenet5 --targets kv260 \
  --qps 100,400 --requests 120 --seed 0 --min-speedup 0 \
  --metrics-out serve_metrics.json
python - serve_metrics.json <<'PY'
import json, sys
from repro.instrument import validate_metrics_snapshot
snap = validate_metrics_snapshot(json.load(open(sys.argv[1])))
assert snap["counters"]["serve_requests_total"]["values"], "no requests"
stages = {row["labels"]["stage"]
          for row in snap["histograms"]["serve_stage_ms"]["values"]}
assert stages >= {"queue_wait", "batch_form", "execute", "respond"}, stages
print(f"serve metrics OK (stages: {sorted(stages)})")
PY
python scripts/smoke_diff.py BENCH_serve.json --mode serve --warn-only
