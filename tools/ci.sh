#!/usr/bin/env bash
# CI gate (PR 8): the checks a green commit must pass, in one script.
#
#   0. Static bit-safety invariant analysis (PR 10): the five
#      repro.analysis rules (readback-outside-drain, dtype-less-random,
#      narrow-accumulation, device-side-tenant-leak,
#      hidden-nondeterminism) with a FAILURE BUDGET OF ZERO against the
#      committed (empty) baseline.  Runs before pytest because it is
#      ~100x cheaper and catches the statically-detectable half of the
#      historical bit-identity regressions before a single test builds
#      a model.  Rule catalog: src/repro/analysis/README.md.
#   1. Tier-1 test suite with a per-test wall-clock timeout
#      (tools/ci_timeout.py) and a pinned KNOWN-FAILURE BUDGET OF ZERO:
#      every test that collects must pass.  The 16 kernel-tolerance
#      failures the seed carried were retired in this PR (wide
#      -accumulation reductions + the fp64/fp32 fixture fix); nothing
#      gets to regress back onto a tolerated-failure list.
#   2. The speculative-decoding bound (PR 9): the recorded
#      spec_decode_on row of benchmarks/BENCH_serving.json must be
#      bit-identity-certified and at or below 0.6 engine steps per token
#      on the repetitive burst, checked with benchmarks/run.py's own
#      checker (run `python -m benchmarks.run` to re-measure; this gate
#      keeps the committed trajectory honest without re-running the
#      multi-minute benchmark).  The cost of telemetry is measured on the
#      chip, not gated here (PERF.md).
#
# Usage: tools/ci.sh [extra pytest args...]
#   PER_TEST_TIMEOUT=seconds  override the per-test ceiling (default
#                             2750s - above the multidevice launcher's
#                             internal 2700s subprocess timeout).

set -euo pipefail
cd "$(dirname "$0")/.."

PER_TEST_TIMEOUT="${PER_TEST_TIMEOUT:-2750}"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "[ci] static bit-safety invariant analysis (failure budget 0)"
python -m repro.analysis --json > /dev/null

echo "[ci] tier-1 suite (per-test timeout ${PER_TEST_TIMEOUT}s, failure budget 0)"
python -m pytest -q \
    -p tools.ci_timeout --per-test-timeout "$PER_TEST_TIMEOUT" \
    "$@"

echo "[ci] spec decode (<= 0.6 steps/token) bound on the recorded trajectory"
python - <<'PY'
import json

from benchmarks.run import SERVING_JSON, _check_spec_decode

with open(SERVING_JSON) as f:
    rows = json.load(f)["rows"]
_check_spec_decode(rows)
PY

echo "[ci] green: 0 failed, spec decode bound held"
