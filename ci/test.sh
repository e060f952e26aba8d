#!/usr/bin/env bash
# CI orchestration — role of the reference's ci/ tree:
#   ci/checks/check_style.sh  -> graftlint (python -m raft_tpu.analysis;
#                                AST+dataflow lint, no deps — style is
#                                rule R0, serving invariants R1-R6)
#   ci/test_python.sh / ctest -> pytest (tests cover the whole framework;
#                                native IO is built on demand via tests/test_io.py)
#   wheel smoke tests         -> editable install + bare import + CLI --help
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== graftlint =="
# exits non-zero on any unsuppressed finding; the JSON report lands
# next to the bench JSONs as a build artifact
JAX_PLATFORMS=cpu python -m raft_tpu.analysis --format=ci \
    --output ci/graftlint_report.json \
    --lockgraph ci/graftlint_lockgraph.json

echo "== packaging smoke =="
python -m pip install -e . --no-deps --no-build-isolation --quiet
(cd /tmp && JAX_PLATFORMS=cpu python -c "import raft_tpu; print('import OK', raft_tpu.__name__)")
JAX_PLATFORMS=cpu python -m raft_tpu.bench --help > /dev/null && echo "bench CLI OK"

echo "== tests =="
# the session drops ci/metrics_snapshot.json — the full tracing
# registries (counters / gauges / cumulative-bucket histograms / span
# ring stats) as a build artifact next to the graftlint report
RAFT_TPU_METRICS_SNAPSHOT="$PWD/ci/metrics_snapshot.json" \
    python -m pytest tests/ -q "$@"
