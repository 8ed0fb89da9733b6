#!/usr/bin/env bash
# Tier-1 CI: fast test suite + the smoke scripts under scripts/smokes/.
#
#     bash scripts/ci.sh
#
# The same smokes are invoked by .github/workflows/ci.yml (no heredoc
# drift: this file and the workflow share the scripts/smokes/*.py files).
# The "not slow" selection skips the subprocess/system tests — the full
# suite is `PYTHONPATH=src python -m pytest -q` (the workflow's nightly /
# `ci:full`-label lane runs it).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== lint (ruff + reprolint contract checks) =="
bash scripts/lint.sh

echo "== pytest (tier 1, -m 'not slow') =="
python -m pytest -q -m "not slow"

echo "== solver registry smoke =="
python scripts/smokes/registry.py

# the device-forcing smokes get XLA_FLAGS set EXPLICITLY (not just the
# scripts' setdefault fallback) so an ambient XLA_FLAGS — e.g. a debug
# --xla_dump_to — cannot silently drop the forced 4-device topology
FORCE4="--xla_force_host_platform_device_count=4"

echo "== mesh-backend smoke (4 forced host devices, 2x2 data x model) =="
XLA_FLAGS="$FORCE4" python scripts/smokes/mesh.py

echo "== serve smoke (LinsysServer: 2 systems, factor-store amortization) =="
python scripts/smokes/serve.py

echo "== serve_async smoke (AsyncLinsysServer: pipelined stream, SLO report) =="
python scripts/smokes/serve_async.py

echo "== scenarios smoke (sparse/LS/stream modes, local + 2x2 mesh) =="
XLA_FLAGS="$FORCE4" python scripts/smokes/scenarios.py

echo "== straggler smoke (r=2, rotating straggler, 4 forced host devices) =="
XLA_FLAGS="$FORCE4" python scripts/smokes/straggler.py

echo "== elastic smoke (kill -> rejoin -> taskmaster recovery, factor reuse) =="
python scripts/smokes/elastic.py

echo "== kernel smoke (every Pallas path, interpret mode) =="
XLA_FLAGS="$FORCE4" REPRO_PALLAS_INTERPRET=1 python scripts/smokes/kernel.py

echo "CI OK"
