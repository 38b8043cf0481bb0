#!/bin/sh
# CI entry: lint + build the C++ runtime + tests.
#
# Lanes:
#   tools/ci.sh        fast lane — lint, C++ build+tests, and the suite
#                      minus the @slow tier (float64 dual-trajectory /
#                      mesh / multi-epoch tests); catches import,
#                      registry, and contract breakage in a few minutes.
#   tools/ci.sh full   everything, including the slow tier.
set -e
cd "$(dirname "$0")/.."
echo "== graftlint (selftest: every checker must reject its seeded violation; then the tree must be findings-clean outside the reviewed baseline)"
python tools/graftlint.py --selftest
python tools/graftlint.py
echo "== cpp"
make -C cpp -s
echo "== telemetry smoke (2-epoch wine, trace + /metrics)"
JAX_PLATFORMS=cpu python tools/telemetry_smoke.py
echo "== health smoke (NaN injection -> halt + crash report)"
JAX_PLATFORMS=cpu python tools/health_smoke.py
echo "== profiler smoke (fused wine, cost registry + ledger)"
JAX_PLATFORMS=cpu python tools/profiler_smoke.py
echo "== async smoke (wine both control-plane modes, 1 readback/segment)"
JAX_PLATFORMS=cpu python tools/async_smoke.py
echo "== mesh smoke (wine 1 vs 4 data shards: identical aggregates, 1 readback/segment)"
JAX_PLATFORMS=cpu python tools/mesh_smoke.py
echo "== accuracy delta selftest (bf16/int8 pins hold; sabotaged int8 scales rejected)"
JAX_PLATFORMS=cpu python tools/accuracy_delta.py --selftest
echo "== chaos smoke (SIGKILL mid-epoch -> resume bit-identical; breaker opens -> recovers)"
JAX_PLATFORMS=cpu python tools/chaos_smoke.py
echo "== serving smoke (wine over HTTP, 64 concurrent, 0 recompiles; then 2-model registry + loadgen SLO; then f32+int8 same-model precision act; then f32-fast batch-1 latency act; then SLO plane: budget burn + trace by rid + live timeseries; then 2-replica fleet: priority overload + mid-burst SIGKILL; then fleet tracing: stitched cross-process tree by rid + hop overhead + merged timeseries; then continuous profiling: fleet-merged /debug/pyprof, >=90% znicz:* attribution, live data-plane phases; then durable blackbox: mid-burst SIGKILL -> obs --rid re-stitches a traced request from disk + postmortem bundle; then binary framed relay: JSON + binary concurrently over a 2-replica fleet, bit-identical replies, per-codec telemetry separated)"
JAX_PLATFORMS=cpu python tools/serving_smoke.py
if [ "$1" = "full" ]; then
    echo "== tests (full lane)"
    python -m pytest tests/ -q
else
    echo "== tests (fast lane; run 'tools/ci.sh full' for the slow tier)"
    python -m pytest tests/ -q -m "not slow"
fi
