#!/usr/bin/env bash
# Full local verification: static analysis first (fails in seconds on
# a broken invariant, before 10+ minutes of tests), then the native
# library build, then the tier-1 suite with the same flags the driver
# uses — twice-lite: the full suite with the native hot paths live,
# plus a pure-Python smoke pass (RP_NATIVE=0) over the suites that
# gate the native/fallback seam, so a fallback regression can't hide
# behind a working .so.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== rplint (baseline gate) =="
python -m tools.rplint --baseline redpanda_tpu

echo "== rplint race rules (RPL015/016 whole-program, empty by construction) =="
python -m tools.rplint --rules RPL015,RPL016 redpanda_tpu tools tests

echo "== rplint compile discipline (RPL020/021 device plane, empty by construction) =="
python -m tools.rplint --rules RPL020,RPL021 redpanda_tpu

echo "== rplint transfer discipline (RPL018 whole-program incl. tests, empty by construction) =="
python -m tools.rplint --rules RPL018 redpanda_tpu tools tests

echo "== rplint fetch discipline (RPL023 span walk, empty by construction) =="
python -m tools.rplint --rules RPL023 redpanda_tpu tools

echo "== native build =="
if make -s -C native; then
    echo "built native/build/libredpanda_native.so"
else
    echo "WARN: native build failed; suite runs on pure-Python fallbacks"
fi

echo "== observability scrape smoke =="
env JAX_PLATFORMS=cpu python tools/scrape_smoke.py

echo "== tier-1 tests (native) =="
env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly "$@"

echo "== fallback smoke (RP_NATIVE=0) =="
env JAX_PLATFORMS=cpu RP_NATIVE=0 python -m pytest \
    tests/test_native_append.py tests/test_native_records.py \
    tests/test_produce_fast.py tests/test_foundation.py \
    -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== shard mp smoke (fork + invoke_on seam, grow -> kill-mid-grow rollback -> retire) =="
env JAX_PLATFORMS=cpu python tools/shard_smoke.py

echo "== proc-fault soak smoke (seeded ProcNemesis, 3 iterations) =="
env JAX_PLATFORMS=cpu python tools/chaos_soak.py --proc-faults \
    --iterations 3 --duration 2

echo "== placement smoke (live move mid-produce, fetch parity, merged /metrics) =="
env JAX_PLATFORMS=cpu python tools/placement_smoke.py

echo "== fleet scrape smoke (merged /metrics + stitched traces) =="
env JAX_PLATFORMS=cpu python tools/scrape_smoke.py --fleet

echo "== sharding-off smoke (RP_SHARDS=0) =="
env JAX_PLATFORMS=cpu RP_SHARDS=0 python -m pytest \
    tests/test_kafka_e2e.py \
    -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly

echo "== tick-frame smoke (100k-partition live replication plane) =="
env JAX_PLATFORMS=cpu python tools/tick_frame_smoke.py

echo "== tick-frame backend parity (host fallback vs device) =="
env JAX_PLATFORMS=cpu python tools/tick_frame_smoke.py --parity --groups 4096

echo "== compile-guard smoke (RP_COMPILEGUARD=1 device plane, 0 recompiles) =="
env JAX_PLATFORMS=cpu RP_COMPILEGUARD=1 RP_QUORUM_BACKEND=device \
    python tools/tick_frame_smoke.py --groups 4096

echo "== tiered chaos smoke (ObjectNemesis schedule, replay-equal) =="
env JAX_PLATFORMS=cpu python tools/tiered_smoke.py

echo "== race sanitizer smoke (RP_SAN=1 election + produce, 0 reports) =="
env JAX_PLATFORMS=cpu python tools/rpsan_smoke.py

echo "== health-plane smoke (partition_health + bounded /metrics) =="
env JAX_PLATFORMS=cpu python tools/scrape_smoke.py --health

echo "== flight-data smoke (history ring + alerts + profiler) =="
env JAX_PLATFORMS=cpu python tools/scrape_smoke.py --alerts

echo "== flight-data stand-down smoke (RP_ALERTS=0 RP_PROFILE=0) =="
env JAX_PLATFORMS=cpu RP_ALERTS=0 RP_PROFILE=0 \
    python tools/scrape_smoke.py --alerts

echo "== mesh backend smoke (8 forced devices, live parity vs host) =="
env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    RP_QUORUM_BACKEND=mesh python tools/mesh_smoke.py

echo "== mesh compile-guard smoke (RP_COMPILEGUARD=1, 8 devices, 0 recompiles) =="
env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    RP_QUORUM_BACKEND=mesh RP_COMPILEGUARD=1 python tools/mesh_smoke.py

echo "== mesh stand-down smoke (RP_QUORUM_BACKEND=host) =="
env JAX_PLATFORMS=cpu RP_QUORUM_BACKEND=host python tools/mesh_smoke.py

echo "== device-plane smoke (RP_DEVPLANE=1, folds==frames + kernel histograms) =="
env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    RP_DEVPLANE=1 python tools/scrape_smoke.py --devplane

echo "== device-plane stand-down smoke (RP_DEVPLANE unset, instrument is identity) =="
env JAX_PLATFORMS=cpu python tools/scrape_smoke.py --devplane

echo "== device-zstd archive smoke (upload + cold-read parity + stand-down) =="
env JAX_PLATFORMS=cpu python tools/tiered_smoke.py --zstd

echo "== front-end churn smoke (1k clients, RST storms, zero leaks) =="
env JAX_PLATFORMS=cpu python tools/traffic_smoke.py

echo "== front-end fallback smoke (RP_NATIVE_FRAME=0 pure-Python framing) =="
env JAX_PLATFORMS=cpu RP_NATIVE_FRAME=0 python tools/traffic_smoke.py \
    --clients 200 --rounds 2

echo "== consume smoke (2-broker wire plane: parity + verify-on-read + counters) =="
env JAX_PLATFORMS=cpu python tools/consume_smoke.py

echo "== consume stand-down smoke (RP_FETCH_WIRE=0 decoded framing) =="
env JAX_PLATFORMS=cpu RP_FETCH_WIRE=0 python tools/consume_smoke.py

echo "== tracing-off smoke (RP_TRACE=0) =="
env JAX_PLATFORMS=cpu RP_TRACE=0 python tools/scrape_smoke.py --fleet
exec env JAX_PLATFORMS=cpu RP_TRACE=0 python -m pytest \
    tests/test_observability.py tests/test_kafka_e2e.py \
    tests/test_admin_server.py \
    -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly
