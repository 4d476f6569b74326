#!/usr/bin/env bash
# Native sanitizer matrix for the C++ ingest engine.
#
# Builds native/stage_tsan_driver.cpp + native/ingest_engine.cpp under
# each requested sanitizer (the PR-2 -Wall -Wextra -Werror harness,
# -fno-sanitize-recover so the first report is fatal) and runs the
# driver: concurrent stage-counter hammering + conservation checks,
# protobuf wire fuzz (vn_route / vn_import_scan truncation + bit-flip
# sweeps, forwarded t-digests included), vn_build_tiers boundary abuse, SPSC staging-ring stress
# (2-slot rings, two concurrent drainers, exact packet conservation),
# and scalar/SIMD parity (vn_key_hash / vn_scan_tokens over random
# bytes plus byte-identical drains from a shared fuzz corpus).
#
# Usage:
#   scripts/native_sanitize.sh              # asan ubsan tsan (full)
#   scripts/native_sanitize.sh asan ubsan   # chosen arms
#   scripts/native_sanitize.sh smoke        # one combined
#                                           # address+undefined arm,
#                                           # reduced workload
#                                           # (scripts/check.py gate)
#
# Env: CXX (default g++), VN_SAN_BUILD_DIR (default
# native/.build/sanitize), VN_SAN_ITERS / VN_SAN_THREADS forwarded to
# the driver.
set -euo pipefail
cd "$(dirname "$0")/.."

CXX=${CXX:-g++}
OUT=${VN_SAN_BUILD_DIR:-native/.build/sanitize}
mkdir -p "$OUT"
SRCS="native/stage_tsan_driver.cpp native/ingest_engine.cpp"
FLAGS="-O1 -g -std=c++17 -pthread -Wall -Wextra -Werror \
-fno-sanitize-recover=all"

if ! command -v "$CXX" >/dev/null; then
    echo "native_sanitize: $CXX not found" >&2
    exit 3
fi

run_arm() {
    local name=$1 san=$2
    shift 2
    local bin="$OUT/$name"
    echo "== $name: $CXX -fsanitize=$san"
    # shellcheck disable=SC2086
    "$CXX" -fsanitize="$san" $FLAGS $SRCS -o "$bin"
    echo "== $name: run"
    env "$@" "$bin"
    echo "== $name: PASS"
}

rc=0
ARMS=("$@")
if [ ${#ARMS[@]} -eq 0 ]; then
    ARMS=(asan ubsan tsan)
fi
for arm in "${ARMS[@]}"; do
    case "$arm" in
        asan)
            run_arm asan address ASAN_OPTIONS=detect_leaks=1 || rc=1 ;;
        ubsan)
            run_arm ubsan undefined UBSAN_OPTIONS=print_stacktrace=1 \
                || rc=1 ;;
        tsan)
            run_arm tsan thread || rc=1 ;;
        smoke)
            run_arm smoke address,undefined \
                ASAN_OPTIONS=detect_leaks=1 \
                VN_SAN_ITERS="${VN_SAN_ITERS:-2000}" \
                VN_SAN_THREADS="${VN_SAN_THREADS:-2}" || rc=1 ;;
        *)
            echo "native_sanitize: unknown arm '$arm'" \
                 "(want asan|ubsan|tsan|smoke)" >&2
            exit 3 ;;
    esac
done
exit $rc
