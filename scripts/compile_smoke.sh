#!/usr/bin/env bash
# Compile-once smoke (CI brick for docs/compile.md): run the SAME train
# leg twice on the 2x4 virtual CPU mesh against a fresh persistent
# executable cache. The cold run populates it (framework executable
# index + XLA persistent cache); the warm rerun — a fresh process —
# must pay ZERO compiles (compile_count == 0, every executable a disk
# hit) and reach its first step at least COMPILE_SMOKE_TTFS_CUT
# (default 30%) faster than cold. Then the serve resize leg: the
# background-precompiled elastic resize must stall strictly less than
# the cold-rebuild baseline (bench.py hard-gates that itself; the
# report carries both numbers). Runtime ~3 min.
#
# Usage: scripts/compile_smoke.sh [--report /path/report.json]
#   COMPILE_SMOKE_TMP=/path scripts/compile_smoke.sh  # keep the cache
#   COMPILE_SMOKE_SERVE=0 scripts/compile_smoke.sh    # train legs only
set -euo pipefail
cd "$(dirname "$0")/.."

REPORT=""
if [ "${1:-}" = "--report" ]; then
    REPORT="$2"; shift 2
fi

TMP="${COMPILE_SMOKE_TMP:-$(mktemp -d)}"
mkdir -p "$TMP"
trap '[ -z "${COMPILE_SMOKE_TMP:-}" ] && rm -rf "$TMP"' EXIT
echo "== compile smoke: executable cache in $TMP/cache ==" >&2

BENCH_ARGS=(--platform cpu --cpu-devices 8 --mesh-shape 2x4
    --model resnet18 --batch-size 2 --image-size 64
    --num-warmup 1 --num-iters 2 --num-batches-per-iter 2)

echo "== compile smoke: cold leg (empty cache) ==" >&2
COLD=$(JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR="$TMP/cache" \
    python bench.py "${BENCH_ARGS[@]}" | tail -n 1)
echo "$COLD"

echo "== compile smoke: warm leg (fresh process, populated cache) ==" >&2
WARM=$(JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR="$TMP/cache" \
    python bench.py "${BENCH_ARGS[@]}" | tail -n 1)
echo "$WARM"

SERVE="null"
if [ "${COMPILE_SMOKE_SERVE:-1}" = "1" ]; then
    echo "== compile smoke: serve resize leg (background precompile vs cold rebuild) ==" >&2
    SERVE=$(JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR="$TMP/cache-serve" \
        python bench.py --serve --platform cpu --cpu-devices 8 \
        --serve-requests "${COMPILE_SMOKE_SERVE_REQUESTS:-24}" \
        --serve-rate 50 | tail -n 1)
    echo "$SERVE"
fi

python - "$COLD" "$WARM" "$SERVE" "${REPORT:-}" <<'EOF'
import json
import sys

cold, warm = json.loads(sys.argv[1]), json.loads(sys.argv[2])
serve = json.loads(sys.argv[3]) if sys.argv[3] != "null" else None
import os
cut = float(os.environ.get("COMPILE_SMOKE_TTFS_CUT", "0.30"))

assert cold["compile_count"] > 0, \
    "cold leg compiled nothing — the cache dir was not fresh"
assert warm["compile_count"] == 0, (
    f"warm rerun COMPILED {warm['compile_count']} executable(s) — the "
    f"persistent cache missed (cache {warm['compile_cache']})")
assert warm["compile_cache"]["hits"] > 0, \
    f"warm rerun never hit the cache: {warm['compile_cache']}"
t_cold = cold["time_to_first_step_ms"]
t_warm = warm["time_to_first_step_ms"]
reduction = 1.0 - t_warm / t_cold
assert reduction >= cut, (
    f"warm TTFS {t_warm:.0f} ms is only {100 * reduction:.1f}% below "
    f"cold {t_cold:.0f} ms (need >= {100 * cut:.0f}%)")
report = {
    "ttfs_cold_ms": round(t_cold, 3),
    "ttfs_warm_ms": round(t_warm, 3),
    "ttfs_reduction": round(reduction, 4),
    "warm_compile_count": warm["compile_count"],
    "cold_compile_count": cold["compile_count"],
    "warm_compile_cache": warm["compile_cache"],
    "compile_ms_total_cold": cold["compile_ms_total"],
}
if serve is not None:
    # bench.py already hard-gated bg < cold; re-assert and record.
    bg = serve["resize_stall_ms_bg"]
    cold_stall = serve["resize_stall_ms_cold"]
    assert bg < cold_stall, f"resize stall bg {bg} >= cold {cold_stall}"
    report.update({
        "resize_stall_ms_bg": bg,
        "resize_stall_ms_cold": cold_stall,
        "resize_stall_speedup": serve.get("resize_stall_speedup"),
        "serve_ttfs_ms": serve.get("time_to_first_step_ms"),
    })
print(f"compile smoke: warm TTFS {t_warm:.0f} ms vs cold "
      f"{t_cold:.0f} ms (-{100 * reduction:.1f}%), warm compiles 0 "
      f"({warm['compile_cache']['hits']} cache hits)"
      + (f"; resize stall bg {report['resize_stall_ms_bg']:.0f} ms vs "
         f"cold {report['resize_stall_ms_cold']:.0f} ms"
         if serve is not None else ""))
if sys.argv[4]:
    with open(sys.argv[4], "w") as f:
        json.dump(report, f, indent=1)
    print(f"compile smoke: report written to {sys.argv[4]}")
EOF

echo "COMPILE SMOKE: OK" >&2
