#!/bin/sh
# Per-op perf regression gate (reference tools/ci_op_benchmark.sh):
# compares the curated op set against tools/op_bench_baseline.json and
# fails on any op slower than the tolerance.
#
# Default: CPU (hermetic CI). Set OP_BENCH_TPU=1 on a TPU runner to
# gate against the tpu/ baseline entries with the env untouched.
set -e
cd "$(dirname "$0")/.."
if [ "${OP_BENCH_TPU:-0}" = "1" ]; then
    exec python tools/op_bench.py --check \
        --tolerance "${OP_BENCH_TOL:-1.5}" "$@"
fi
exec env JAX_PLATFORMS=cpu PYTHONPATH=. \
    python tools/op_bench.py --check --tolerance "${OP_BENCH_TOL:-2.0}" "$@"
