#!/usr/bin/env bash
# Runs of one benchmark cell in one chip call, a seed each, as the driver
# runs them (BENCHMARK.json's command and run_seconds), then a traced run.
#
#   chiprun --timeout 3000 -- bash scripts/cell_runs.sh <cell> <dir> <seed,seed,...> [<traced seed>]
#
# Each run's stdout goes to chiprun_out/<dir>/run_<seed>.log (stderr beside
# it), the traced run's to trace_<seed>.log and its trace by scope to
# scopes_trace.txt. Printed for each: the exit code, the window, the three
# gaps beside their limits, and the result line. PERF.md cites these files
# by <dir> and seed.
set -u
cell=$1 out=chiprun_out/$2 seeds=$3 traced=${4:-}
mkdir -p "$out"

show() {
    grep -h '^\[window\]\|^\[check\] \(plain\|loss_gap\|grad_norm_gap\|delta_norm_gap\)\|^\[metric\]' "$1"
    tail -n 1 "$1"
}

for seed in ${seeds//,/ }; do
    python3 benchmarks/run.py --workload "$cell" --seed "$seed" --seconds 25 \
        --trace 0 > "$out/run_$seed.log" 2> "$out/run_$seed.err"
    echo "seed $seed exit $?"
    show "$out/run_$seed.log"
done
if [ -n "$traced" ]; then
    python3 benchmarks/run.py --workload "$cell" --seed "$traced" --seconds 25 \
        --trace 1 > "$out/trace_$traced.log" 2> "$out/trace_$traced.err"
    echo "traced seed $traced exit $?"
    show "$out/trace_$traced.log"
    python3 -m benchmarks.lib.scopes > "$out/scopes_trace.txt" 2>&1
fi
