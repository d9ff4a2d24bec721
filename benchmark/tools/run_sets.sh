#!/bin/bash
# The two sets of runs a bound is set from: the same seeds in both sets,
# every run a process of its own through the benchmark's one command, the
# result lines kept under chiprun_out/; each run is cut at 420 s (the
# contract allows a run 360 s), so one that hangs cannot eat the budget.
# Usage (on the chip):
#   chiprun --timeout 3000 -- bash benchmark/tools/run_sets.sh <cell> <seconds> <seed>...
cell=$1; seconds=$2; shift 2
mkdir -p chiprun_out
out=chiprun_out/sets_${cell}.jsonl
: > "$out"
for set in 1 2; do
  for seed in "$@"; do
    timeout 420 python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 > chiprun_out/_run.log 2>&1
    rc=$?
    grep "^compared\|^itl_ms\|^decode_tokens\|^window:\|^reference check\|^device memory" chiprun_out/_run.log | cut -c1-300
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"result\": $(tail -n 1 chiprun_out/_run.log)}" | tee -a "$out" | cut -c1-700
  done
done
timeout 420 python3 benchmark/run.py --workload "$cell" --seed 9 --seconds "$seconds" --trace 1 > chiprun_out/_run.log 2>&1
echo "{\"traced\": true, \"rc\": $?, \"result\": $(tail -n 1 chiprun_out/_run.log)}" | tee -a "$out" | cut -c1-4000
