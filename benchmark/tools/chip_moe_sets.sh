#!/bin/bash
# The two sets of six runs a new cell is admitted on, every run a process of
# its own through the benchmark's one command and with a seed of its own,
# then one traced run; result lines are kept under chiprun_out/.  A run is
# cut at 600 s, so one that hangs cannot eat the budget.
#   chiprun --timeout 3600 -- bash benchmark/tools/chip_moe_sets.sh <cell> <12 seeds> <traced seed>
cell=$1; shift
mkdir -p chiprun_out
out=chiprun_out/sets_${cell}.jsonl
: > "$out"
n=0
for seed in "$@"; do
  n=$((n + 1))
  if [ $n -le 12 ]; then set=$(( (n + 5) / 6 )); trace=0; else set=0; trace=1; fi
  timeout 600 python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds 30 --trace $trace > chiprun_out/_run.log 2>&1
  rc=$?
  grep "^compared\|^decode_tokens\|^reference check\|^device memory\|^set-up\|^garbage" chiprun_out/_run.log | cut -c1-300
  echo "{\"set\": $set, \"trace\": $trace, \"seed\": $seed, \"rc\": $rc, \"result\": $(tail -n 1 chiprun_out/_run.log)}" | tee -a "$out" | cut -c1-3500
done
