#!/bin/bash
# Whole runs of the cell phi-4-mini-flash-reasoning.reason-saturate through
# the benchmark's one command, a process a run, each cut at 420 s; the
# result lines are kept under chiprun_out/.
#   chiprun --timeout 3000 -- bash benchmark/tools/chip_sambay_runs.sh <trace 0|1> <seed>...
cell=phi-4-mini-flash-reasoning.reason-saturate
trace=$1; shift
mkdir -p chiprun_out
out=chiprun_out/sambay_runs.jsonl
for seed in "$@"; do
  t0=$(date +%s)
  timeout 420 python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds 30 --trace "$trace" > chiprun_out/_run.log 2>&1
  rc=$?
  grep "^compared\|^reference check\|^device memory\|^set-up\|^garbage\|Error\|error:" chiprun_out/_run.log | cut -c1-400
  echo "{\"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"wall_s\": $(( $(date +%s) - t0 )), \"result\": $(tail -n 1 chiprun_out/_run.log)}" | tee -a "$out" | cut -c1-6000
  if [ $rc != 0 ]; then tail -n 30 chiprun_out/_run.log | cut -c1-400; fi
done
