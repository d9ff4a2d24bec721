#!/bin/bash
# Parent against change on the chip, one benchmark cell, in one call: for
# each seed the order parent, change, change, parent by turns, every run a
# process of its own through the benchmark's one command.  Beforehand, in
# the sandbox (both directories are git-ignored):
#   rm -rf .bench_parent && mkdir .bench_parent && \
#       git archive <parent> | tar -x -C .bench_parent
#   git add -A && rm -rf .bench_checkout && mkdir .bench_checkout && \
#       git archive $(git write-tree) | tar -x -C .bench_checkout
# Usage:
#   chiprun --timeout 3000 -- bash tools/perf/chip_pairs.sh <cell> <trace 0|1> <both|change> <seed>...
# "change" runs the change alone (a set of seeds for its spread).
cell=$1; trace=$2; which=$3; shift 3
out=$PWD/chiprun_out; mkdir -p "$out"
log=$out/pairs_${cell}.jsonl
flip=0
for seed in "$@"; do
  sides="parent change"; [ $flip = 1 ] && sides="change parent"; flip=$((1 - flip))
  [ "$which" = change ] && sides="change"
  for side in $sides; do
    dir=.bench_checkout; [ $side = parent ] && dir=.bench_parent
    ( cd $dir || exit 1
      timeout 600 python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds 30 --trace "$trace" > "$out/_run.log" 2>&1
      rc=$?
      grep "^compared\|^reference check\|^device memory" "$out/_run.log" | cut -c1-300
      echo "{\"side\": \"$side\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"result\": $(tail -n 1 "$out/_run.log")}" | tee -a "$log" | cut -c1-3500 )
  done
done
