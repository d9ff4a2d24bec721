#!/bin/bash
# Every named cell from the committed files alone, on the chip.  Beforehand,
# in the sandbox, the tree git would commit is unpacked into .bench_checkout/
# (git-ignored, no git repository):
#   git add -A && rm -rf .bench_checkout && mkdir .bench_checkout && \
#       git archive $(git write-tree) | tar -x -C .bench_checkout
# Each cell then runs from there through the one command: two sets of the
# same three seeds, and a traced run.  Result lines are kept under
# chiprun_out/final_<cell>.jsonl.
#   chiprun --timeout 2400 -- bash benchmark/tools/chip_final.sh <cell>...
out=$PWD/chiprun_out; mkdir -p "$out"
cd .bench_checkout || exit 1
for cell in "$@"; do
  : > "$out/final_${cell}.jsonl"
  for run in "1 0 901" "1 0 2147484902" "1 0 903" "2 0 901" "2 0 2147484902" "2 0 903" "0 1 904"; do
    read -r set trace seed <<< "$run"
    timeout 420 python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds 30 --trace "$trace" > "$out/_run.log" 2>&1
    rc=$?
    grep "^compared\|^itl_ms\|^decode_tokens\|^window:\|^reference check\|^device memory\|^set-up" "$out/_run.log" | cut -c1-260
    echo "{\"set\": $set, \"trace\": $trace, \"seed\": $seed, \"rc\": $rc, \"result\": $(tail -n 1 "$out/_run.log")}" | tee -a "$out/final_${cell}.jsonl" | cut -c1-2500
  done
done
