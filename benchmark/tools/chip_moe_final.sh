#!/bin/bash
# The K-EXAONE cell from the committed files alone, on the chip, and the
# parent commit failing on it at once.  Beforehand, in the sandbox:
#   git add -A && rm -rf .bench_checkout && mkdir .bench_checkout && \
#       git archive $(git write-tree) | tar -x -C .bench_checkout
#   rm -rf .bench_parent && mkdir .bench_parent && \
#       git archive <parent> | tar -x -C .bench_parent && \
#       cp -r BENCHMARK.json benchmark .bench_parent/ && cp -r tests/benchmark .bench_parent/tests/
#   chiprun --timeout 1500 -- bash benchmark/tools/chip_moe_final.sh <seed> <traced seed>
cell=k-exaone-236b-a23b.reason-saturate
out=$PWD/chiprun_out; mkdir -p "$out"
( cd .bench_parent || exit 1
  SECONDS=0
  python3 benchmark/run.py --workload $cell --seed 5 --seconds 30 --trace 0 > "$out/_parent.log" 2>&1
  echo "the parent on $cell: rc=$? after $SECONDS s: $(tail -n 1 "$out/_parent.log" | cut -c1-200)" )
cd .bench_checkout || exit 1
for run in "0 $1" "1 $2"; do
  read -r trace seed <<< "$run"
  timeout 600 python3 benchmark/run.py --workload $cell --seed "$seed" --seconds 30 --trace "$trace" > "$out/_run.log" 2>&1
  rc=$?
  grep "^compared\|^decode_tokens\|^reference check\|^device memory\|^set-up" "$out/_run.log" | cut -c1-300
  echo "{\"trace\": $trace, \"seed\": $seed, \"rc\": $rc, \"result\": $(tail -n 1 "$out/_run.log")}" | tee -a "$out/final_${cell}.jsonl" | cut -c1-3000
done
