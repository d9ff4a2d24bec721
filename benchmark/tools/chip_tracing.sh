#!/bin/bash
# PR 24's chip calls: what recording costs when it is on, and that it costs
# nothing when it is off.  Beforehand, in the sandbox, the parent commit is
# unpacked into .bench_parent/ (git-ignored, no git repository) and this
# tree's benchmark laid over it, as the driver does:
#   rm -rf .bench_parent && mkdir .bench_parent && \
#       git archive <parent> | tar -x -C .bench_parent && \
#       cp -r BENCHMARK.json benchmark .bench_parent/ && \
#       cp -r tests/benchmark .bench_parent/tests/
# Then, on the chip, for each "<cell> <trace> <seed> [<side>...]" given, the
# sides in that order with that seed (parent, change, change, parent where
# none is named), every run a process of its own through the benchmark's one
# command.  Result lines go to chiprun_out/tracing_pairs.jsonl.
#   chiprun --timeout 3000 -- bash benchmark/tools/chip_tracing.sh "<cell> <trace> <seed>" ...
out=$PWD/chiprun_out; mkdir -p "$out"
root=$PWD
for run in "$@"; do
  read -r cell trace seed sides <<< "$run"
  for side in ${sides:-parent change change parent}; do
    if [ "$side" = parent ]; then cd "$root/.bench_parent" || exit 1; else cd "$root" || exit 1; fi
    timeout 420 python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds 30 --trace "$trace" > "$out/_run.log" 2>&1
    rc=$?
    grep "^itl_ms\|^decode_tokens\|^window:\|^set-up" "$out/_run.log" | cut -c1-200
    echo "{\"side\": \"$side\", \"cell\": \"$cell\", \"trace\": $trace, \"seed\": $seed, \"rc\": $rc, \"result\": $(tail -n 1 "$out/_run.log")}" | tee -a "$out/tracing_pairs.jsonl" | cut -c1-1800
  done
done
