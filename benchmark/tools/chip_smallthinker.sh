#!/bin/bash
# The SmallThinker cell's chip calls, each one command (every call starts
# with nothing compiled; the runs of one call share .jax_cache):
#   chiprun --timeout 3000 -- bash benchmark/tools/chip_smallthinker.sh first <seed>
#       the two expert products and the rings timed alone, then the cell
#       once with --trace 0, once with --trace 1, once with both controls
#   chiprun --timeout 3400 -- bash benchmark/tools/chip_smallthinker.sh runs <trace> <seed>...
#       one run of the cell a seed, each a process of its own
#   chiprun --timeout 3000 -- bash benchmark/tools/chip_smallthinker.sh limits <seconds> <seed>...
#       the limits' readings with both controls, a process a seed (a second
#       cell in one process finds the chip's memory still held)
cell=smallthinker-21ba3b-instruct.long-saturate
out=$PWD/chiprun_out; mkdir -p "$out"
mode=$1; shift
run() {  # trace seed
  timeout 900 python3 benchmark/run.py --workload $cell --seed "$2" --seconds 30 --trace "$1" > "$out/_run.log" 2>&1
  rc=$?
  grep "^compared\|^reference check\|^device memory\|^set-up\|^decode_tokens\|Error\|error" "$out/_run.log" | cut -c1-400 | tail -n 12
  echo "{\"seed\": $2, \"trace\": $1, \"rc\": $rc, \"result\": $(tail -n 1 "$out/_run.log" | cut -c1-6000)}" | tee -a "$out/smallthinker_runs.jsonl"
  cp "$out/_run.log" "$out/run_$1_$2.log"
}
case $mode in
first)
  timeout 900 python3 benchmark/tools/expert_product_variants.py > "$out/variants.txt" 2>&1
  grep "^VARIANT\|^device\|Error" "$out/variants.txt"
  run 0 "$1"; run 1 "$1"
  python3 benchmark/tools/read_limits.py --workload $cell --seeds "$(( $1 + 1 ))" --seconds 30 --control 2>&1 \
    | grep "^READ\|^control\|^compared\|^reference check\|Error" | cut -c1-1500 | tee "$out/limits_first.txt" ;;
runs)
  trace=$1; shift
  for seed in "$@"; do run "$trace" "$seed"; done ;;
limits)
  seconds=$1; shift
  for seed in "$@"; do
    python3 benchmark/tools/read_limits.py --workload $cell --seeds "$seed" --seconds "$seconds" --control 2>&1 \
      | grep "^READ\|^control\|^compared\|^reference check\|Error" | cut -c1-1500 | tee -a "$out/limits.txt"
  done ;;
esac
