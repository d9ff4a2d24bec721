#!/bin/bash
# The SDAR cell's chip calls, each one command (the runs of one call share
# a compile cache; every run is a process of its own and keeps its whole
# log as chiprun_out/<tag>_<trace>_<seed>.log, its result line in
# chiprun_out/sdar_runs.jsonl):
#   chiprun --timeout 3000 -- bash benchmark/tools/chip_sdar.sh runs <trace> <seed>...
#       one run of the cell a seed
#   ... chip_sdar.sh archive <trace> <seed>...
#       the same from .bench_checkout/, where the builder unpacked
#       `git archive $(git write-tree)`: what git would commit is enough
#   ... chip_sdar.sh limits <seconds> <seed>...
#       the limits' readings with the stated-precision replay and both
#       controls (benchmark/tools/read_limits.py --control), a process a seed
#   ... chip_sdar.sh fault <crossed|out_of_turn> <every> <seed>...
#       the cell with a fault under the timed path
#       (benchmark/tools/fault_sdar.py): the upper readings of the two
#       limits on a widest gap; correct false is the point
#   ... chip_sdar.sh rehearsal <seed>
#       the parent's program under this PR's benchmark files, in
#       .bench_overlay/ (`git archive HEAD` with benchmark/, BENCHMARK.json
#       and tests/benchmark/ of this tree laid over it): a traced run of two
#       accepted decode cells has to print its result line, and both runs
#       of the new cell have to exit non-zero at once
# Modes chain with `--`: `... fault crossed 2048 7 8 -- limits 30 9 -- runs 0 10`.
cell=sdar-30b-a3b-chat.reason-saturate
out=${CHIP_OUT:-$PWD/chiprun_out}; mkdir -p "$out"
keep() {  # tag trace seed rc seconds: the log's lines that matter, the result line
  grep "^compared\|^reference check\|^device memory\|^set-up\|^decode_tokens\|Error\|error" "$out/_run.log" | cut -c1-500 | tail -n 12
  echo "{\"run\": \"$1\", \"seed\": $3, \"trace\": $2, \"rc\": $4, \"wall_s\": $5, \"result\": $(tail -n 1 "$out/_run.log" | cut -c1-6000)}" | tee -a "$out/sdar_runs.jsonl"
  cp "$out/_run.log" "$out/$1_$2_$3.log"
}
run() {  # trace seed
  t0=$(date +%s)
  timeout 900 python3 benchmark/run.py --workload $cell --seed "$2" --seconds 30 --trace "$1" > "$out/_run.log" 2>&1
  keep "${CHIP_TAG:-run}" "$1" "$2" $? $(( $(date +%s) - t0 ))
}
one() {
  mode=$1; shift
  case $mode in
  runs)
    trace=$1; shift
    for seed in "$@"; do run "$trace" "$seed"; done ;;
  archive)
    ( cd .bench_checkout && CHIP_OUT=$out CHIP_TAG=archive bash benchmark/tools/chip_sdar.sh runs "$@" ) ;;
  limits)
    seconds=$1; shift
    for seed in "$@"; do
      python3 benchmark/tools/read_limits.py --workload $cell --seeds "$seed" --seconds "$seconds" --control 2>&1 \
        | grep "^READ\|^control\|^compared\|^reference check\|Error" | cut -c1-4000 | tee -a "$out/limits.txt"
    done ;;
  fault)
    fault=$1; every=$2; shift 2
    for seed in "$@"; do
      t0=$(date +%s)
      timeout 900 python3 benchmark/tools/fault_sdar.py "$fault" --every "$every" --seed "$seed" > "$out/_run.log" 2>&1
      keep "$fault$every" 0 "$seed" $? $(( $(date +%s) - t0 ))
    done ;;
  rehearsal)
    ( cd .bench_overlay
      for old in smallthinker-21ba3b-instruct.long-saturate k-exaone-236b-a23b.reason-saturate; do
        t0=$(date +%s)
        timeout 900 python3 benchmark/run.py --workload $old --seed "$1" --seconds 30 --trace 1 > "$out/parent_$old.log" 2>&1
        echo "PARENT traced $old: rc=$? after $(( $(date +%s) - t0 )) s"; tail -n 1 "$out/parent_$old.log" | cut -c1-3500
      done
      for trace in 0 1; do
        t0=$(date +%s)
        timeout 120 python3 benchmark/run.py --workload $cell --seed "$1" --seconds 30 --trace $trace > "$out/parent_new_cell_$trace.log" 2>&1
        echo "PARENT on the new cell, trace $trace: rc=$? after $(( $(date +%s) - t0 )) s"; tail -n 3 "$out/parent_new_cell_$trace.log" | cut -c1-300
      done ) ;;
  esac
}
args=()
for word in "$@" --; do
  if [ "$word" = -- ]; then
    [ ${#args[@]} -gt 0 ] && one "${args[@]}"
    args=()
  else
    args+=("$word")
  fi
done
