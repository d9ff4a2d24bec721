#!/bin/bash
# The DeepSeek-V2 cell's chip calls, each one command (the runs of one call
# share a compile cache; every run is a process of its own and keeps its
# whole log as chiprun_out/run_<trace>_<seed>.log, its result line in
# chiprun_out/deepseek_v2_runs.jsonl):
#   chiprun --timeout 3000 -- bash benchmark/tools/chip_deepseek_v2.sh first <seed>
#       the latent kernel's chunks and layouts and the two expert products
#       timed alone (tools/perf/mla_variants.py), then the cell once with
#       --trace 0 and once with --trace 1
#   ... chip_deepseek_v2.sh variants
#       the latent kernel's chunks and layouts alone
#   ... chip_deepseek_v2.sh runs <trace> <seed>...
#       one run of the cell a seed
#   ... chip_deepseek_v2.sh archive <trace> <seed>...
#       the same from .bench_checkout/, where the builder unpacked
#       `git archive $(git write-tree)`: what git would commit is enough
#   ... chip_deepseek_v2.sh limits <seconds> <seed>...
#       the limits' readings with both controls, a process a seed
#   ... chip_deepseek_v2.sh fault <every> <seed>...
#       the cell with one token in <every> of each session another
#       session's (benchmark/tools/fault_deepseek_v2.py): the widest gap's
#       upper reading; correct false is the point
#   ... chip_deepseek_v2.sh parent <seed>
#       the parent commit, unpacked in .bench_parent/, on the new cell: it
#       has to exit non-zero at once
# Modes chain with `--`: `... variants -- fault 1024 7 8 -- runs 0 9`.
cell=deepseek-v2.doc-saturate
out=${CHIP_OUT:-$PWD/chiprun_out}; mkdir -p "$out"
keep() {  # tag trace seed rc: the log's lines that matter, the result line
  grep "^compared\|^reference check\|^device memory\|^set-up\|^decode_tokens\|Error\|error" "$out/_run.log" | cut -c1-400 | tail -n 12
  echo "{\"run\": \"$1\", \"seed\": $3, \"trace\": $2, \"rc\": $4, \"result\": $(tail -n 1 "$out/_run.log" | cut -c1-6000)}" | tee -a "$out/deepseek_v2_runs.jsonl"
  cp "$out/_run.log" "$out/$1_$2_$3.log"
}
run() {  # trace seed
  timeout 900 python3 benchmark/run.py --workload $cell --seed "$2" --seconds 30 --trace "$1" > "$out/_run.log" 2>&1
  keep "${CHIP_TAG:-run}" "$1" "$2" $?
}
variants() {
  timeout 900 python3 tools/perf/mla_variants.py "$@" > "$out/mla_variants.txt" 2>&1
  grep "^VARIANT\|^device\|Error" "$out/mla_variants.txt" | cut -c1-600
}
one() {
  mode=$1; shift
  case $mode in
  first)
    variants; run 0 "$1"; run 1 "$1" ;;
  variants)
    variants latent ;;
  runs)
    trace=$1; shift
    for seed in "$@"; do run "$trace" "$seed"; done ;;
  archive)
    ( cd .bench_checkout && CHIP_OUT=$out CHIP_TAG=archive bash benchmark/tools/chip_deepseek_v2.sh runs "$@" ) ;;
  limits)
    seconds=$1; shift
    for seed in "$@"; do
      python3 benchmark/tools/read_limits.py --workload $cell --seeds "$seed" --seconds "$seconds" --control 2>&1 \
        | grep "^READ\|^control\|^compared\|^reference check\|Error" | cut -c1-1500 | tee -a "$out/limits.txt"
    done ;;
  fault)
    every=$1; shift
    for seed in "$@"; do
      timeout 900 python3 benchmark/tools/fault_deepseek_v2.py --every "$every" --seed "$seed" > "$out/_run.log" 2>&1
      keep "fault$every" 0 "$seed" $?
    done ;;
  parent)
    ( cd .bench_parent; t0=$(date +%s)
      timeout 300 python3 benchmark/run.py --workload $cell --seed "$1" --seconds 30 --trace 0 > "$out/parent_new_cell.log" 2>&1
      echo "PARENT on the new cell: rc=$? after $(( $(date +%s) - t0 )) s"; tail -n 3 "$out/parent_new_cell.log" | cut -c1-300 ) ;;
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
