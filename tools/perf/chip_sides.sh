#!/bin/bash
# Two or three trees on the chip, one benchmark cell, in one call, all
# THROUGH ONE PATH and ONE compile cache: each tree is moved to
# .bench_side for its run and back, and JAX_COMPILATION_CACHE_DIR is one
# directory beside them.  So the first run (thrown away: "cold") fills the
# cache for every side whose programs have the parent's text, kernels'
# call stacks included, and a side that lowers another text shows as
# misses in its log line.  Each run is a process of its own through the
# benchmark's one command; its log's "set-up" line is kept, because a
# traced run's result line has no setup_s.  Beforehand, in the sandbox
# (every directory here is git-ignored):
#   rm -rf .bench_parent && mkdir .bench_parent && \
#       git archive <parent> | tar -x -C .bench_parent
#   git add -A && rm -rf .bench_checkout && mkdir .bench_checkout && \
#       git archive $(git write-tree) | tar -x -C .bench_checkout
# Usage:
#   chiprun --timeout 3000 -- bash tools/perf/chip_sides.sh <cell> <plan>...
# A plan is <side>:<trace>:<seed>, side one of parent, change, stage (a
# third tree in .bench_stage), run in the order given; begin with the
# parent on a seed of its own, which compiles.  A trace of "a0" or "a1" is
# an untraced or traced run through tools/perf/setup_account.py (this
# tree's copy, on any side), which also writes where set-up's seconds went
# to chiprun_out/setup_account_<cell>.jsonl (a line a run) and .txt.
cell=$1; shift
root=$PWD
out=$root/chiprun_out; mkdir -p "$out"
log=$out/sides_${cell}.jsonl
# the machine's own cache directory, where it has one, outlives the call
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$root/.bench_cache}
mkdir -p "$JAX_COMPILATION_CACHE_DIR"; echo "compile cache: $JAX_COMPILATION_CACHE_DIR"
for plan in "$@"; do
  IFS=: read -r side trace seed <<< "$plan"
  case $side in
    parent) dir=.bench_parent ;; change) dir=.bench_checkout ;;
    stage) dir=.bench_stage ;; *) echo "no side $side"; exit 2 ;;
  esac
  mv "$root/$dir" "$root/.bench_side" || exit 1
  t0=$(date +%s)
  prog="benchmark/run.py --trace $trace"
  case $trace in a0|a1) prog="$root/tools/perf/setup_account.py --trace ${trace#a}" ;; esac
  ( cd "$root/.bench_side" && SETUP_ACCOUNT_OUT=$out/setup_account_${cell} \
      SETUP_ACCOUNT_NOTE=$side:$seed timeout 900 python3 $prog \
      --workload "$cell" --seed "$seed" --seconds 30 > "$out/_run.log" 2>&1 )
  rc=$?
  mv "$root/.bench_side" "$root/$dir"
  grep "^compared\|^reference check\|^device memory\|^itl_ms" "$out/_run.log" | cut -c1-240
  setup=$(grep "^set-up" "$out/_run.log" | head -n 1)
  echo "{\"side\": \"$side\", \"seed\": $seed, \"trace\": \"$trace\", \"rc\": $rc, \"wall_s\": $(( $(date +%s) - t0 )), \"setup_line\": \"$setup\", \"result\": $(grep '^{"correct"' "$out/_run.log" | tail -n 1)}" \
    | tee -a "$log" | cut -c1-3000
done
