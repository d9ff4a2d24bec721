#!/bin/bash
# The Granite-4.0-H cell's chip calls, each one command (the runs of one
# call share a compile cache; every run is a process of its own and keeps
# its whole log as chiprun_out/<tag>_<trace>_<seed>.log, its result line in
# chiprun_out/granite_runs.jsonl):
#   chiprun --timeout 3000 -- bash benchmark/tools/chip_granite_hybrid.sh runs <trace> <seed>...
#       one run of the cell a seed
#   ... chip_granite_hybrid.sh archive <trace> <seed>...
#       the same from .bench_checkout/, where the builder unpacked
#       `git archive $(git write-tree)`: what git would commit is enough
#   ... chip_granite_hybrid.sh limits <seconds> <seed>...
#       the limits' readings with the stated-precision pass and both
#       controls (benchmark/tools/read_limits.py --control), a process a seed
#   ... chip_granite_hybrid.sh paths <seed>
#       one run with the program's telemetry on: ops.kernel_path as the
#       programs were traced (ssd_scan, decode_attention and slot_write on
#       their Pallas paths), serving.cache.bytes by kind, and how many
#       distinct tokens the sessions the check could read were served
#       (seeded weights that repeat one token would make every gap 0)
#   ... chip_granite_hybrid.sh rehearsal <seed>
#       the parent's program under this PR's benchmark files, in
#       .bench_stage/ (`git archive <parent>` with benchmark/, BENCHMARK.json
#       and tests/benchmark/ of this tree laid over it), through
#       tools/perf/chip_sides.sh (one path, one compile cache): a traced run
#       of the Phi-4 cell has to print its result line, and both runs of the
#       new cell have to exit non-zero at once
# The shared code's pairs are tools/perf/chip_sides.sh's own calls:
#   bash tools/perf/chip_sides.sh phi-4-mini-flash-reasoning.reason-saturate parent:0:<s> change:0:<s>
#   bash tools/perf/chip_sides.sh k-exaone-236b-a23b.reason-saturate parent:0:<s> change:0:<s>
# Modes chain with `--`: `... paths 7 -- limits 30 8 9 -- runs 0 10`.
cell=granite-4.0-h-micro.reason-saturate
out=${CHIP_OUT:-$PWD/chiprun_out}; mkdir -p "$out"
keep() {  # tag trace seed rc seconds: the log's lines that matter, the result line
  grep "^compared\|^reference check\|^device memory\|^set-up\|^decode_tokens\|^KERNEL\|^CACHE\|^SERVED\|Error\|error" "$out/_run.log" | cut -c1-700 | tail -n 14
  echo "{\"run\": \"$1\", \"seed\": $3, \"trace\": $2, \"rc\": $4, \"wall_s\": $5, \"result\": $(tail -n 1 "$out/_run.log" | cut -c1-6000)}" | tee -a "$out/granite_runs.jsonl"
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
    ( cd .bench_checkout && CHIP_OUT=$out CHIP_TAG=archive bash benchmark/tools/chip_granite_hybrid.sh runs "$@" ) ;;
  limits)
    seconds=$1; shift
    for seed in "$@"; do
      python3 benchmark/tools/read_limits.py --workload $cell --seeds "$seed" --seconds "$seconds" --control 2>&1 \
        | grep "^READ\|^control\|^compared\|^reference check\|Error" | cut -c1-4000 | tee -a "$out/limits.txt"
    done ;;
  paths)
    t0=$(date +%s)
    timeout 900 python3 - "$1" > "$out/_run.log" 2>&1 <<'E'
import json, sys, time
sys.path.insert(0, ".")
from mxnet_tpu import telemetry
telemetry.enable()
from benchmark import harness
from benchmark.run import take_chips

CELL = "granite-4.0-h-micro.reason-saturate"
find = harness.find


def spying(kind, name):
    module = find(kind, name)
    if kind == "families":
        check = module.System.check

        def spy(self, window, with_control=False):
            done = [r for r in window["requests"] if r.finished()]
            print("SERVED %d sessions finished in the window; distinct "
                  "tokens of the first eight: %s of %s" % (
                      len(done), [len(set(r.tokens)) for r in done[:8]],
                      [len(r.tokens) for r in done[:8]]), flush=True)
            return check(self, window, with_control)

        module.System.check = spy
    return module


harness.find = spying
manifest = harness.load_manifest()
result, _compared, _control = harness.run_cell(
    manifest, CELL, int(sys.argv[1]), 30.0, 0, take_chips(1),
    time.monotonic())
snap = telemetry.snapshot()
print("KERNEL_PATHS " + json.dumps(snap["counters"].get("ops.kernel_path")))
print("CACHE_BYTES " + json.dumps(snap["gauges"].get("serving.cache.bytes")))
print(json.dumps(result), flush=True)
E
    keep paths 0 "$1" $? $(( $(date +%s) - t0 )) ;;
  rehearsal)
    bash tools/perf/chip_sides.sh phi-4-mini-flash-reasoning.reason-saturate stage:1:"$1"
    bash tools/perf/chip_sides.sh $cell stage:0:"$1" stage:1:"$1" ;;
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
