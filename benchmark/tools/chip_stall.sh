# Four runs of the decode cell that print, beside the result, the largest token gap
# and the garbage collections of the window: a look for the stall behind a run
# that reads 3% low (PERF.md section 7).
#   chiprun --timeout 700 -- bash benchmark/tools/chip_stall.sh
mkdir -p chiprun_out
for seed in 911 2147484912 913 914; do
  timeout 420 python3 benchmark/run.py --workload gpt2-large.saturate --seed $seed --seconds 30 --trace 0 > chiprun_out/_run.log 2>&1
  echo "rc=$? seed=$seed"; grep "^itl_ms\|^garbage\|^decode_tokens\|^compared\|^set-up" chiprun_out/_run.log | cut -c1-250; tail -n 1 chiprun_out/_run.log | cut -c1-400
done
