#!/bin/bash
# The one chip call PR 23's limits of `correct` were read with (PERF.md
# section 6): for each cell one process that drives a run per seed through
# the harness and, after each window, puts the reference in the nearest
# lower precision in the program's place.  One READ line a seed, kept under
# chiprun_out/.
#   chiprun --timeout 1500 -- bash benchmark/tools/chip_readings.sh
mkdir -p chiprun_out
timeout 900 python3 benchmark/tools/read_limits.py --workload gpt2-large.saturate \
    --seeds 811,2147484812,813,814,815,816,817,818 --seconds 30 --control \
    > chiprun_out/limits_saturate.txt 2>&1
echo "saturate rc=$?"; grep "^READ" chiprun_out/limits_saturate.txt | cut -c1-1500
timeout 600 python3 benchmark/tools/read_limits.py --workload resnet50.fit \
    --seeds 821,2147484822,823,824,825,826,827,828,829,830,831,832 --seconds 3 --control \
    > chiprun_out/limits_fit.txt 2>&1
echo "fit rc=$?"; grep "^READ" chiprun_out/limits_fit.txt | cut -c1-1800
tail -n 5 chiprun_out/limits_saturate.txt chiprun_out/limits_fit.txt | cut -c1-400
