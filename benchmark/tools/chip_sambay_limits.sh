#!/bin/bash
# The readings ``configs/phi-4-mini-flash-reasoning.json``'s limits are set
# from: whole runs of the cell (20 s ramp, 30 s window) on the given seeds,
# each with the plain forward pass in the configuration's own precision and
# both controls (weights through fp8; the recurrent state kept in bfloat16)
# read beside the served tokens.  A process a seed: two systems of this
# size do not fit one chip.
#   chiprun --timeout 2400 -- bash benchmark/tools/chip_sambay_limits.sh <seed>...
mkdir -p chiprun_out
for seed in "$@"; do
  python3 benchmark/tools/read_limits.py \
      --workload phi-4-mini-flash-reasoning.reason-saturate --seeds "$seed" \
      --seconds 30 --control 2>&1 \
      | grep "^READ\|^reference check\|^set-up\|^device memory\|Error\|error" \
      | tee -a chiprun_out/sambay_limits.txt | cut -c1-2200
done
