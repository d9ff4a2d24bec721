#!/bin/bash
# The readings ``configs/k-exaone-236b-a23b.json``'s limits are set from:
# whole runs of the cell (20 s ramp, 30 s window) on the given seeds, each
# with the plain forward pass in the configuration's own precision and the
# lower-precision control read beside the served tokens.  A process a seed:
# two systems of this size do not fit one chip, and the first is not freed
# before the second is built.
#   chiprun --timeout 2400 -- bash benchmark/tools/chip_moe_limits.sh <seed>...
mkdir -p chiprun_out
for seed in "$@"; do
  python3 benchmark/tools/read_limits.py \
      --workload k-exaone-236b-a23b.reason-saturate --seeds "$seed" \
      --seconds 30 --control 2>&1 \
      | grep "^READ\|^reference check\|^set-up\|^device memory\|Error\|error" \
      | tee -a chiprun_out/moe_limits.txt | cut -c1-1800
done
