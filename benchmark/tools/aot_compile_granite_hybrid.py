"""The sandbox rehearsal for ``configs/granite-4.0-h-micro.json``: the
decode engine's own ``jit_step`` and ``jit_prefill`` programs, built by
``DecodeEngine`` over ``models/granite_hybrid.py`` at the configuration's
widths (all 40 layers, 64 slots x 4096), compiled for a described
``v5e:2x2`` chip without the chip, with ``memory_analysis()``, what is held
beside a program (weights, 36 states and tails, four layers' K and V), the
layout the compiler keeps each kind of slot state in, and any copy of an
array the size of one.  Nothing runs.  The tool is
``aot_compile_sdar.py``'s, which asks a configuration for its family; this
file names the configuration.

    JAX_PLATFORMS=cpu python benchmark/tools/aot_compile_granite_hybrid.py [slots] [step|prefill|<bucket> ...]
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools import aot_compile_sdar as tool  # noqa: E402 — sets the environment of a compile-only process as it is imported
from benchmark.tools.aot_compile_sdar import (  # noqa: E402,F401 — what tests/test_tpu_aot_compile.py asks a tool for
    cache_copies, engine_programs, prefill_shapes)

CONFIG = "granite-4.0-h-micro"

if __name__ == "__main__":
    tool.CONFIG = CONFIG
    tool.main()
