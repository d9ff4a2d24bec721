"""SDAR's pass (the decode step of a model that generates by blocks) and
its three prefill buckets, compiled for a described v5e at the real sizes.
The cases, the child process and the runner are ``test_tpu_aot_compile.py``'s;
a file a model family lets ``--dist loadfile`` hand the families to
different workers.  And the other decode models' programs, which share the
engine, ``decode_attention`` and the expert layer with it: their lowered
text is what it was."""

import os
import subprocess
import sys

import pytest

from test_tpu_aot_compile import ROOT, cases_of, compile_in_a_child

#: ``python tools/perf/program_fingerprints.py --tiny --tpu`` less SDAR's own
#: lines, as the tree before PR 44 printed it: the engine's step and prefill
#: programs of every other decode model at ``tests/benchmark/tiny/``'s
#: sizes, traced as for the chip.  A PR that means to change one of these
#: models' programs replaces its lines with what the tool prints then (and
#: says so); one that does not and fails here has changed them by accident.
#: PR 48 meant to: ``lm_tiny``'s step attends through the ladder's one body
#: (one rung at 64 positions, no branch) where it ran two einsums
THE_OTHER_MODELS = """\
deepseek_v2_tiny jit_step a74d72ea96e43ff5
deepseek_v2_tiny jit_prefill 8 56c3ba6695767f68
deepseek_v2_tiny jit_prefill 16 4f0f00375a5408e8
deepseek_v2_tiny jit_prefill 32 9326ff51f83c220c
exaone_tiny jit_step 929f47eb0097a063
exaone_tiny jit_prefill 8 bef8ce0fd1a76f16
exaone_tiny jit_prefill 32 97273e3848493a7b
lm_tiny jit_step 78299685fcc0f486
lm_tiny jit_prefill 8 9e15d6c4c644b793
lm_tiny jit_prefill 32 b43409b6a90dd3e3
sambay_tiny jit_step 4de5912459323169
sambay_tiny jit_prefill 8 7c29633371e48dcc
sambay_tiny jit_prefill 32 bdc50eb44173eb1a
smallthinker_tiny jit_step 950415fd9e43c361
smallthinker_tiny jit_prefill 8 ab55c9254c9dafc5
smallthinker_tiny jit_prefill 16 c11bcc59100c6892
smallthinker_tiny jit_prefill 32 85d70f04602f234a
"""


@pytest.mark.parametrize("case", cases_of("sdar"))
def test_kernel_compiles_for_a_described_v5e(case):
    compile_in_a_child(case)


def test_the_other_models_programs_lower_to_the_text_they_had():
    """What SDAR's merged pass handed the shared code (a second horizon in
    ``decode_attention``, a rows' mask in the expert layer, two more arrays
    in a block model's slot state) is seen only where a call hands it
    over: the engines of the five other decode models lower to the same
    text (``tools/perf/program_fingerprints.py``; at the benchmark's own
    sizes the tool takes minutes, and CHANGES.md quotes it)."""
    configs = sorted({line.split()[0]
                      for line in THE_OTHER_MODELS.splitlines()})
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tools", "perf", "program_fingerprints.py"),
         "--tiny", "--tpu"] + configs,
        env=dict(os.environ, TPU_LOG_DIR="disabled"), capture_output=True,
        text=True, timeout=250)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout == THE_OTHER_MODELS
