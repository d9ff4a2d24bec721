"""SDAR's pass (the decode step of a model that generates by blocks) and
its three prefill buckets, compiled for a described v5e at the real sizes.
The cases, the child process and the runner are ``test_tpu_aot_compile.py``'s;
a file a model family lets ``--dist loadfile`` hand the families to
different workers."""

import pytest

from test_tpu_aot_compile import cases_of, compile_in_a_child


@pytest.mark.parametrize("case", cases_of("sdar"))
def test_kernel_compiles_for_a_described_v5e(case):
    compile_in_a_child(case)
