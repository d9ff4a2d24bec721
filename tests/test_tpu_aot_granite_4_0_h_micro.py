"""granite-4.0-h-micro's decode step and its first and largest prefill
buckets, compiled for a described v5e at the real sizes.  The cases, the
child process and the runner are ``test_tpu_aot_compile.py``'s; a file a
model family lets ``--dist loadfile`` hand the families to different
workers."""

import pytest

from test_tpu_aot_compile import cases_of, compile_in_a_child


@pytest.mark.parametrize("case", cases_of("granite-4.0-h-micro"))
def test_kernel_compiles_for_a_described_v5e(case):
    compile_in_a_child(case)
