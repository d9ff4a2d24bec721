"""TPU-hardware tests: require a real TPU; skip the whole tree without one.

No platform pinning here — contrast with tests/conftest.py, which forces
the virtual CPU mesh.  JAX finds the chip by itself on a TPU host; from the
sandbox the tree runs through the builder's chip tool
(``chiprun -- python -m pytest tests_tpu/ -q``), one pytest process, which
is the one process the chip belongs to.
"""

import pytest


def pytest_sessionfinish(session, exitstatus):
    """Same execution-count dump as tests/conftest.py, so the census TPU
    column can be execution-backed: MXNET_OP_COVERAGE_OUT=path pytest
    tests_tpu/ writes {op: OpDef.apply call count} for the hardware run.
    An all-skip session (no TPU) writes nothing."""
    try:
        from mxnet_tpu.test_utils import dump_op_coverage
    except Exception:
        return
    dump_op_coverage("OpDef.apply call counts from one tests_tpu session")


def pytest_collection_modifyitems(config, items):
    import jax

    has_tpu = jax.default_backend() == "tpu"
    if not has_tpu:
        skip = pytest.mark.skip(reason="no TPU visible")
        for item in items:
            item.add_marker(skip)
