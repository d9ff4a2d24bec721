"""Generated docs cannot go stale: regenerate each to a temp path and
diff against the committed file (the census-freshness pattern)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.parametrize("tool,committed", [
    ("tools/gen_op_reference.py", "docs/api/op_reference.md"),
])
def test_generated_doc_is_fresh(tool, committed, tmp_path):
    fresh = str(tmp_path / "fresh.md")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, os.path.join(ROOT, tool),
                           "--out", fresh],
                          capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(os.path.join(ROOT, committed)) as f:
        want = f.read()
    with open(fresh) as f:
        got = f.read()
    assert got == want, "%s is stale: rerun %s" % (committed, tool)
