"""Docs cannot go stale: each generated one is regenerated to a temp path
and diffed against the committed file (the census-freshness pattern), and
every hand-written one names only files that are in the tree."""

import glob
import os
import re
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


#: a path ending in .py or .sh, then an optional ``:line`` or ``::test``
_PATH = re.compile(r"^((?:[\w.\-]+/)*[\w.\-]+\.(?:py|sh))(?::.*)?$")


def _named_files(doc):
    """Every word inside backticks of ``doc`` that is a path of this repo
    ending in ``.py`` or ``.sh``: one whose first component is a directory
    at the root, or a bare file name.  (``python/mxnet/...`` and
    ``src/operator/...`` are the reference's tree, not this one.)"""
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    out = []
    for quoted in re.findall(r"`([^`\n]+)`", text):
        for word in quoted.split():
            m = _PATH.match(word)
            if m and ("/" not in m.group(1) or os.path.isdir(
                    os.path.join(ROOT, m.group(1).split("/")[0]))):
                out.append(m.group(1))
    return out


@pytest.fixture(scope="module")
def file_names():
    """The base name of every file in the tree (dot-directories and what
    a chip run brings back left out)."""
    names = set()
    for top, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "chiprun_out"]
        names.update(files)
    return names


#: the hand-written documents that name at least one, and what each names
NAMED = {doc: named for doc, named in (
    (doc, _named_files(doc)) for doc in sorted(
        os.path.relpath(p, ROOT)
        for pat in ("README.md", "docs/*.md", "docs/how_to/*.md")
        for p in glob.glob(os.path.join(ROOT, pat)))) if named}


@pytest.mark.parametrize("doc", NAMED)
def test_doc_names_files_that_exist(doc, file_names):
    """A path must be a file; a bare name (``executor.py``) must be a file
    at the root or the name of a file somewhere in the tree."""
    gone = [p for p in NAMED[doc]
            if not (os.path.isfile(os.path.join(ROOT, p))
                    or ("/" not in p and p in file_names))]
    assert not gone, "%s names files that are not in the tree: %s" % (
        doc, sorted(set(gone)))
