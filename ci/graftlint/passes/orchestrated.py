"""Orchestrated pass — a CI runner re-exposed through graftlint.

``check_compile_cache`` is not a source analyzer: it runs a fit+predict
workload twice in subprocesses.  It keeps its script (and its
run_tests.sh slot) but is ALSO addressable as a graftlint pass
(``--pass compile-cache``) so one entry point can drive the whole lint
surface and one JSON artifact can report it.  It is excluded from the
default pass set: the probe costs two subprocess jax sessions, far past
the <30 s lint budget."""

from __future__ import annotations

from ..core import Finding, Pass


class CompileCachePass(Pass):
    id = "compile-cache"
    title = "second run against a warm cache compiles nothing"
    orchestrated = True

    def run(self, sources, ctx):
        from ... import check_compile_cache

        rc = check_compile_cache.main()
        if rc:
            return [Finding(
                self.id, "ci/check_compile_cache.py", 0,
                "orchestrated-failure",
                "ci.check_compile_cache failed with exit status %r (its "
                "own output above has the details)" % (rc,))]
        return []
