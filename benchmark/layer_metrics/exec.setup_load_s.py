"""Seconds of set-up spent inside XLA's ``compile_or_get_cached``: loads
from the persistent cache on a warm machine, compiles on a cold one, every
program that went through it before the window opened
(``compile_cache.programs()``: one entry a program, with the moment it
ended on the monotonic clock).  The reference's own programs, compiled
after the window, are cut off."""


def read(run):
    if run["trace"] is None:
        return None
    from mxnet_tpu import compile_cache

    programs = getattr(compile_cache, "programs", None)
    if programs is None:
        return None
    t0 = run["window"]["t0"]
    before = [seconds for at, seconds, _hit in programs() if at < t0]
    return sum(before) if before else None
