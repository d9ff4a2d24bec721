"""Programs XLA compiled or loaded from its cache between the opening and
the closing of the window (``compile_cache.stats()``: every compile
consults the persistent cache, so hits plus misses count them).  Must read
0: warm-up belongs to set-up.  A run in which it is not 0 is not correct."""


def read(run):
    return run["window_compiles"]
