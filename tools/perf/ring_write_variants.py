#!/usr/bin/env python3
"""On the chip: ways to put one new row a slot into a heads-major cache of
K or V, ``cache (S, n, R, d)`` with ``rows[i]`` at ``[i, :, at[i]]``, timed
alone at the decode cells' sizes (the Phi-4-mini-flash rings and shared
layer, K-EXAONE's full layer and rings), and the engine's sampling over a
wide vocabulary beside them.

A write is timed as the device takes it: ``INNER`` of them in one program,
each at other rows, over the donated cache.  (Timed a call a dispatch, as
this tool did at PR 31, nothing reads under the 0.1 ms the host takes to
launch a program.)

    chiprun -- python tools/perf/ring_write_variants.py
"""

import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from mxnet_tpu.ops import attention  # noqa: E402

STEPS = 50
#: writes in one program
INNER = 200


def _select(cache, rows, at):
    """A pass over the whole array (K-EXAONE's rings until PR 32)."""
    hit = jnp.arange(cache.shape[2])[None, :] == at[:, None]
    return jnp.where(hit[:, None, :, None],
                     rows[:, :, None].astype(cache.dtype), cache)


def _scatter(cache, rows, at):
    return cache.at[jnp.arange(cache.shape[0]), :, at].set(
        rows.astype(cache.dtype))


def _kernel(cache, rows, at):
    """``write_slot_rows``'s Pallas path with the plan's group."""
    group, reason = attention.write_slot_rows_plan(cache, rows)
    if reason is not None:
        raise RuntimeError("the plan refuses: %s" % reason)
    return attention._slot_write_pallas(cache, rows.astype(cache.dtype), at,
                                        group)


def timed_write(name, fn, cache, rows, at):
    """``INNER`` writes in one program, write ``i`` a tile further on;
    prints the milliseconds one takes and returns the cache after."""
    def writes(cache, rows, at):
        return jax.lax.fori_loop(
            0, INNER,
            lambda i, held: fn(held, rows, (at + 16 * i) % cache.shape[2]),
            cache)

    jitted = jax.jit(writes, donate_argnums=(0,))
    try:
        t0 = time.monotonic()
        cache = jax.block_until_ready(jitted(cache, rows, at))
        t_compile = time.monotonic() - t0
        best = None
        for _ in range(3):
            t0 = time.monotonic()
            cache = jax.block_until_ready(jitted(cache, rows, at))
            ms = 1e3 * (time.monotonic() - t0) / INNER
            best = ms if best is None else min(best, ms)
        print("VARIANT " + json.dumps({"variant": name, "ms": best,
                                       "first_call_s": t_compile}),
              flush=True)
        return cache
    except Exception as e:  # noqa: broad-except — a refusal is a reading
        print("VARIANT " + json.dumps({
            "variant": name, "error": "%s: %s" % (type(e).__name__,
                                                  str(e)[:400])}),
              flush=True)


def timed(name, fn, *args):
    jitted = jax.jit(fn)
    t0 = time.monotonic()
    jax.block_until_ready(jitted(*args))
    t_compile = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(STEPS):
        out = jitted(*args)
    jax.block_until_ready(out)
    ms = 1e3 * (time.monotonic() - t0) / STEPS
    print("VARIANT " + json.dumps({"variant": name, "ms": ms,
                                   "first_call_s": t_compile}), flush=True)


def main():
    rs = np.random.RandomState(0)
    for shape in ((128, 10, 512, 128), (128, 10, 4096, 128),
                  (256, 8, 4096, 128), (256, 8, 128, 128)):
        s, n, w, d = shape
        rows = jnp.asarray(rs.normal(0, 1, (s, n, d)), jnp.bfloat16)
        at = jnp.asarray(rs.randint(0, w, (s,)), jnp.int32)
        want = None
        for name, fn in (("update-slices", attention._slot_write_xla),
                         ("select", _select),
                         ("scatter", _scatter),
                         ("slot_write kernel", _kernel)):
            if name in ("select", "scatter") and w > 512:
                continue        # a pass over 1.3 or 2.1 GB: PR 31 has them
            cache = jnp.zeros(shape, jnp.bfloat16) + jnp.bfloat16(0.5)
            out = timed_write("%s %s" % (name, shape), fn, cache, rows, at)
            if out is None:
                continue
            # the same rows at the same places, whatever wrote them
            got = np.asarray(out[:, :, :, :8].astype(jnp.float32))
            if want is None:
                want = got
            print("  equal to the first: %s" % bool((got == want).all()))
            del out

    logits = jnp.asarray(rs.normal(0, 1, (128, 200064)), jnp.float32)
    seeds = jnp.arange(128, dtype=jnp.uint32)
    temps = jnp.zeros((128,), jnp.float32)

    def keys(seeds, pos):
        return jax.vmap(lambda s, p: jax.random.fold_in(
            jax.random.PRNGKey(s), p))(seeds, pos)

    def both(logits, seeds, temps):
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)
        drawn = jax.vmap(lambda k, lg, t: jax.random.categorical(
            k, lg / jnp.maximum(t, 1e-6)))(
                keys(seeds, seeds.astype(jnp.int32)), logits,
                temps).astype(jnp.int32)
        return jnp.where(temps > 0.0, drawn, greedy)

    def only_if_asked(logits, seeds, temps):
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)
        return jax.lax.cond(
            jnp.any(temps > 0.0),
            functools.partial(both, logits, seeds, temps), lambda: greedy)

    timed("sample: drawn and greedy, one chosen", both, logits, seeds, temps)
    timed("sample: drawn only where a slot asks", only_if_asked, logits,
          seeds, temps)
    timed("sample: ... and one does", only_if_asked, logits, seeds,
          temps.at[3].set(0.7))


if __name__ == "__main__":
    main()
