#!/usr/bin/env python3
"""On the chip: ways to put one new row a slot into a ring of K or V,
``cache (S, n, W, d)`` with ``rows[i]`` at ``[i, :, at[i]]``, timed alone
at the Phi-4-mini-flash cell's sizes (16 such arrays a step), and the
engine's sampling over a wide vocabulary beside them.

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

from mxnet_tpu.models import exaone_moe as xm  # noqa: E402

STEPS = 50


def _scatter(cache, rows, at):
    return cache.at[jnp.arange(cache.shape[0]), :, at].set(
        rows.astype(cache.dtype))


def timed(name, fn, *args, donate=()):
    jitted = jax.jit(fn, donate_argnums=donate)
    try:
        t0 = time.monotonic()
        out = jitted(*args)
        jax.block_until_ready(out)
        t_compile = time.monotonic() - t0
        held = out if donate else args[0]
        t0 = time.monotonic()
        for _ in range(STEPS):
            held = jitted(held, *args[1:]) if donate else jitted(*args)
        jax.block_until_ready(held)
        ms = 1e3 * (time.monotonic() - t0) / STEPS
        print("VARIANT " + json.dumps({"variant": name, "ms": ms,
                                       "first_call_s": t_compile}),
              flush=True)
        return held
    except Exception as e:  # noqa: broad-except — a refusal is a reading
        print("VARIANT " + json.dumps({
            "variant": name, "error": "%s: %s" % (type(e).__name__,
                                                  str(e)[:400])}),
              flush=True)


def main():
    rs = np.random.RandomState(0)
    for shape in ((128, 10, 512, 128), (128, 10, 4096, 128)):
        s, n, w, d = shape
        rows = jnp.asarray(rs.normal(0, 1, (s, n, d)), jnp.bfloat16)
        at = jnp.asarray(rs.randint(0, w, (s,)), jnp.int32)
        want = None
        for name, fn in (("select", xm.write_ring),
                         ("update-slices", xm.write_full),
                         ("scatter", _scatter)):
            cache = jnp.zeros(shape, jnp.bfloat16) + jnp.bfloat16(0.5)
            out = timed("%s %s" % (name, shape), fn, cache, rows, at,
                        donate=(0,))
            if out is None:
                continue
            # fifty-one writes of the same rows at the same places
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
