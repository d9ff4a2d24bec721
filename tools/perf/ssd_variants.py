#!/usr/bin/env python3
"""On the chip: the two recurrences of ``models/granite_hybrid.py`` alone at
the granite-4.0-h-micro cell's shapes (64 heads of 64, a float32 state of
``(128, 4096)`` a slot and layer).

* **A step's state update**, one layer over 64 slots (268 MB read and
  written): the form ``_Step.recur`` has (decay and push laid over the
  lanes, ``B`` and ``C`` down the sublanes, ``y`` the sum over ``d_state``
  in the same fusion), the same with the sum taken in two stages, and a
  pass that only multiplies the state by its decay, which is what these
  bytes can reach.  ``INNER`` updates in one program over the donated
  state; milliseconds and GB/s of one.
* **A prompt's chunked scan**, ``ops.ssm.ssd_scan`` at the three buckets:
  the kernel by heads a grid step (``_SSD_HEADS``: 8 is what the plan
  takes, 16 beside it), the ``jnp`` einsums the plan's refusals take, and
  those einsums with every product at the backend's default; ``INNER``
  scans in one program (a scan a dispatch reads the host's 0.35 ms and
  nothing else: PR 46's first reading), and how far the state and ``y`` of
  one scan from a carried state lie from the recurrence taken one position
  after another (elementwise float32: nothing rounded), as a share of
  their size.  **These times are of arrays that stay in VMEM between the
  scans of one program**: the einsums read within a sixth of the kernel
  here and cost the cell 0.9-2.2% of its tokens served in a prefill
  (PERF.md section 6, PR 46), so a form is judged in the cell.

    chiprun -- python tools/perf/ssd_variants.py
"""

import functools
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from mxnet_tpu.ops import ssm  # noqa: E402

SLOTS, HEADS, WIDTH, N = 64, 64, 64, 128
INNER, STEPS = 20, 5


def _lanes(v):
    return jnp.repeat(v, WIDTH, axis=-1)


def _as_served(state, xs, dt, a, b, c):
    """``granite_hybrid._Step.recur``'s update and sum."""
    state = _lanes(jnp.exp(dt * a))[:, None, :] * state \
        + (_lanes(dt) * xs)[:, None, :] * b[:, :, None]
    return state, (state * c[:, :, None]).sum(1)


def _two_stage_sum(state, xs, dt, a, b, c):
    """The same, the sum over ``d_state`` first across tiles of eight
    sublanes, then within one."""
    state = _lanes(jnp.exp(dt * a))[:, None, :] * state \
        + (_lanes(dt) * xs)[:, None, :] * b[:, :, None]
    s = state.shape[0]
    return state, (state * c[:, :, None]).reshape(
        s, N // 8, 8, -1).sum(1).sum(1)


def _decay_only(state, xs, dt, a, b, c):
    """One multiply a value: the bytes alone."""
    del b, c
    return _lanes(jnp.exp(dt * a))[:, None, :] * state, xs


def time_updates():
    rs = np.random.RandomState(0)
    f32 = jnp.float32
    xs = jnp.asarray(rs.normal(0, 1, (SLOTS, HEADS * WIDTH)), f32)
    dt = jnp.asarray(rs.uniform(1e-3, 0.1, (SLOTS, HEADS)), f32)
    a = -jnp.asarray(rs.uniform(1, 16, (HEADS,)), f32)
    b, c = (jnp.asarray(rs.normal(0, 1, (SLOTS, N)), f32) for _ in range(2))
    moved = 2 * SLOTS * N * HEADS * WIDTH * 4
    for fn in (_as_served, _two_stage_sum, _decay_only):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def many(state, fn=fn):
            def one(i, carry):
                state, acc = carry
                state, y = fn(state, xs + i.astype(f32), dt, a, b, c)
                return state, acc + y
            return jax.lax.fori_loop(0, INNER, one,
                                     (state, jnp.zeros_like(xs)))

        state = jnp.zeros((SLOTS, N, HEADS * WIDTH), f32)
        state, acc = many(state)
        acc.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(STEPS):
            state, acc = many(state)
        acc.block_until_ready()
        ms = (time.perf_counter() - t0) / STEPS / INNER * 1e3
        print("state update %-16s %.3f ms a layer, %.0f GB/s" % (
            fn.__name__.strip("_"), ms, moved / ms / 1e6), flush=True)


def _recurrence(x, dt, a, b, c, d, state):
    """One position after another, elementwise in float32: no product is
    rounded, so this is what a form's state is held against."""
    def position(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = _lanes(jnp.exp(dt_t * a))[None, :] * s \
            + b_t[:, None] * (_lanes(dt_t) * x_t)[None, :]
        return s, (s * c_t[:, None]).sum(0) + _lanes(d) * x_t
    return jax.lax.scan(position, state, (x, dt, b, c))


def _off(got, want):
    """The distance as a share of ``want``'s size."""
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _all_default(*args, q):
    """The einsums with the carried state's two products at the default
    too: what rounding them costs the state."""
    exact, ssm._EXACT = ssm._EXACT, None
    try:
        return ssm._ssd_xla(*args, q)
    finally:
        ssm._EXACT = exact


def time_scans():
    rs = np.random.RandomState(1)
    f32 = jnp.float32
    for t in (128, 512, 1024):
        args = (rs.normal(0, 1, (t, HEADS * WIDTH)),
                rs.uniform(1e-3, 0.1, (t, HEADS)),
                -rs.uniform(1, 16, (HEADS,)), rs.normal(0, 1, (t, N)),
                rs.normal(0, 1, (t, N)), np.ones((HEADS,)),
                rs.normal(0, 1, (N, HEADS * WIDTH)))
        args = tuple(jnp.asarray(v, f32) for v in args)
        want_s, want_y = jax.jit(_recurrence)(*args)
        (heads, q), reason = ssm.ssd_scan_plan(args[0], args[1], args[3])
        assert reason is None, reason
        forms = [("kernel, %d heads a step" % h, functools.partial(
            ssm._ssd_pallas, heads=h, q=q)) for h in (heads, 2 * heads)]
        forms += [("jnp einsums", functools.partial(ssm._ssd_xla, q=q)),
                  ("jnp einsums, all default",
                   functools.partial(_all_default, q=q))]
        for name, fn in forms:
            @jax.jit
            def many(state, fn=fn):
                def one(i, carry):
                    state, acc = carry
                    state, y = fn(args[0] + i.astype(f32), *args[1:6],
                                  state)
                    return state, acc + y
                return jax.lax.fori_loop(0, INNER, one,
                                         (state, jnp.zeros_like(args[0])))

            try:
                jax.block_until_ready(many(args[6]))
            except Exception as e:  # noqa: broad-except — the compiler's
                # refusal of a size the plan does not take is a reading
                print("ssd_scan %4d positions, %-24s refused: %s" % (
                    t, name, str(e)[:200].replace("\n", " ")), flush=True)
                continue
            t0 = time.perf_counter()
            for _ in range(STEPS):
                out = many(args[6])
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / STEPS / INNER * 1e3
            got_s, got_y = jax.jit(fn)(*args)
            print("ssd_scan %4d positions, %-24s %.3f ms; off the "
                  "recurrence: the state %.2e of its size, y %.2e" % (
                      t, name, ms, _off(got_s, want_s), _off(got_y, want_y)),
                  flush=True)


if __name__ == "__main__":
    print("device: %s" % jax.devices()[0].device_kind, flush=True)
    time_updates()
    time_scans()
