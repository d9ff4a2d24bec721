"""Global random state (``mx.random``).

Reference: ``python/mxnet/random.py`` + ``MXRandomSeed`` (seed is global,
per-device generators live in the resource manager, ``src/resource.cc:66``).
JAX PRNG is explicit-key, so the framework keeps one global key and splits
off a subkey per imperative sampling call; symbolic executors fold a per-call
key in as a hidden input (see ``executor.py``).  ``mx.random.seed(n)`` makes
everything reproducible exactly like the reference's global seed.
"""

from __future__ import annotations

import threading

import jax

__all__ = ["seed", "next_key", "get_state", "set_state", "uniform",
           "normal", "randint"]

_lock = threading.Lock()
# lazy: building a PRNGKey runs a jit computation, which would initialize
# the jax backend (and claim the chip) at package-import time — breaking
# host-only processes (PS server, decode workers, a parent that starts
# children which need the chip) and any later platform pinning
_key = None


_seed_value = 0


def seed(seed_state):
    """reference ``random.py:40`` / MXRandomSeed.

    Also seeds numpy's global RNG: the reference's initializers draw from
    the engine RNG that MXRandomSeed controls, so ``mx.random.seed(n)``
    makes ``init_params`` reproducible there — here the initializer zoo
    samples via ``np.random``, and seeding it keeps that contract."""
    import numpy as _np

    global _key, _seed_value
    with _lock:
        _seed_value = int(seed_state)
        _key = jax.random.PRNGKey(int(seed_state))
        _np.random.seed(int(seed_state) & 0xFFFFFFFF)


def get_seed():
    """The last value passed to ``seed()`` (0 before any call) — the
    shared base for multi-process SPMD keys, which must be identical on
    every process."""
    return _seed_value


def next_key():
    """Split off a fresh subkey from the global state."""
    global _key
    with _lock:
        if _key is None:
            _key = jax.random.PRNGKey(0)
        _key, sub = jax.random.split(_key)
    return sub


def get_state():
    """JSON-able capture of the global RNG state — the jax key, the seed
    base, and numpy's generator — for exact mid-epoch training resume
    (docs/resilience.md): a resumed run draws the same sample stream an
    uninterrupted run would have."""
    import numpy as _np

    with _lock:
        key = None if _key is None \
            else _np.asarray(_key).astype(_np.uint32).tolist()
        seed_value = _seed_value
    kind, keys, pos, has_gauss, cached = _np.random.get_state()
    return {"seed": seed_value, "key": key,
            "np_state": {"kind": kind, "keys": keys.tolist(), "pos": pos,
                         "has_gauss": has_gauss, "cached": cached}}


def set_state(state):
    """Inverse of :func:`get_state`."""
    import numpy as _np

    global _key, _seed_value
    with _lock:
        _seed_value = int(state.get("seed", 0))
        key = state.get("key")
        _key = None if key is None \
            else jax.numpy.asarray(_np.asarray(key, _np.uint32))
    nps = state.get("np_state")
    if nps:
        _np.random.set_state((nps["kind"],
                              _np.asarray(nps["keys"], _np.uint32),
                              int(nps["pos"]), int(nps["has_gauss"]),
                              float(nps["cached"])))


def _nd():
    """ndarray imports this module at its top, so a top-level back-import
    would cycle; a sys.modules lookup also avoids the package import lock
    — kvstore-server handler threads run while ``import mxnet_tpu`` is
    still blocked in the auto server loop, and a ``from . import`` there
    deadlocks (see kvstore_server._pkg_mod)."""
    import sys as _sys

    mod = _sys.modules.get(__package__ + ".ndarray")
    if mod is None:  # pragma: no cover - only during partial init
        from . import ndarray as mod
    return mod


def uniform(low=0, high=1, shape=None, ctx=None, dtype="float32", out=None):
    return _nd().uniform(low=low, high=high,
                         shape=(1,) if shape is None else shape,
                         dtype=dtype, ctx=ctx, out=out)


def normal(loc=0, scale=1, shape=None, ctx=None, dtype="float32", out=None):
    return _nd().normal(loc=loc, scale=scale,
                        shape=(1,) if shape is None else shape,
                        dtype=dtype, ctx=ctx, out=out)


def randint(low, high, shape=(1,), ctx=None, dtype="int32"):
    import numpy as np

    k = next_key()
    arr = jax.random.randint(k, shape, low, high, dtype=np.dtype(dtype))
    return _nd().NDArray._from_jax(arr, ctx)
