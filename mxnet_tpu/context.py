"""Device contexts: ``mx.cpu()`` / ``mx.tpu()`` (+ ``mx.gpu()`` alias).

Reference: ``python/mxnet/context.py:1-126`` — ``Context(device_type,
device_id)``, the with-scope ``current_context``. TPU-native twist (the
BASELINE.json north star): device_type 4 is ``tpu`` and maps onto a JAX/PJRT
device; ``gpu`` is kept as an accepted alias for the local accelerator so
reference training scripts run unmodified.

A Context is hashable/comparable by (device_type_string-normalised, id) so it
keys executor caches exactly like the reference's Context does.
"""

from __future__ import annotations

import os
import threading

import jax

__all__ = ["Context", "cpu", "tpu", "gpu", "current_context", "num_tpus",
           "num_gpus", "measurement_context"]

# Accelerator device types all normalise to the local PJRT accelerator; this is
# what lets `--gpus 0` style reference scripts run on a TPU chip untouched.
_ACCEL_TYPES = ("tpu", "gpu")


class Context:
    """A device context. reference ``context.py:5-88``."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}
    _tls = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if isinstance(device_type, str):
                device_type = Context.devstr2type[device_type]
            self.device_typeid = device_type
            self.device_id = device_id

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self._norm_type() == other._norm_type()
            and self.device_id == other.device_id
        )

    def _norm_type(self):
        t = self.device_type
        return "accel" if t in _ACCEL_TYPES else "cpu"

    def __hash__(self):
        return hash((self._norm_type(), self.device_id))

    @property
    def platform(self):
        """PJRT platform of the device behind this context, ``"tpu"`` or
        ``"cpu"`` (``gpu`` is an alias of the local accelerator) — what
        device-dependent lowering keys on; touches no backend."""
        return "cpu" if self._norm_type() == "cpu" else "tpu"

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __str__ = __repr__

    # -- JAX mapping ------------------------------------------------------
    def jax_device(self):
        """The PJRT device backing this context.

        Contexts are PROCESS-LOCAL (a worker's ``mx.cpu(0)``/``mx.tpu(0)``
        is its own chip): under a ``jax.distributed`` process group the
        lookup uses addressable devices only — ``jax.devices()`` would
        enumerate every process's chips."""
        if self._norm_type() == "cpu":
            devs = jax.local_devices(backend="cpu")
            return devs[min(self.device_id, len(devs) - 1)]
        devs = _accel_devices()
        if not devs:
            raise RuntimeError(
                "Context %r: no accelerator (TPU) devices visible to JAX" % (self,)
            )
        if self.device_id >= len(devs):
            raise ValueError(
                "Context %r: only %d accelerator device(s) present" % (self, len(devs))
            )
        return devs[self.device_id]

    def __enter__(self):
        if not hasattr(Context._tls, "stack"):
            Context._tls.stack = [Context(_default_typeid(), 0)]
        Context._tls.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._tls.stack.pop()

    @staticmethod
    def current():
        if not hasattr(Context._tls, "stack"):
            Context._tls.stack = [Context(_default_typeid(), 0)]
        return Context._tls.stack[-1]


def _accel_devices():
    """Process-local TPU chips (none when JAX runs on the CPU)."""
    if jax.default_backend() != "tpu":
        return []
    return jax.local_devices()


def _default_typeid():
    return 4 if _accel_devices() else 1


def cpu(device_id=0):
    """reference ``context.py:90``"""
    return Context(1, device_id)


def gpu(device_id=0):
    """Alias for the local accelerator — keeps reference scripts runnable."""
    return Context(2, device_id)


def tpu(device_id=0):
    """The new first-class device type (BASELINE.json north star)."""
    return Context(4, device_id)


def num_tpus():
    return len(_accel_devices())


def measurement_context():
    """Context for entry points whose output is a measurement (the benchmark's
    ``module_fit`` family, ``benchmark_score.py``): the chip, and a loud
    failure when there is none.  The CPU is used only when the caller
    names it with ``JAX_PLATFORMS=cpu`` (as the tests do) — never as a
    silent fallback whose numbers would pass for the chip's."""
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        return cpu()
    if not _accel_devices():
        raise RuntimeError(
            "no TPU visible to JAX (default backend %r): a measurement "
            "entry point does not fall back to the CPU; set "
            "JAX_PLATFORMS=cpu to ask for it by name"
            % jax.default_backend())
    return tpu()


num_gpus = num_tpus


def current_context():
    """reference ``context.py:122``"""
    return Context.current()
