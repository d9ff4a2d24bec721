"""Data iterators (``mx.io``).

Reference: ``python/mxnet/io.py`` + ``src/io/`` (SURVEY §2.5): ``DataIter``
ABC, ``NDArrayIter``, ``MNISTIter`` (idx format, distributed part_index
sharding — ``src/io/iter_mnist.cc``), ``CSVIter``, ``ResizeIter``,
``PrefetchingIter`` (the ``iter_prefetcher.h`` background double-buffer).

TPU notes: batches land on device via the NDArray layer; PrefetchingIter
overlaps host decode with device compute (the PJRT async dispatch gives the
copy/compute overlap the reference got from pinned-memory copy workers).
"""

from __future__ import annotations

import contextlib
import gzip
import logging
import os
import struct
import threading

import numpy as np

from .base import MXNetError
from .ndarray import NDArray, array

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "MNISTIter",
           "CSVIter", "ResizeIter", "PrefetchingIter", "DevicePrefetchIter",
           "ElasticShardIter", "ImageRecordIter", "corrupt_skip_count",
           "reset_corrupt_skip_count"]


class DataDesc:
    """(name, shape, dtype, layout) — reference io.py DataDesc namedtuple."""

    def __init__(self, name, shape, dtype=np.float32, layout="NCHW"):
        self.name = name
        self.shape = tuple(shape)
        self.dtype = dtype
        self.layout = layout

    def __iter__(self):
        yield self.name
        yield self.shape

    def __getitem__(self, i):
        return (self.name, self.shape)[i]

    def __repr__(self):
        return "DataDesc[%s,%s,%s,%s]" % (self.name, self.shape, self.dtype,
                                          self.layout)

    def __eq__(self, other):
        return tuple(self) == tuple(other)


class DataBatch:
    """reference ``include/mxnet/io.h`` DataBatch / io.py"""

    def __init__(self, data, label=None, pad=0, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label if label is not None else []
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter:
    """reference ``io.py:126``"""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError()

    def getdata(self):
        raise NotImplementedError()

    def getlabel(self):
        raise NotImplementedError()

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError()

    # -- iterator-state protocol (preemption-tolerant fit) ----------------
    def state_dict(self):
        """JSON-able mid-epoch position of this iterator.  Restoring it
        with :meth:`load_state_dict` on a freshly-constructed equivalent
        iterator makes ``next()`` yield exactly the batch that would
        have come next — the contract ``Module.fit``'s exact mid-epoch
        resume builds on (docs/resilience.md).  Iterators without the
        protocol raise; fit then degrades to epoch-boundary resume."""
        raise NotImplementedError(
            "%s does not implement the iterator-state protocol "
            "(state_dict/load_state_dict); mid-epoch checkpoint resume "
            "degrades to the epoch boundary" % type(self).__name__)

    def load_state_dict(self, state):
        """Restore a :meth:`state_dict` capture."""
        raise NotImplementedError(
            "%s does not implement the iterator-state protocol"
            % type(self).__name__)


def _init_data(data, allow_empty, default_name):
    """reference io.py _init_data — normalize to list of (name, numpy)."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {"_%d_%s" % (i, default_name): d
                    for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of "
                        "them or dict with them as values")
    out = {}
    for k, v in data.items():
        out[k] = v.asnumpy() if isinstance(v, NDArray) else np.asarray(v)
    return list(sorted(out.items()))


class NDArrayIter(DataIter):
    """reference ``io.py:453`` — batching/shuffle/pad over in-memory arrays."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)
        self.num_data = self.data[0][1].shape[0]
        if shuffle:
            idx = np.arange(self.num_data)
            np.random.shuffle(idx)
            self.data = [(k, v[idx]) for k, v in self.data]
            self.label = [(k, v[idx]) for k, v in self.label]
        if last_batch_handle == "discard":
            new_n = self.num_data - self.num_data % batch_size
            self.data = [(k, v[:new_n]) for k, v in self.data]
            self.label = [(k, v[:new_n]) for k, v in self.label]
            self.num_data = new_n
        assert self.num_data >= batch_size, \
            "batch_size needs to be smaller than data size."
        self.cursor = -batch_size
        self.last_batch_handle = last_batch_handle

    @property
    def provide_data(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.label]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self.last_batch_handle == "roll_over" and \
                self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) \
                % self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=None)
        raise StopIteration

    def _getdata(self, data_source):
        assert self.cursor < self.num_data, "DataIter needs reset."
        if self.cursor + self.batch_size <= self.num_data:
            return [array(v[self.cursor:self.cursor + self.batch_size])
                    for _, v in data_source]
        pad = self.batch_size - self.num_data + self.cursor
        return [array(np.concatenate([v[self.cursor:], v[:pad]], axis=0))
                for _, v in data_source]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0

    def state_dict(self):
        # the cursor IS the iterator state: shuffle/discard permute the
        # backing arrays at construction, so an equivalently-constructed
        # iterator (same data/seed) + cursor lands on the same batch
        return {"type": "NDArrayIter", "cursor": self.cursor,
                "num_data": self.num_data, "batch_size": self.batch_size}

    def load_state_dict(self, state):
        if state.get("type", "NDArrayIter") != "NDArrayIter":
            raise MXNetError("iterator state of type %r cannot restore "
                             "onto NDArrayIter" % (state.get("type"),))
        if state.get("num_data", self.num_data) != self.num_data or \
                state.get("batch_size", self.batch_size) != self.batch_size:
            raise MXNetError(
                "NDArrayIter state (num_data=%s, batch_size=%s) does not "
                "match this iterator (num_data=%d, batch_size=%d)"
                % (state.get("num_data"), state.get("batch_size"),
                   self.num_data, self.batch_size))
        self.cursor = int(state["cursor"])


def _read_idx(path):
    """Read an MNIST idx file (gz or raw) — ``src/io/iter_mnist.cc`` format."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        magic = struct.unpack(">I", f.read(4))[0]
        ndim = magic & 0xFF
        dims = struct.unpack(">" + "I" * ndim, f.read(4 * ndim))
        data = np.frombuffer(f.read(), dtype=np.uint8)
        return data.reshape(dims)


class MNISTIter(DataIter):
    """reference ``src/io/iter_mnist.cc:241`` — idx reader with shuffle and
    distributed sharding (part_index/num_parts)."""

    def __init__(self, image="train-images-idx3-ubyte",
                 label="train-labels-idx1-ubyte", batch_size=128,
                 shuffle=True, flat=False, seed=0, silent=False,
                 num_parts=1, part_index=0, **kwargs):
        super().__init__(batch_size)
        img = _read_idx(image).astype(np.float32) / 255.0
        lab = _read_idx(label).astype(np.float32)
        if shuffle:
            rs = np.random.RandomState(seed)
            idx = rs.permutation(img.shape[0])
            img, lab = img[idx], lab[idx]
        # distributed shard (reference partitions by part_index/num_parts)
        n = img.shape[0] // num_parts
        img = img[part_index * n:(part_index + 1) * n]
        lab = lab[part_index * n:(part_index + 1) * n]
        img = img.reshape(img.shape[0], -1) if flat \
            else img.reshape(img.shape[0], 1, img.shape[1], img.shape[2])
        self._inner = NDArrayIter(
            {"data": img}, {"softmax_label": lab}, batch_size,
            shuffle=False, last_batch_handle="discard")

    provide_data = property(lambda s: s._inner.provide_data)
    provide_label = property(lambda s: s._inner.provide_label)

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()

    def iter_next(self):
        return self._inner.iter_next()

    def state_dict(self):
        return {"type": "MNISTIter", "inner": self._inner.state_dict()}

    def load_state_dict(self, state):
        self._inner.load_state_dict(state["inner"])


class CSVIter(DataIter):
    """reference ``src/io/iter_csv.cc:132``"""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, **kwargs):
        super().__init__(batch_size)
        data = np.loadtxt(data_csv, delimiter=",", dtype=np.float32,
                          ndmin=2).reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype=np.float32,
                               ndmin=2).reshape((-1,) + tuple(label_shape))
        else:
            label = np.zeros((data.shape[0],), dtype=np.float32)
        self._inner = NDArrayIter(
            {"data": data}, {"label": label}, batch_size,
            last_batch_handle="pad" if round_batch else "discard",
            label_name="label")

    provide_data = property(lambda s: s._inner.provide_data)
    provide_label = property(lambda s: s._inner.provide_label)

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()

    def state_dict(self):
        return {"type": "CSVIter", "inner": self._inner.state_dict()}

    def load_state_dict(self, state):
        self._inner.load_state_dict(state["inner"])


class ResizeIter(DataIter):
    """reference ``io.py:216`` — resize an iterator to a fixed #batches."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad

    def state_dict(self):
        return {"type": "ResizeIter", "cur": self.cur,
                "inner": self.data_iter.state_dict()}

    def load_state_dict(self, state):
        self.cur = int(state["cur"])
        self.data_iter.load_state_dict(state["inner"])


class PrefetchingIter(DataIter):
    """reference ``io.py:281`` — background thread double-buffering (the
    python analog of ``src/io/iter_prefetcher.h:49``).

    Owns one daemon thread per sub-iterator; call :meth:`close` (or use
    the iterator as a context manager) to stop and join them — relying
    on ``__del__`` alone leaks N live threads for as long as the GC
    defers the collection."""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0][1][0]
        self.data_ready = [threading.Event() for _ in range(self.n_iter)]
        self.data_taken = [threading.Event() for _ in range(self.n_iter)]
        for e in self.data_taken:
            e.set()
        self.started = True
        self.current_batch = [None for _ in range(self.n_iter)]
        self.next_batch = [None for _ in range(self.n_iter)]
        self._errors = [None for _ in range(self.n_iter)]
        # iterator-state protocol: each produce first captures the
        # sub-iterator's PRE-batch state, so state_dict() can report the
        # position of the buffered batch the consumer has not seen yet
        self._capture_state = True
        self._pending_state = [None for _ in range(self.n_iter)]

        def prefetch_func(self, i):
            while True:
                self.data_taken[i].wait()
                if not self.started:
                    break
                try:
                    self.next_batch[i] = self._produce(i)
                except StopIteration:
                    self.next_batch[i] = None
                except BaseException as e:  # noqa: BLE001
                    # surface producer crashes on the consumer thread —
                    # swallowing them would deadlock iter_next's wait
                    self._errors[i] = e
                    self.next_batch[i] = None
                self.data_taken[i].clear()
                self.data_ready[i].set()

        self.prefetch_threads = [
            threading.Thread(target=prefetch_func, args=[self, i], daemon=True)
            for i in range(self.n_iter)]
        for thread in self.prefetch_threads:
            thread.start()

    def _produce(self, i):
        """Produce sub-iterator ``i``'s next batch — runs ON the prefetch
        thread.  Captures the inner iterator's pre-batch state first
        (see :meth:`state_dict`); the hook :meth:`_produce_batch` is what
        :class:`DevicePrefetchIter` overrides to add the host→device
        copy to the background work."""
        if self._capture_state:
            try:
                self._pending_state[i] = self.iters[i].state_dict()
            except NotImplementedError:
                # the inner iterator has no state protocol: stop asking
                # (once per wrapper, not once per batch)
                self._capture_state = False
                self._pending_state = None
        return self._produce_batch(i)

    def _produce_batch(self, i):
        return self.iters[i].next()

    def state_dict(self):
        """State of the *consumer* position: the producers are drained
        (parked on ``data_taken``) and the captured pre-batch state of
        the buffered batch is returned — restoring it re-produces that
        buffered (never-consumed) batch first, so a wrapper snapshot
        taken after fit consumed ``k`` batches resumes at batch
        ``k + 1`` exactly, prefetch depth and all."""
        self.drain()
        if not self._capture_state or self._pending_state is None \
                or any(s is None for s in self._pending_state):
            raise NotImplementedError(
                "%s cannot snapshot: wrapped iterator(s) lack the "
                "state protocol" % type(self).__name__)
        return {"type": type(self).__name__,
                "inner": [dict(s) for s in self._pending_state]}

    def drain(self):
        """Block until every in-flight produce completes and the
        producer threads are parked (on ``data_taken``).  The inner
        iterators are then safe to mutate externally — e.g. an elastic
        reshard — until ``next()``/``reset()``/``load_state_dict``
        re-arms production."""
        for e in self.data_ready:
            e.wait()

    def load_state_dict(self, state):
        """Restore: park the producers, rewind the inner iterators to
        the captured positions, drop the stale buffered batches, and
        re-arm — the next produced batch comes from the restored
        state."""
        inner = state["inner"]
        if len(inner) != self.n_iter:
            raise MXNetError(
                "prefetch state has %d sub-iterators, wrapper has %d"
                % (len(inner), self.n_iter))
        self.drain()
        for i in range(self.n_iter):
            self.iters[i].load_state_dict(inner[i])
        self._errors = [None for _ in range(self.n_iter)]
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()

    def close(self):
        """Stop the prefetch threads and JOIN them (idempotent).  After
        ``close()`` the iterator must not be used again."""
        self.started = False
        for e in self.data_taken:
            e.set()
        for t in self.prefetch_threads:
            if t is not threading.current_thread():
                t.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: broad-except — interpreter-shutdown GC
            pass

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def reset(self):
        self.drain()
        for i in self.iters:
            i.reset()
        # stale producer errors must not outlive the reset
        self._errors = [None for _ in range(self.n_iter)]
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()

    def iter_next(self):
        for e in self.data_ready:
            e.wait()
        err = next((e for e in self._errors if e is not None), None)
        if err is not None:
            # clear EVERY producer's error (a stale sibling error must not
            # poison the next, clean round), invalidate the half-populated
            # batches, and re-arm the producers so a caller that catches
            # the error can keep iterating
            self._errors = [None for _ in range(self.n_iter)]
            for j in range(self.n_iter):
                self.next_batch[j] = None
                self.data_ready[j].clear()
                self.data_taken[j].set()
            raise err
        if self.next_batch[0] is None:
            for i in self.next_batch:
                assert i is None, "Number of entry mismatches between iters"
            return False
        for batch in self.next_batch:
            assert batch.pad == self.next_batch[0].pad, \
                "Different pad number in the data batches"
        self.current_batch = DataBatch(
            sum([batch.data for batch in self.next_batch], []),
            sum([batch.label for batch in self.next_batch], []),
            self.next_batch[0].pad, self.next_batch[0].index)
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class DevicePrefetchIter(PrefetchingIter):
    """Device-side double-buffered prefetch: the background thread also
    runs each batch array's ``jax.device_put``, so the host→device copy
    of batch N overlaps the device compute of batch N-1 — completing on
    the device link what :class:`PrefetchingIter` does for host decode
    (``iter_prefetcher.h`` took decode off the critical path; the H2D
    copy stayed on it until now).

    ``placer(name, array) -> NDArray`` does the placement; ``Module``
    passes its ``_device_put_batch``, which recomputes the module's
    mesh sharding per input — on a mesh-bound module (``context=Mesh``
    or ``fit(kvstore='mesh')``) the batch lands pre-sharded over the
    data axis, never on one device with the step left to re-lay it
    out.  Alternatively pass ``device`` (a jax device) or ``sharding``
    (a ``jax.sharding.Sharding``, e.g. a mesh ``NamedSharding``) for a
    module-free placement target.  ``fit(prefetch_to_device=True)``
    (or ``MXNET_DEVICE_PREFETCH=1``) wires this in around
    ``train_data`` and closes it deterministically.
    """

    def __init__(self, iters, placer=None, device=None, sharding=None,
                 rename_data=None, rename_label=None):
        if placer is None:
            target = sharding if sharding is not None else device
            if target is None:
                raise MXNetError("DevicePrefetchIter needs a placer, a "
                                 "device, or a sharding")

            def placer(_name, arr):
                import jax

                from .ndarray import NDArray

                raw = arr._transfer_src() if isinstance(arr, NDArray) \
                    else np.asarray(arr)
                return NDArray._from_jax(jax.device_put(raw, target))

        # set before super().__init__: the prefetch threads start inside
        # it and call _produce immediately
        self._placer = placer
        self._names_cache = {}
        super().__init__(iters, rename_data=rename_data,
                         rename_label=rename_label)

    def _names(self, i):
        cached = self._names_cache.get(i)
        if cached is None:
            cached = ([d.name for d in self.iters[i].provide_data],
                      [d.name for d in self.iters[i].provide_label])
            self._names_cache[i] = cached
        return cached

    def _produce_batch(self, i):
        batch = self.iters[i].next()
        data_names, label_names = self._names(i)
        batch.data = [self._placer(n, a)
                      for n, a in zip(data_names, batch.data)]
        if batch.label:
            batch.label = [self._placer(n, a)
                           for n, a in zip(label_names, batch.label)]
        return batch


class ElasticShardIter(DataIter):
    """Elastic sharded data service (docs/resilience.md "Elastic
    membership & resharding"): serves this worker's deterministic shard
    of a record-addressable dataset, recomputes shard ownership on
    membership change, and carries a **global sample-accounting ledger**
    so an elasticity event neither skips nor repeats records.

    Sharding is a pure function: the records *remaining* in the current
    data epoch (all minus the ledger) are partitioned by
    :func:`mxnet_tpu.elastic.shard_records` over ``(sorted ranks,
    membership epoch)`` — every member computes the identical partition,
    and all members serve the same number of batches per assignment
    (short shards wrap-pad their tail batch; pad slots are presentation
    copies, excluded from the ledger).

    The ledger is *derivable*: because synchronous training keeps ranks
    in batch lockstep, the globally-consumed set at cursor ``pos`` is
    ``base ∪ (every rank's first pos batches of its shard)`` — a pure
    function of the state dict, with no runtime cross-worker union.  Any
    one rank's snapshot therefore carries the correct GLOBAL ledger for
    its boundary, which is exactly what the reshard cycle adopts when it
    rolls every member back to the newest snapshot generation.

    Sources: in-memory arrays (``data``/``label``, NDArrayIter-style) or
    ``record_reader`` — a callable ``(ids) -> (data_arrays,
    label_arrays)`` over e.g. an ``MXIndexedRecordIO`` file — with
    ``num_records``.
    """

    def __init__(self, data=None, label=None, batch_size=1, rank=0,
                 ranks=(0,), membership_epoch=0, record_reader=None,
                 num_records=None, data_name="data",
                 label_name="softmax_label", audit=False):
        super().__init__(batch_size)
        self._lock = threading.Lock()
        self.audit = bool(audit)
        if record_reader is not None:
            if num_records is None:
                raise MXNetError(
                    "ElasticShardIter(record_reader=...) needs "
                    "num_records")
            self._reader = record_reader
            self._n = int(num_records)
            probe_d, probe_l = record_reader([0])

            def _descs(arrays, default):
                names = [default] if len(arrays) == 1 else \
                    ["_%d_%s" % (i, default) for i in range(len(arrays))]
                return [DataDesc(nm,
                                 (batch_size,) + np.asarray(a).shape[1:],
                                 np.asarray(a).dtype)
                        for nm, a in zip(names, arrays)]

            self._data_descs = _descs(probe_d, data_name)
            self._label_descs = _descs(probe_l, label_name)
            self._arrays = None
        else:
            self._reader = None
            self._arrays = (_init_data(data, allow_empty=False,
                                       default_name=data_name),
                            _init_data(label, allow_empty=True,
                                       default_name=label_name))
            self._n = self._arrays[0][0][1].shape[0]
            self._data_descs = [
                DataDesc(k, (batch_size,) + v.shape[1:], v.dtype)
                for k, v in self._arrays[0]]
            self._label_descs = [
                DataDesc(k, (batch_size,) + v.shape[1:], v.dtype)
                for k, v in self._arrays[1]]
        if self._n < 1:
            raise MXNetError("ElasticShardIter: empty dataset")
        self.rank = rank
        self.ranks = sorted(ranks)
        self.membership_epoch = int(membership_epoch)
        self.data_epoch = 0
        self.base = set()        # global ledger at this assignment's start
        self._pos = 0            # batches served under this assignment
        self._committed = {}     # data_epoch -> ids THIS rank committed
        # sample-accounting ledger: the reshard machinery only ever
        # reads the current and previous data epoch, so reset() prunes
        # older epochs by default — ``audit=True`` keeps the whole-job
        # trail (the chaos/acceptance tests assert exactly-once over
        # EVERY epoch of a run)
        self.applied = {}        # data_epoch -> {id: surviving-train count}
        self.history = []        # closed assignment segments (diagnostics)
        with self._lock:
            self._recompute()

    # -- pure shard/ledger math (lock held) -------------------------------
    def _recompute(self):
        from .elastic import shard_records

        remaining = [i for i in range(self._n) if i not in self.base]
        if remaining:
            self._parts = shard_records(remaining, self.ranks,
                                        self.membership_epoch)
        else:
            self._parts = {r: [] for r in self.ranks}
        self._owned = list(self._parts.get(self.rank, []))
        longest = max((len(p) for p in self._parts.values()), default=0)
        self._nbatches = -(-longest // self.batch_size) if longest else 0

    def _served_global(self, pos):
        """The ledger at cursor ``pos`` of THIS assignment: ``base`` plus
        every rank's first ``pos`` batches of its shard (lockstep makes
        all ranks' cursors equal at any sync boundary)."""
        out = set(self.base)
        take = pos * self.batch_size
        for part in self._parts.values():
            out.update(part[:take])
        return out

    def ledger(self):
        """The global sample-accounting ledger at this worker's cursor:
        the set of records of the current data epoch whose updates are
        part of the surviving trajectory."""
        with self._lock:
            return self._served_global(self._pos)

    @property
    def num_records(self):
        return self._n

    # -- DataIter protocol -------------------------------------------------
    @property
    def provide_data(self):
        return self._data_descs

    @property
    def provide_label(self):
        return self._label_descs

    def _read(self, ids):
        from .ndarray import array as _array

        if self._reader is not None:
            data, label = self._reader(ids)
            return ([_array(np.asarray(a)) for a in data],
                    [_array(np.asarray(a)) for a in label])
        data_src, label_src = self._arrays
        idx = np.asarray(ids, np.int64)
        return ([_array(v[idx]) for _k, v in data_src],
                [_array(v[idx]) for _k, v in label_src])

    def next(self):
        with self._lock:
            if self._pos >= self._nbatches:
                raise StopIteration
            own = self._owned
            start = self._pos * self.batch_size
            ids = list(own[start:start + self.batch_size])
            pad = self.batch_size - len(ids)
            if pad:
                src = own
                if not src:
                    # an empty shard (fewer remaining records than
                    # ranks after a late-epoch reshard): serve full-pad
                    # batches from the lowest remaining record so this
                    # rank stays in the sync-round lockstep its peers
                    # depend on; pads never commit to the ledger.
                    # _nbatches > 0 guarantees some part is non-empty.
                    src = [min(min(p)
                              for p in self._parts.values() if p)]
                k = 0
                while len(ids) < self.batch_size:
                    ids.append(src[k % len(src)])
                    k += 1
            self._pos += 1
        data, label = self._read(ids)
        return DataBatch(data=data, label=label, pad=pad,
                         index=np.asarray(ids, np.int64))

    def reset(self):
        """Data-epoch boundary: close the current assignment segment and
        start a fresh pass over the FULL record set under the current
        membership."""
        with self._lock:
            self._close_segment("epoch-end")
            self.data_epoch += 1
            self.base = set()
            self._pos = 0
            # sync lockstep keeps rank cursors within one batch, so the
            # rollback target (the newest snapshot generation) is always
            # in the current or previous data epoch: older commit sets
            # can never be retracted and would otherwise grow without
            # bound over a long job
            for e in [e for e in self._committed
                      if e < self.data_epoch - 1]:
                del self._committed[e]
            if not self.audit:
                # same rule as _committed: epochs older than the
                # rollback horizon can never be retracted — dropping
                # them bounds the ledger at O(records) instead of
                # O(records x epochs) over a long job
                for e in [e for e in self.applied
                          if e < self.data_epoch - 1]:
                    del self.applied[e]
            self._recompute()

    def _close_segment(self, why):
        self.history.append({
            "why": why, "data_epoch": self.data_epoch,
            "membership_epoch": self.membership_epoch,
            "ranks": list(self.ranks), "pos": self._pos,
            "covered": len(self._served_global(self._pos))})

    # -- ledger commits ----------------------------------------------------
    def commit(self, index, pad=0):
        """Record a trained batch's non-pad ids as applied in the
        surviving trajectory.  ``fit(elastic=True)`` calls this after
        ``update()`` landed; a batch whose update was rejected with
        ``StaleEpoch`` is never committed, and commits rolled back by a
        reshard are retracted in :meth:`reshard`."""
        ids = np.asarray(index).ravel()
        if pad:
            ids = ids[:len(ids) - pad]
        with self._lock:
            c = self._committed.setdefault(self.data_epoch, set())
            a = self.applied.setdefault(self.data_epoch, {})
            for i in ids:
                i = int(i)
                if i in c:
                    continue  # pad wrap / replay: counted once
                c.add(i)
                a[i] = a.get(i, 0) + 1

    def _retract(self, epoch, rolled):
        """Undo rolled-back commits in the epoch's ledger (lock held):
        decrement each record's applied count (dropping zeroed entries)
        and remove it from the committed set, so the records re-enter
        the remaining pool at the next :meth:`_recompute`."""
        a = self.applied.setdefault(epoch, {})
        for i in rolled:
            n = a.get(i, 0) - 1
            if n > 0:
                a[i] = n
            else:
                a.pop(i, None)
        self._committed.get(epoch, set()).difference_update(rolled)

    # -- elastic reshard ---------------------------------------------------
    def reshard(self, rank, ranks, membership_epoch, state=None):
        """Recompute shard ownership for a new membership.  With
        ``state`` (the adopted snapshot's iterator state) the GLOBAL
        ledger rolls back/forward to that snapshot's boundary first:
        records the snapshot had not yet accounted return to the
        remaining pool (their updates were rolled back with the
        parameters), and this rank's local commits beyond the boundary
        are retracted — no record is skipped, none is trained twice in
        the surviving trajectory."""
        from .elastic import shard_records

        with self._lock:
            self._close_segment("reshard")
            if state is not None:
                s_ranks = sorted(state["ranks"])
                s_base = set(int(i) for i in state["base"])
                s_pos = int(state["pos"])
                s_bs = int(state.get("batch_size", self.batch_size))
                s_depoch = int(state["data_epoch"])
                remaining = [i for i in range(self._n) if i not in s_base]
                parts = shard_records(remaining, s_ranks,
                                      int(state["membership_epoch"])) \
                    if remaining else {}
                new_base = set(s_base)
                for part in parts.values():
                    new_base.update(part[:s_pos * s_bs])
                # retract local commits the rollback undid
                for epoch in sorted(self._committed):
                    if epoch < s_depoch:
                        continue
                    c = self._committed[epoch]
                    self._retract(
                        epoch, c - new_base if epoch == s_depoch else set(c))
                self.data_epoch = s_depoch
                self.base = new_base
            else:
                # no snapshot generation exists (a fresh job's initial
                # sync, or a membership change before the leader's first
                # write landed): there is no common rollback target, so
                # the SEGMENT START is the rollback target — the base is
                # kept and the current assignment's local commits are
                # retracted, giving every member (newcomers included)
                # the identical remaining pool.  Per-rank committed
                # views must NOT leak into the base: a pull racing the
                # epoch bump leaves ranks with different committed
                # boundaries, and divergent bases mean divergent shard
                # ownership.  An update that landed without a generation
                # (at most the segment's first round under the pinned
                # every-batch elastic cadence) is retrained rather than
                # divergently skipped.
                c = self._committed.get(self.data_epoch, set())
                self._retract(self.data_epoch, c - self.base)
            self.rank = rank
            self.ranks = sorted(ranks)
            self.membership_epoch = int(membership_epoch)
            self._pos = 0
            self._recompute()

    # -- iterator-state protocol (PR 5) ------------------------------------
    def state_dict(self):
        with self._lock:
            return {"type": "ElasticShardIter", "num_records": self._n,
                    "batch_size": self.batch_size,
                    "data_epoch": self.data_epoch,
                    "membership_epoch": self.membership_epoch,
                    "ranks": list(self.ranks), "rank": self.rank,
                    "pos": self._pos, "base": sorted(self.base)}

    def load_state_dict(self, state):
        if state.get("type") != "ElasticShardIter":
            raise MXNetError("iterator state of type %r cannot restore "
                             "onto ElasticShardIter" % (state.get("type"),))
        if int(state.get("num_records", self._n)) != self._n or \
                int(state.get("batch_size", self.batch_size)) \
                != self.batch_size:
            raise MXNetError(
                "ElasticShardIter state (num_records=%s, batch_size=%s) "
                "does not match this iterator (num_records=%d, "
                "batch_size=%d)" % (state.get("num_records"),
                                    state.get("batch_size"), self._n,
                                    self.batch_size))
        with self._lock:
            self.data_epoch = int(state["data_epoch"])
            self.membership_epoch = int(state["membership_epoch"])
            self.ranks = sorted(state["ranks"])
            # restore the captured rank too (found by the state-protocol
            # lint pass: the key was emitted but silently dropped) — a
            # same-rank resume is a no-op, but restoring a capture onto
            # a differently-constructed iterator must land on the SAME
            # shard assignment the capture described, or _recompute()
            # below walks another rank's records
            self.rank = int(state.get("rank", self.rank))
            self.base = set(int(i) for i in state["base"])
            self._pos = int(state["pos"])
            self._recompute()


def _mp_decode_worker(ctor_kwargs, shm_names, data_shape, label_shape,
                      cmd_q, free_q, out_q):
    """Decode worker PROCESS: owns one dataset shard
    (part_index/num_parts inside ctor_kwargs) and runs the full native
    decode pipeline on it, one epoch per 'epoch' command.  Runs under
    the 'spawn' start method so the child gets a fresh interpreter (a
    forked child would inherit the parent's initialized XLA runtime,
    whose threads do not survive fork) — and, decisively for the 1-core
    clamp, its OWN CPU affinity mask: the decode library sizes its pool
    from sched_getaffinity, so N processes on an M-core host scale
    where in-process threads clamp to the parent's mask.

    Batches hand over through SHARED-MEMORY slots, not pickled queues:
    a 224-ImageNet f32 batch is ~77 MB, and pickling it through
    mp.Queue's feeder thread measured 5x slower than the decode itself.
    The worker memcpys into a free slot and sends only the slot index;
    the parent memcpys out and returns the slot via free_q."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from multiprocessing import shared_memory

    import numpy as np

    from mxnet_tpu.image import ImageIter

    shms = [shared_memory.SharedMemory(name=n) for n in shm_names]
    data_n = int(np.prod(data_shape)) * 4
    views = [(np.ndarray(data_shape, np.float32, buffer=s.buf),
              np.ndarray(label_shape, np.float32, buffer=s.buf,
                         offset=data_n)) for s in shms]
    from mxnet_tpu.native import get_imgdecode_lib

    if get_imgdecode_lib() is None:
        # no native decode in this environment: swap native_norm for the
        # equivalent python batch converter so the fallback still
        # normalizes (silently un-normalized data would train garbage)
        mean, std, scale = ctor_kwargs.pop("native_norm")
        ctor_kwargs["post_batch"] = _batch_converter(
            np.asarray(mean, np.float32), np.asarray(std, np.float32),
            scale, None)
    it = ImageIter(**ctor_kwargs)
    while True:
        cmd = cmd_q.get()
        if cmd == "stop":
            break
        it.reset()
        while True:
            slot = free_q.get()   # claim the slot BEFORE decoding
            if slot is None:      # abort sentinel (parent close())
                break
            dv, lv = views[slot]
            it.batch_out = (dv, lv)   # native path decodes into the slot
            try:
                batch = it.next()
            except StopIteration:
                free_q.put(slot)
                break
            if it.batch_out is not None:
                # non-native fallback didn't consume the buffers — copy
                it.batch_out = None
                np.copyto(dv, batch.data[0].asnumpy())
                lab = batch.label[0].asnumpy().astype(np.float32)
                np.copyto(lv, lab.reshape(label_shape))
            out_q.put(("b", slot, batch.pad))
        out_q.put(("end", -1, 0))
    for s in shms:
        s.close()


_child_env_lock = threading.Lock()


@contextlib.contextmanager
def _host_only_child_env():
    """Environment for starting a decode worker: ``JAX_PLATFORMS=cpu``.

    A chip belongs to one process, and the parent that feeds it holds
    it.  The worker imports this package (and so JAX) and hands batches
    back as NDArrays, which would initialise whatever backend JAX
    defaults to — a second claim on the parent's chip that fails or
    hangs.  A ``spawn`` child inherits ``os.environ`` as it stands at
    ``start()``, so the variable is set across that call and restored;
    the parent's own JAX read it at import and is unaffected."""
    with _child_env_lock:
        prev = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            yield
        finally:
            if prev is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = prev


class MultiProcessIter(DataIter):
    """Host-sharded multi-process decode (round-4/5 IO-scaling design).

    N worker PROCESSES each own a 1/N dataset shard via the existing
    ``part_index``/``num_parts`` sharding and run the one-C-call decode
    pipeline; finished batches return over bounded per-worker queues and
    the parent round-robins them.  This is the multi-worker analog of
    the reference's decode-thread pool (``iter_image_recordio.cc:458``)
    for hosts where in-process threads cannot scale: the decode library
    clamps its pool to the process affinity mask, and separate processes
    each carry their own mask (plus their own GIL).

    Epoch semantics: each worker's shard pads/rolls independently, so
    batch ORDER differs from the single-process iterator but per-epoch
    sample coverage is identical (the sharding is the same
    ``part_index``/``num_parts`` split dist training uses).  Batches
    cross the process boundary through per-worker shared-memory slot
    rings — one memcpy in, one memcpy out, slot indices on the queues —
    because pickling 77 MB f32 batches through mp.Queue measured 5x
    slower than the decode work itself.
    """

    def __init__(self, ctor_kwargs, num_procs, batch_size, data_shape,
                 label_width=1, data_name="data",
                 label_name="softmax_label", slots_per_worker=2):
        import multiprocessing as mp
        from multiprocessing import shared_memory

        super().__init__(batch_size)
        self._data_shape = tuple(data_shape)
        self._label_width = label_width
        self._data_name, self._label_name = data_name, label_name
        full_data = (batch_size,) + self._data_shape
        label_shape = (batch_size, label_width)
        data_n = int(np.prod(full_data)) * 4
        label_n = int(np.prod(label_shape)) * 4
        ctx = mp.get_context("spawn")
        self._workers, self._cmd_qs, self._out_qs = [], [], []
        self._free_qs, self._shms, self._views = [], [], []
        for w in range(num_procs):
            kw = dict(ctor_kwargs, part_index=w, num_parts=num_procs)
            cmd_q = ctx.Queue()
            free_q = ctx.Queue()
            out_q = ctx.Queue()
            shms = [shared_memory.SharedMemory(
                create=True, size=data_n + label_n)
                for _ in range(slots_per_worker)]
            self._views.append([
                (np.ndarray(full_data, np.float32, buffer=s.buf),
                 np.ndarray(label_shape, np.float32, buffer=s.buf,
                            offset=data_n)) for s in shms])
            for i in range(slots_per_worker):
                free_q.put(i)
            p = ctx.Process(target=_mp_decode_worker,
                            args=(kw, [s.name for s in shms], full_data,
                                  label_shape, cmd_q, free_q, out_q),
                            daemon=True)
            with _host_only_child_env():
                p.start()
            self._workers.append(p)
            self._cmd_qs.append(cmd_q)
            self._free_qs.append(free_q)
            self._out_qs.append(out_q)
            self._shms.append(shms)
        self._live = []
        self._rr = 0
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(self._data_name,
                         (self.batch_size,) + self._data_shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self._label_width == 1 \
            else (self.batch_size, self._label_width)
        return [DataDesc(self._label_name, shape)]

    def reset(self):
        # drain any tail of the previous epoch (returning its slots) so
        # commands stay in phase.  NOTE: a mid-epoch reset waits for the
        # workers to decode the REST of their shards (batches
        # discarded); epoch-boundary resets, the training-loop norm,
        # cost nothing
        for w, q in enumerate(self._out_qs):
            if w in getattr(self, "_live", []):
                while True:
                    kind, slot, _pad = q.get()
                    if kind == "end":
                        break
                    self._free_qs[w].put(slot)
        for q in self._cmd_qs:
            q.put("epoch")
        self._live = list(range(len(self._workers)))
        self._rr = 0

    def next(self):
        while self._live:
            w = self._live[self._rr % len(self._live)]
            kind, slot, pad = self._out_qs[w].get()
            if kind == "end":
                self._live.remove(w)
                continue
            dv, lv = self._views[w][slot]
            # ONE memcpy out of the slot into a fresh per-batch buffer.
            # Zero-copy handoff was measured and REVERTED: the executor
            # device_puts host batches by aliasing (jax CPU backend
            # zero-copy), so a recycled slot corrupts the async
            # in-flight step — a fresh buffer has the same lifetime
            # semantics as the in-process iterator (GC-owned by the
            # returned NDArray).  The copy overlaps worker decode on
            # any multi-core host.
            data = np.array(dv)
            label = np.array(lv)
            self._free_qs[w].put(slot)  # copied out — recycle now
            if self._label_width == 1:
                label = label.reshape(self.batch_size)
            self._rr += 1
            from . import ndarray as _nd

            return DataBatch(data=[_nd.from_host(data)],
                             label=[_nd.from_host(label)],
                             pad=pad,
                             provide_data=self.provide_data,
                             provide_label=self.provide_label)
        raise StopIteration

    def close(self):
        # wake any worker blocked on free_q (abort sentinel) so the
        # 'stop' command is reachable — otherwise a mid-epoch close
        # hangs the join and falls back to SIGTERM
        for q in self._free_qs:
            try:
                q.put(None)
            except (OSError, ValueError):
                pass
        for q in self._cmd_qs:
            try:
                q.put("stop")
            except (OSError, ValueError):
                pass
        for p in self._workers:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        self._workers = []
        for shms in self._shms:
            for s in shms:
                try:
                    s.close()
                    s.unlink()
                except (FileNotFoundError, OSError):
                    pass
        self._shms = []

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: broad-except — interpreter-shutdown GC
            pass


def corrupt_skip_count():
    """Process-wide count of corrupt records skipped by the data pipeline
    under ``MXNET_IO_SKIP_CORRUPT=1`` (see docs/resilience.md).  Per-reader
    counts live on ``MXRecordIO.num_skipped``."""
    from . import recordio

    return recordio.skipped_record_count()


def reset_corrupt_skip_count():
    from . import recordio

    recordio.reset_skipped_record_count()


def _batch_converter(mean, std, scale, ctx):
    """Batch-level cast+normalize+transpose for the ImageRecordIter fast
    path: uint8 HWC staging -> f32 NCHW, either host-vectorized or — with
    ``ctx`` on an accelerator — ON DEVICE, so the host ships a quarter of
    the bytes and the chip does the layout work (the TPU answer to the
    reference's GPU-side ``ImageRecordUInt8Iter`` pattern)."""
    from . import ndarray

    use_mean = mean is not None and mean.any()
    use_std = std is not None and (std != 1.0).any()

    if ctx is not None:
        import jax
        import jax.numpy as jnp

        dev = ctx.jax_device()
        mean_j = jnp.asarray(mean) if use_mean else None
        std_j = jnp.asarray(std) if use_std else None

        @jax.jit
        def convert(x):
            y = x.astype(jnp.float32)
            if use_mean:
                y = y - mean_j
            if use_std:
                y = y / std_j
            if scale != 1.0:
                y = y * jnp.float32(scale)
            return y.transpose(0, 3, 1, 2)

        def post(hwc, label):
            out = convert(jax.device_put(hwc, dev))
            return (ndarray.NDArray._from_jax(out, ctx),
                    ndarray.array(label, ctx=ctx))

        return post

    mean_c = mean.reshape(1, -1, 1, 1) if use_mean else None
    std_c = std.reshape(1, -1, 1, 1) if use_std else None
    from .context import cpu as _cpu

    def post(hwc, label):
        # ONE strided-read/contiguous-write pass does transpose+cast; the
        # resulting contiguous buffer makes the jax conversion a memcpy.
        # Host batches stay on CPU (reference iterators fill pinned host
        # memory; the executor's _load_io does the device copy) — an
        # accelerator default context would drag every batch through the
        # host->device link twice
        x = hwc.transpose(0, 3, 1, 2).astype(np.float32)
        if use_mean:
            x -= mean_c
        if use_std:
            x /= std_c
        if scale != 1.0:
            x *= np.float32(scale)
        return (ndarray.array(x, ctx=_cpu()),
                ndarray.array(label, ctx=_cpu()))

    return post


def ImageRecordIter(path_imgrec, data_shape, batch_size, shuffle=False,
                    part_index=0, num_parts=1, rand_crop=False,
                    rand_mirror=False, mean_r=0.0, mean_g=0.0, mean_b=0.0,
                    std_r=1.0, std_g=1.0, std_b=1.0, scale=1.0, resize=0,
                    path_imgidx=None, prefetch=True, data_name="data",
                    label_name="softmax_label", label_width=1,
                    preprocess_threads=4, prefetch_buffer=1,
                    round_batch=True, ctx=None, decode_procs=None,
                    **kwargs):
    """C-iter-style facade over ``image.ImageIter`` (+ prefetch thread).

    Reference: ``ImageRecordIter`` registered at
    ``src/io/iter_image_recordio.cc:458`` with the decode→augment→batch→
    prefetch decorator chain of §3.5; kwargs mirror its dmlc params
    (``mean_r``..., ``rand_crop``, ``part_index``/``num_parts``...).

    TPU-first pipeline shape: N decode threads (``preprocess_threads``,
    default 4 — cv2 releases the GIL) run geometric augmenters on uint8,
    the batch is cast/normalized/transposed ONCE (on ``ctx`` when it is
    an accelerator — quarter the host->device bytes, layout work on the
    MXU's neighbors), and ``PrefetchingIter`` double-buffers the whole
    thing against the consumer (``iter_prefetcher.h:49`` analog).
    Per-image color augmentations (brightness/contrast/saturation/pca)
    need float images, so requesting them falls back to the reference's
    per-image CastAug chain.

    ``decode_procs`` (default ``$MXNET_DECODE_PROCS`` or 0): when > 1,
    decode runs in that many worker PROCESSES instead of in-process
    threads (``MultiProcessIter``) — the scaling path for hosts where
    the decode pool clamps to a narrow affinity mask.  Requires the
    fast path (no color augs) and is mutually exclusive with
    ``num_parts`` sharding (the processes ARE the parts).
    """
    from .image import (CenterCropAug, CreateAugmenter, HorizontalFlipAug,
                        ImageIter, RandomCropAug, ResizeAug)

    known = ("brightness", "contrast", "saturation", "pca_noise",
             "inter_method")
    unknown = set(kwargs) - set(known)
    if unknown:
        raise TypeError("ImageRecordIter: unsupported parameters %s"
                        % sorted(unknown))
    mean = np.array([mean_r, mean_g, mean_b], np.float32)
    std = np.array([std_r, std_g, std_b], np.float32)
    color_ops = any(kwargs.get(k) for k in
                    ("brightness", "contrast", "saturation", "pca_noise"))
    post_batch = None
    if not color_ops:
        # fast path: geometric augs stay uint8; one batch-level convert
        inter = kwargs.get("inter_method", 1)
        aug_list = []
        if resize > 0:
            aug_list.append(ResizeAug(resize, inter))
        crop_size = (data_shape[2], data_shape[1])
        aug_list.append(RandomCropAug(crop_size, inter) if rand_crop
                        else CenterCropAug(crop_size, inter))
        if rand_mirror:
            aug_list.append(HorizontalFlipAug(0.5))
        post_batch = _batch_converter(mean, std, scale, ctx)
    else:
        aug_list = CreateAugmenter(
            data_shape, resize=resize, rand_crop=rand_crop,
            rand_mirror=rand_mirror,
            mean=mean if mean.any() else None,
            std=std if (std != 1.0).any() else None,
            **kwargs)
        if scale != 1.0:
            aug_list.append(lambda img: img * scale)
    # host-destination batches fuse cast+normalize+transpose into the
    # native decode call (f32 NCHW straight out of C); device batches
    # keep uint8 staging so the link carries a quarter of the bytes
    native_norm = (tuple(mean), tuple(std), float(scale)) \
        if (post_batch is not None and ctx is None) else None
    # reference round_batch=1 (iter_batchloader.h:36): the final partial
    # batch wraps around to the start of the data and the next epoch
    # skips the wrapped samples — every sample still appears once per
    # cycle and every batch is full (pad == 0), the semantics dist
    # workers rely on for equal step counts.  Defaults ON to match the
    # reference (iter_batchloader.h:30 set_default(true)); round_batch=0
    # keeps the pad-and-set-batch.pad behavior.
    if decode_procs is None:
        decode_procs = int(os.environ.get("MXNET_DECODE_PROCS", "0"))
    if decode_procs > 1:
        if color_ops:
            raise ValueError("decode_procs needs the fast (geometric-"
                             "aug) path; color augs run in-process")
        if num_parts != 1:
            raise ValueError("decode_procs and num_parts are mutually "
                             "exclusive (the processes are the parts)")
        if ctx is not None:
            raise ValueError("decode_procs produces host f32 batches; "
                             "the uint8-on-device conversion path "
                             "(ctx=...) is single-process only")
        ctor = dict(batch_size=batch_size, data_shape=data_shape,
                    label_width=label_width, path_imgrec=path_imgrec,
                    path_imgidx=path_imgidx, shuffle=shuffle,
                    aug_list=aug_list, data_name=data_name,
                    label_name=label_name,
                    preprocess_threads=preprocess_threads,
                    native_norm=(tuple(mean), tuple(std), float(scale)),
                    last_batch_handle="roll_over" if round_batch
                    else "pad")
        return MultiProcessIter(ctor, decode_procs, batch_size,
                                data_shape, label_width=label_width,
                                data_name=data_name,
                                label_name=label_name)
    it = ImageIter(batch_size, data_shape, label_width=label_width,
                   path_imgrec=path_imgrec, path_imgidx=path_imgidx,
                   shuffle=shuffle, part_index=part_index,
                   num_parts=num_parts, aug_list=aug_list,
                   data_name=data_name, label_name=label_name,
                   preprocess_threads=preprocess_threads,
                   post_batch=post_batch, native_norm=native_norm,
                   last_batch_handle="roll_over" if round_batch else "pad")
    if not prefetch or not prefetch_buffer:
        return it
    return PrefetchingIter(it)
