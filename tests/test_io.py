"""Data iterators (reference ``tests/python/unittest/test_io.py``)."""

import gzip
import os
import struct

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import io, nd


def test_ndarrayiter_basic():
    x = np.arange(40, dtype=np.float32).reshape(10, 4)
    y = np.arange(10, dtype=np.float32)
    it = io.NDArrayIter(x, y, batch_size=4, last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data[0].shape == (4, 4)
    assert batches[2].pad == 2
    it.reset()
    assert len(list(it)) == 3


def test_ndarrayiter_discard_and_shuffle():
    x = np.arange(30, dtype=np.float32).reshape(10, 3)
    it = io.NDArrayIter(x, None, batch_size=4, shuffle=True,
                        last_batch_handle="discard")
    batches = list(it)
    assert len(batches) == 2
    desc = it.provide_data[0]
    assert desc.name == "data" and desc.shape == (4, 3)


def _write_idx_images(path, arr):
    with open(path, "wb") as f:
        f.write(struct.pack(">I", 0x00000803))
        f.write(struct.pack(">III", *arr.shape))
        f.write(arr.astype(np.uint8).tobytes())


def _write_idx_labels(path, arr):
    with open(path, "wb") as f:
        f.write(struct.pack(">I", 0x00000801))
        f.write(struct.pack(">I", arr.shape[0]))
        f.write(arr.astype(np.uint8).tobytes())


def test_mnist_iter(tmp_path):
    imgs = np.random.randint(0, 255, (50, 28, 28)).astype(np.uint8)
    labels = np.random.randint(0, 10, 50).astype(np.uint8)
    ip = str(tmp_path / "imgs-idx3-ubyte")
    lp = str(tmp_path / "labels-idx1-ubyte")
    _write_idx_images(ip, imgs)
    _write_idx_labels(lp, labels)
    it = io.MNISTIter(image=ip, label=lp, batch_size=10, shuffle=False)
    b = it.next()
    assert b.data[0].shape == (10, 1, 28, 28)
    assert b.label[0].shape == (10,)
    # flat + sharding
    it2 = io.MNISTIter(image=ip, label=lp, batch_size=5, flat=True,
                       shuffle=False, num_parts=2, part_index=1)
    b2 = it2.next()
    assert b2.data[0].shape == (5, 784)


def test_csv_iter(tmp_path):
    data = np.random.rand(12, 3).astype(np.float32)
    labels = np.random.randint(0, 2, 12).astype(np.float32)
    dp = str(tmp_path / "d.csv")
    lp = str(tmp_path / "l.csv")
    np.savetxt(dp, data, delimiter=",")
    np.savetxt(lp, labels, delimiter=",")
    it = io.CSVIter(data_csv=dp, data_shape=(3,), label_csv=lp,
                    label_shape=(1,), batch_size=4)
    b = it.next()
    assert b.data[0].shape == (4, 3)


def test_resize_iter():
    x = np.random.rand(8, 2).astype(np.float32)
    base = io.NDArrayIter(x, None, batch_size=4)
    it = io.ResizeIter(base, 5)
    assert len(list(it)) == 5


def test_prefetching_iter():
    x = np.random.rand(16, 2).astype(np.float32)
    y = np.arange(16, dtype=np.float32)
    base = io.NDArrayIter(x, y, batch_size=4)
    it = io.PrefetchingIter(base)
    batches = list(it)
    assert len(batches) == 4
    it.reset()
    assert len(list(it)) == 4


def test_prefetching_iter_propagates_producer_error():
    """A crash in the prefetch thread must surface on next(), not hang."""
    import pytest

    class Boom(io.DataIter):
        def __init__(self):
            super().__init__(batch_size=2)
            self.n = 0

        @property
        def provide_data(self):
            return [io.DataDesc("data", (2, 2))]

        @property
        def provide_label(self):
            return []

        def reset(self):
            self.n = 0

        def next(self):
            self.n += 1
            if self.n > 1:
                raise RuntimeError("producer exploded")
            return io.DataBatch(data=[nd.zeros((2, 2))], label=[])

    it = io.PrefetchingIter(Boom())
    next(iter(it))  # first batch fine
    with pytest.raises(RuntimeError, match="producer exploded"):
        it.next()


def test_image_iter_batches_are_ndarrays(tmp_path):
    """DataBatch contract: .data/.label hold NDArrays (not numpy)."""
    import numpy as np

    from mxnet_tpu import recordio

    rec_path = str(tmp_path / "t.rec")
    rec = recordio.MXRecordIO(rec_path, "w")
    rs = np.random.RandomState(0)
    for i in range(8):
        img = (rs.rand(12, 12, 3) * 255).astype(np.uint8)
        rec.write(recordio.pack_img(recordio.IRHeader(0, float(i), i, 0),
                                    img))
    rec.close()
    it = io.ImageRecordIter(path_imgrec=rec_path, data_shape=(3, 12, 12),
                            batch_size=4, prefetch_buffer=2,
                            round_batch=True)
    batch = next(iter(it))
    assert isinstance(batch.data[0], nd.NDArray)
    assert isinstance(batch.label[0], nd.NDArray)
    assert batch.data[0].shape == (4, 3, 12, 12)


def test_prefetching_iter_close_joins_threads():
    """close() must stop AND join the daemon prefetch threads — __del__
    racing GC used to be the only teardown, leaking N threads per
    leaked iterator."""
    x = np.random.rand(16, 2).astype(np.float32)
    base = io.NDArrayIter(x, None, batch_size=4)
    it = io.PrefetchingIter(base)
    assert any(t.is_alive() for t in it.prefetch_threads)
    next(iter(it))
    it.close()
    assert not any(t.is_alive() for t in it.prefetch_threads)
    it.close()  # idempotent


def test_prefetching_iter_context_manager():
    x = np.random.rand(16, 2).astype(np.float32)
    with io.PrefetchingIter(io.NDArrayIter(x, None, batch_size=4)) as it:
        assert len(list(it)) == 4
    assert not any(t.is_alive() for t in it.prefetch_threads)


def test_prefetching_iter_reset_clears_errors():
    """A producer error before reset() must not resurface after it."""
    class Flaky(io.DataIter):
        def __init__(self):
            super().__init__(batch_size=2)
            self.fail_once = True

        @property
        def provide_data(self):
            return [io.DataDesc("data", (2, 2))]

        @property
        def provide_label(self):
            return []

        def reset(self):
            pass

        def next(self):
            if self.fail_once:
                self.fail_once = False
                raise RuntimeError("transient")
            return io.DataBatch(data=[nd.zeros((2, 2))], label=[])

    it = io.PrefetchingIter(Flaky())
    it.reset()
    batch = it.next()  # healthy after reset — stale error must not raise
    assert batch.data[0].shape == (2, 2)


def _write_rec(tmp_path, n=12, size=16):
    import numpy as np

    from mxnet_tpu import recordio

    rec_path = str(tmp_path / "fp.rec")
    rec = recordio.MXRecordIO(rec_path, "w")
    rs = np.random.RandomState(3)
    imgs = []
    for i in range(n):
        img = (rs.rand(size, size, 3) * 255).astype(np.uint8)
        imgs.append(img)
        rec.write(recordio.pack_img(recordio.IRHeader(0, float(i), i, 0),
                                    img, img_fmt=".png"))
    rec.close()
    return rec_path, imgs


def test_image_record_iter_fast_path_values(tmp_path):
    """The uint8-staging fast path (no color augs) must produce the same
    normalized NCHW values as doing the math by hand."""
    import numpy as np

    rec_path, imgs = _write_rec(tmp_path, n=6, size=16)
    mean = (10.0, 20.0, 30.0)
    std = (2.0, 3.0, 4.0)
    it = io.ImageRecordIter(path_imgrec=rec_path, data_shape=(3, 16, 16),
                            batch_size=6, prefetch=False,
                            preprocess_threads=1,
                            mean_r=mean[0], mean_g=mean[1], mean_b=mean[2],
                            std_r=std[0], std_g=std[1], std_b=std[2],
                            scale=0.5)
    batch = it.next()
    got = batch.data[0].asnumpy()
    assert batch.data[0].context.device_type in ("cpu",)
    for i, img in enumerate(imgs[:6]):
        # pack_img takes BGR (cv2 convention); imdecode returns RGB
        want = img[:, :, ::-1].astype(np.float32)
        want = (want - np.array(mean, np.float32)) / np.array(std, np.float32)
        want = (want * 0.5).transpose(2, 0, 1)
        np.testing.assert_allclose(got[i], want, rtol=1e-5, atol=1e-5)


def test_image_record_iter_device_convert_matches_host(tmp_path):
    """ctx= moves cast/normalize/transpose on device; values must match
    the host path."""
    import numpy as np

    import mxnet_tpu as mx

    rec_path, _ = _write_rec(tmp_path, n=8, size=16)

    def run(**kw):
        it = io.ImageRecordIter(path_imgrec=rec_path,
                                data_shape=(3, 16, 16), batch_size=8,
                                prefetch=False, preprocess_threads=1,
                                mean_r=5.0, std_r=2.0, scale=0.25, **kw)
        return it.next().data[0]

    host = run()
    dev = run(ctx=mx.cpu(0))
    assert dev.shape == (8, 3, 16, 16)
    np.testing.assert_allclose(dev.asnumpy(), host.asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_image_record_iter_color_augs_still_work(tmp_path):
    """brightness etc. fall back to the per-image float chain."""
    rec_path, _ = _write_rec(tmp_path, n=4, size=16)
    it = io.ImageRecordIter(path_imgrec=rec_path, data_shape=(3, 16, 16),
                            batch_size=4, prefetch=False,
                            preprocess_threads=1, brightness=0.1)
    batch = it.next()
    assert batch.data[0].shape == (4, 3, 16, 16)


def test_multiprocess_decode_shard_coverage(tmp_path):
    """decode_procs=N (MultiProcessIter): N worker PROCESSES each own a
    part_index/num_parts shard; per-epoch sample coverage must equal the
    single-process iterator exactly (order may differ), two epochs in a
    row (exercises the end-drain + re-command protocol), and a second
    epoch must not duplicate or drop samples."""
    import numpy as np

    from mxnet_tpu import recordio

    rec_path = str(tmp_path / "mp.rec")
    rec = recordio.MXRecordIO(rec_path, "w")
    rs = np.random.RandomState(3)
    n = 24
    for i in range(n):
        img = (rs.rand(16, 16, 3) * 255).astype(np.uint8)
        rec.write(recordio.pack_img(recordio.IRHeader(0, float(i), i, 0),
                                    img))
    rec.close()

    def labels_of(it):
        out = []
        for b in it:
            lab = b.label[0].asnumpy()
            out.extend(lab[:len(lab) - b.pad].astype(int).tolist())
        return out

    single = io.ImageRecordIter(path_imgrec=rec_path,
                                data_shape=(3, 16, 16), batch_size=4,
                                round_batch=True)
    want = sorted(labels_of(single))
    assert want == list(range(n))

    it = io.ImageRecordIter(path_imgrec=rec_path, data_shape=(3, 16, 16),
                            batch_size=4, round_batch=True,
                            decode_procs=2)
    try:
        assert isinstance(it, io.MultiProcessIter)
        got1 = labels_of(it)
        assert sorted(got1) == want, sorted(got1)
        it.reset()
        got2 = labels_of(it)
        assert sorted(got2) == want, sorted(got2)
        batch = next(iter(io.ImageRecordIter(
            path_imgrec=rec_path, data_shape=(3, 16, 16), batch_size=4,
            round_batch=True, decode_procs=2)))
        assert batch.data[0].shape == (4, 3, 16, 16)
    finally:
        it.close()


def test_multiprocess_decode_rejects_bad_combos(tmp_path):
    import pytest as _pytest

    with _pytest.raises(ValueError):
        io.ImageRecordIter(path_imgrec="x.rec", data_shape=(3, 8, 8),
                           batch_size=2, decode_procs=2, num_parts=2)
    with _pytest.raises(ValueError):
        io.ImageRecordIter(path_imgrec="x.rec", data_shape=(3, 8, 8),
                           batch_size=2, decode_procs=2, brightness=0.2)


def _report_jax_platforms(q):
    import os as _os

    import jax

    q.put((_os.environ.get("JAX_PLATFORMS"), jax.default_backend()))


def test_decode_workers_start_host_only(monkeypatch):
    """A chip belongs to one process: decode workers are spawned with
    ``JAX_PLATFORMS=cpu`` in their environment whatever the parent's
    says, and the parent's is restored."""
    import multiprocessing as mp

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_report_jax_platforms, args=(q,), daemon=True)
    with io._host_only_child_env():
        p.start()
    assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"
    assert q.get(timeout=120) == ("cpu", "cpu")
    p.join(60)
    assert not p.is_alive()
    monkeypatch.delenv("JAX_PLATFORMS")
    with io._host_only_child_env():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in os.environ
