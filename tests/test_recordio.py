"""RecordIO format + image pipeline tests.

Models the reference's ``tests/python/unittest/test_recordio.py`` and
``test_io.py`` image-record coverage, plus the im2rec tool end-to-end.
"""

import os
import struct
import subprocess
import sys

import numpy as np
import pytest

from mxnet_tpu import image, recordio
from mxnet_tpu import io as mxio

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "a.rec")
    w = recordio.MXRecordIO(path, "w")
    payloads = [b"hello", b"", b"x" * 1237, np.arange(100).tobytes()]
    for p in payloads:
        w.write(p)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    for p in payloads:
        assert r.read() == p
    assert r.read() is None
    r.close()


def test_recordio_magic_escape(tmp_path):
    """Payloads containing the magic must round-trip (multipart chain)."""
    magic = struct.pack("<I", 0xced7230a)
    payloads = [magic, b"ab" + magic + b"cd", magic * 3,
                b"x" * 11 + magic + b"y" * 7 + magic]
    path = str(tmp_path / "m.rec")
    w = recordio.MXRecordIO(path, "w")
    for p in payloads:
        w.write(p)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    for p in payloads:
        assert r.read() == p
    r.close()


def test_indexed_recordio(tmp_path):
    path = str(tmp_path / "b.rec")
    idx = str(tmp_path / "b.idx")
    w = recordio.MXIndexedRecordIO(idx, path, "w")
    for i in range(20):
        w.write_idx(i, b"rec%03d" % i)
    w.close()
    r = recordio.MXIndexedRecordIO(idx, path, "r")
    assert r.keys == list(range(20))
    for i in (7, 0, 19, 3):  # random access
        assert r.read_idx(i) == b"rec%03d" % i
    r.close()


def test_pack_unpack_header():
    hdr = recordio.IRHeader(0, 3.0, 42, 0)
    s = recordio.pack(hdr, b"payload")
    h2, payload = recordio.unpack(s)
    assert payload == b"payload"
    assert h2.label == 3.0 and h2.id == 42

    # multi-label
    hdr = recordio.IRHeader(4, [1.0, 2.0, 3.0, 4.0], 7, 0)
    s = recordio.pack(hdr, b"xyz")
    h2, payload = recordio.unpack(s)
    np.testing.assert_array_equal(h2.label, [1, 2, 3, 4])
    assert payload == b"xyz"


def test_pack_img_roundtrip():
    img = np.random.RandomState(0).randint(0, 255, (32, 24, 3), np.uint8)
    s = recordio.pack_img(recordio.IRHeader(0, 1.0, 0, 0), img,
                          img_fmt=".png", quality=9)
    h, img2 = recordio.unpack_img(s)
    assert h.label == 1.0
    np.testing.assert_array_equal(img, img2)  # png is lossless


def _write_rec(tmp_path, n=24, hw=(40, 36)):
    prefix = str(tmp_path / "data")
    w = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rs = np.random.RandomState(0)
    for i in range(n):
        img = rs.randint(0, 255, hw + (3,), np.uint8)
        hdr = recordio.IRHeader(0, float(i % 4), i, 0)
        w.write_idx(i, recordio.pack_img(hdr, img, img_fmt=".png"))
    w.close()
    return prefix


def test_image_iter_rec(tmp_path):
    prefix = _write_rec(tmp_path)
    it = image.ImageIter(batch_size=8, data_shape=(3, 32, 32),
                         path_imgrec=prefix + ".rec")
    batches = list(it)
    assert len(batches) == 3
    for b in batches:
        assert b.data[0].shape == (8, 3, 32, 32)
        assert b.label[0].shape == (8,)
    it.reset()
    assert len(list(it)) == 3


def test_image_iter_sharding(tmp_path):
    prefix = _write_rec(tmp_path)
    seen = []
    for part in range(3):
        it = image.ImageIter(batch_size=4, data_shape=(3, 32, 32),
                             path_imgrec=prefix + ".rec",
                             part_index=part, num_parts=3)
        n = sum(b.data[0].shape[0] - b.pad for b in it)
        seen.append(n)
    assert sum(seen) == 24
    assert all(s == 8 for s in seen)


def test_image_record_iter_facade(tmp_path):
    prefix = _write_rec(tmp_path)
    it = mxio.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(3, 32, 32), batch_size=6,
        shuffle=True, rand_mirror=True, mean_r=123.0, mean_g=117.0,
        mean_b=104.0, prefetch=True)
    total = 0
    for b in it:
        assert b.data[0].shape == (6, 3, 32, 32)
        total += b.data[0].shape[0] - b.pad
    assert total == 24


def test_augmenters():
    rs = np.random.RandomState(1)
    img = rs.randint(0, 255, (48, 40, 3), np.uint8)
    assert image.resize_short(img, 32).shape[0] == 38  # aspect kept: 48*32/40
    out, _ = image.center_crop(img, (24, 24))
    assert out.shape == (24, 24, 3)
    out, _ = image.random_crop(img, (24, 24))
    assert out.shape == (24, 24, 3)
    normed = image.color_normalize(img, np.array([1.0, 2.0, 3.0]),
                                   np.array([2.0, 2.0, 2.0]))
    np.testing.assert_allclose(
        normed[0, 0], (img[0, 0].astype(np.float32) - [1, 2, 3]) / 2)
    for aug in image.CreateAugmenter((3, 32, 32), rand_crop=True,
                                     rand_mirror=True, brightness=0.1,
                                     contrast=0.1, saturation=0.1,
                                     pca_noise=0.1, mean=True, std=True):
        img2 = aug(img.astype(np.float32) if not isinstance(
            aug, (image.RandomCropAug, image.CenterCropAug)) else img)
    # chain runs without error; exact values are stochastic


def test_im2rec_tool(tmp_path):
    import cv2

    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(4):
            img = np.random.RandomState(i).randint(0, 255, (20, 20, 3),
                                                   np.uint8)
            cv2.imwrite(str(root / cls / ("%d.png" % i)), img)
    prefix = str(tmp_path / "ds")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, os.path.join(REPO, "tools/im2rec.py"),
                    "--list", prefix, str(root)], check=True, env=env,
                   timeout=300)
    subprocess.run([sys.executable, os.path.join(REPO, "tools/im2rec.py"),
                    prefix, str(root)], check=True, env=env, timeout=300)
    it = image.ImageIter(batch_size=4, data_shape=(3, 20, 20),
                         path_imgrec=prefix + ".rec")
    n = sum(b.data[0].shape[0] - b.pad for b in it)
    assert n == 8
