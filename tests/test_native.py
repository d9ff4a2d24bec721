"""Native host runtime tests: engine / pooled storage / recordio scanner.

The engine tests mirror the reference's ``tests/cpp/threaded_engine_test.cc``
(randomized dependency workloads + push/wait semantics) and
``storage_test.cc`` (pool reuse assertions), as Python tests over the ctypes
ABI.
"""

import os
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import native


@pytest.fixture(scope="module")
def lib():
    lib = native.get_lib()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    return lib


def test_engine_write_ordering(lib):
    eng = native.Engine(num_workers=4)
    var = eng.new_var()
    log = []
    for i in range(100):
        eng.push(lambda i=i: log.append(i), mutable_vars=[var])
    eng.wait_for_all()
    assert log == list(range(100))  # writers on one var are serialized
    eng.close()


def test_engine_readers_parallel_writers_exclusive(lib):
    eng = native.Engine(num_workers=8)
    var = eng.new_var()
    state = {"readers": 0, "writer": False, "max_readers": 0,
             "violations": 0}
    lock = threading.Lock()

    def read():
        with lock:
            if state["writer"]:
                state["violations"] += 1
            state["readers"] += 1
            state["max_readers"] = max(state["max_readers"],
                                       state["readers"])
        time.sleep(0.002)
        with lock:
            state["readers"] -= 1

    def write():
        with lock:
            if state["writer"] or state["readers"]:
                state["violations"] += 1
            state["writer"] = True
        time.sleep(0.002)
        with lock:
            state["writer"] = False

    rng = np.random.RandomState(0)
    for _ in range(60):
        if rng.rand() < 0.7:
            eng.push(read, const_vars=[var])
        else:
            eng.push(write, mutable_vars=[var])
    eng.wait_for_all()
    assert state["violations"] == 0
    assert state["max_readers"] > 1  # reads did overlap
    eng.close()


def test_engine_randomized_dependencies(lib):
    """Random var sets; verify writer-exclusion per var (the
    threaded_engine_test.cc randomized workload)."""
    eng = native.Engine(num_workers=8)
    n_vars = 10
    vars_ = [eng.new_var() for _ in range(n_vars)]
    flags = [0] * n_vars
    lock = threading.Lock()
    violations = []
    counts = [0] * n_vars
    rng = np.random.RandomState(1)

    def make_op(mut_idx, const_idx):
        def op():
            with lock:
                for i in mut_idx + const_idx:
                    if flags[i] == -1:
                        violations.append(i)  # concurrent writer present
                for i in mut_idx:
                    if flags[i] != 0:
                        violations.append(i)
                    flags[i] = -1
                for i in const_idx:
                    flags[i] += 1
            time.sleep(0.001)
            with lock:
                for i in mut_idx:
                    flags[i] = 0
                    counts[i] += 1
                for i in const_idx:
                    flags[i] -= 1
        return op

    expected = [0] * n_vars
    for _ in range(150):
        k = rng.randint(1, 4)
        idx = list(rng.choice(n_vars, size=k, replace=False))
        cut = rng.randint(0, k + 1)
        mut, const = idx[:cut], idx[cut:]
        for i in mut:
            expected[i] += 1
        eng.push(make_op(mut, const),
                 const_vars=[vars_[i] for i in const],
                 mutable_vars=[vars_[i] for i in mut])
    eng.wait_for_all()
    assert violations == []
    assert counts == expected
    eng.close()


def test_engine_wait_for_var(lib):
    eng = native.Engine(num_workers=2)
    var = eng.new_var()
    done = []
    eng.push(lambda: (time.sleep(0.05), done.append(1)), mutable_vars=[var])
    eng.wait_for_var(var)
    assert done == [1]
    eng.close()


def test_naive_engine_sync(lib):
    eng = native.Engine(engine_type="NaiveEngine")
    var = eng.new_var()
    log = []
    eng.push(lambda: log.append(1), mutable_vars=[var])
    assert log == [1]  # executed synchronously on push
    eng.close()


def test_pooled_storage_reuse(lib):
    st = native.PooledStorage()
    p1 = st.alloc(1000)           # bucket 1024
    assert st.used_bytes == 1024
    st.free(p1, 1000)
    assert st.pooled_bytes == 1024 and st.used_bytes == 0
    p2 = st.alloc(900)            # same bucket → reuse p1
    assert p2 == p1
    assert st.pooled_bytes == 0
    p3 = st.alloc(2000)           # bucket 2048, fresh
    assert p3 != p2
    st.free(p2, 900)
    st.free(p3, 2000)
    st.release_all()
    assert st.pooled_bytes == 0
    st.close()


def test_recordio_scan_matches_python(lib, tmp_path):
    from mxnet_tpu import recordio

    path = str(tmp_path / "scan.rec")
    w = recordio.MXRecordIO(path, "w")
    import struct
    magic = struct.pack("<I", 0xced7230a)
    payloads = [b"a" * 10, b"bb" + magic + b"cc", b"", b"d" * 999]
    offsets = []
    for p in payloads:
        offsets.append(w.tell())
        w.write(p)
    w.close()
    scanned = native.recordio_scan(path)
    assert scanned == offsets


def test_indexed_recordio_native_rebuild(tmp_path):
    """MXIndexedRecordIO random access without a .idx file."""
    from mxnet_tpu import recordio

    path = str(tmp_path / "noidx.rec")
    w = recordio.MXRecordIO(path, "w")
    for i in range(12):
        w.write(b"payload-%04d" % i)
    w.close()
    r = recordio.MXIndexedRecordIO(str(tmp_path / "noidx.idx"), path, "r")
    if not r.keys:
        pytest.skip("native scanner unavailable")
    assert r.read_idx(7) == b"payload-0007"
    assert r.read_idx(0) == b"payload-0000"
    assert r.read_idx(11) == b"payload-0011"


def test_image_iter_parallel_decode(tmp_path):
    from mxnet_tpu import image, recordio

    prefix = str(tmp_path / "p")
    w = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rs = np.random.RandomState(0)
    for i in range(16):
        img = rs.randint(0, 255, (24, 24, 3), np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img, img_fmt=".png"))
    w.close()

    kw = dict(batch_size=8, data_shape=(3, 24, 24),
              path_imgrec=prefix + ".rec", aug_list=[])
    serial = [b.data[0].copy() for b in image.ImageIter(**kw)]
    parallel = [b.data[0].copy()
                for b in image.ImageIter(preprocess_threads=4, **kw)]
    assert len(serial) == len(parallel) == 2
    for a, b in zip(serial, parallel):
        np.testing.assert_array_equal(a, b)


def test_c_api_header_from_pure_c(tmp_path):
    """include/mxnet_tpu/c_api.h is the binding surface: a pure-C program
    compiled against it must drive the engine and storage pool (the
    reference's c_api.h multi-language contract, SURVEY §2.7)."""
    import subprocess

    from mxnet_tpu.native import get_lib, _LIB_PATH

    if get_lib() is None:
        pytest.skip("native toolchain unavailable")
    src = tmp_path / "t.c"
    src.write_text(r'''
#include "mxnet_tpu/c_api.h"
#include <stdio.h>
static int counter = 0;
static void incr(void* ctx) { counter += *(int*)ctx; }
int main(void) {
  void* eng = EngineCreate(2, 0);
  void* var = EngineNewVar(eng);
  int three = 3; void* mv[1] = {var};
  for (int i = 0; i < 10; i++) EnginePush(eng, incr, &three, 0, 0, mv, 1);
  EngineWaitForAll(eng);
  if (counter != 30) return 1;
  void* st = StorageCreate();
  void* p = StorageAlloc(st, 1024);
  StorageRelease(st, p, 1024);
  if (StorageAlloc(st, 1024) != p) return 2;
  StorageFree(st); EngineFree(eng);
  return 0;
}
''')
    exe = tmp_path / "t"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run(["gcc", "-I", os.path.join(repo, "include"), str(src),
                    "-o", str(exe), _LIB_PATH, "-lpthread"], check=True,
                   timeout=300)
    subprocess.run([str(exe)], check=True, timeout=300)
