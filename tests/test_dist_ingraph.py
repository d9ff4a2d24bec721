"""TPU-native multi-process dist_sync: the gradient plane is in-graph
collectives (psum over the global jax.distributed mesh), not parameter-server
push/pull.

Reference analog: ``tests/nightly/dist_sync_kvstore.py`` (launched via
``tools/launch.py -n N``) asserts arithmetic exactness of the dist gradient
plane; here additionally (a) per-step PS traffic must be ZERO and (b) the
2-process result must match a single-process 2-device mesh run."""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os, sys
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import mxnet_tpu as mx
from mxnet_tpu import io

kv = mx.kv.create("dist_sync")
rank, n = kv.rank, kv.num_workers
assert kv.in_graph_sync, "process group did not initialize"

# count per-step PS traffic AFTER optimizer init
pushes = {"n": 0}
orig_push = kv.push
def counted_push(*a, **k):
    pushes["n"] += 1
    return orig_push(*a, **k)
kv.push = counted_push

rs = np.random.RandomState(42)  # same data on every rank; slice by rank
X = rs.rand(64, 10).astype(np.float32)
Y = rs.randint(0, 4, 64).astype(np.float32)
local_x = X[rank * 32:(rank + 1) * 32]
local_y = Y[rank * 32:(rank + 1) * 32]

data = mx.sym.Variable("data")
h = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
h = mx.sym.Activation(h, act_type="relu")
h = mx.sym.FullyConnected(h, num_hidden=4, name="fc2")
net = mx.sym.SoftmaxOutput(h, name="softmax")

mod = mx.mod.Module(net, context=mx.cpu())
it = io.NDArrayIter(local_x, local_y, batch_size=8)
mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
np.random.seed(7 if rank == 0 else 999)  # DIFFERENT init per rank on
# purpose: only rank 0's draw may survive (the broadcast-from-root check)
mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
opt_name = os.environ.get("TEST_OPT", "sgd")
opt_params = {"learning_rate": 0.2, "momentum": 0.9} if opt_name == "sgd" \
    else {"learning_rate": 0.05}
mod.init_optimizer(kvstore=kv, optimizer=opt_name,
                   optimizer_params=opt_params)
init_pushes = pushes["n"]

assert mod._dist_dp, "module did not enter global-mesh mode"
for epoch in range(3):
    it.reset()
    for batch in it:
        mod.forward_backward(batch)
        mod.update()
assert pushes["n"] == init_pushes, \
    "per-step PS traffic detected: %d pushes" % (pushes["n"] - init_pushes)

params = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
out_dir = os.environ["OUT_DIR"]
np.savez(os.path.join(out_dir, "params.%d.npz" % rank), **params)
outs = mod.get_outputs()[0].asnumpy()
assert outs.shape == (8, 4), outs.shape  # per-worker local rows
open(os.path.join(out_dir, "ok.%d" % rank), "w").write("1")
kv.close()
"""


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_dist_sync_in_graph_two_workers(tmp_path, opt_name):
    # adam covers the non-fused update path: gradients are already
    # globally psum'd in-graph, so update() must NOT route them through
    # the PS a second time (ADVICE r2 high: double reduction)
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = dict(os.environ, OUT_DIR=str(tmp_path), JAX_PLATFORMS="cpu",
               TEST_OPT=opt_name)
    env.pop("DMLC_PS_ROOT_PORT", None)
    env.pop("XLA_FLAGS", None)  # workers see exactly one local cpu device
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", sys.executable, str(script)],
        env=env, timeout=300, capture_output=True, text=True)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-3000:])
    assert (tmp_path / "ok.0").exists() and (tmp_path / "ok.1").exists()

    p0 = dict(np.load(tmp_path / "params.0.npz"))
    p1 = dict(np.load(tmp_path / "params.1.npz"))
    # rank-0 init was broadcast and every update is the same psum'd
    # gradient -> weights must be IDENTICAL across workers
    for k in p0:
        np.testing.assert_array_equal(p0[k], p1[k], err_msg=k)

    # and must match a single-process 2-device mesh run on the same
    # global batch with the same rank-0 init
    ref = _single_process_reference(opt_name)
    for k in ref:
        np.testing.assert_allclose(p0[k], ref[k], rtol=2e-5, atol=1e-6,
                                   err_msg=k)


def _single_process_reference(opt_name="sgd"):
    """Same training run: one process, 2-virtual-device mesh, global
    batch 16, rank-0's initializer."""
    script = r"""
import os, sys, json
sys.path.insert(0, %r)
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import mxnet_tpu as mx
from mxnet_tpu import io

rs = np.random.RandomState(42)
X = rs.rand(64, 10).astype(np.float32)
Y = rs.randint(0, 4, 64).astype(np.float32)
# interleave the two ranks' batches the way the global mesh sees them:
# global batch = [rank0 batch rows, rank1 batch rows]
order = []
for b in range(4):
    order += list(range(b * 8, b * 8 + 8))            # rank0 rows
    order += list(range(32 + b * 8, 32 + b * 8 + 8))  # rank1 rows
Xg, Yg = X[order], Y[order]

data = mx.sym.Variable("data")
h = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
h = mx.sym.Activation(h, act_type="relu")
h = mx.sym.FullyConnected(h, num_hidden=4, name="fc2")
net = mx.sym.SoftmaxOutput(h, name="softmax")

mod = mx.mod.Module(net, context=[mx.cpu(0), mx.cpu(1)])
it = io.NDArrayIter(Xg, Yg, batch_size=16)
mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
np.random.seed(7)  # rank-0's init draw
mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
# rescale matches dist (local 8 x 2 workers = 16)
opt_name = os.environ.get("TEST_OPT", "sgd")
opt_params = {"learning_rate": 0.2, "momentum": 0.9} if opt_name == "sgd" \
    else {"learning_rate": 0.05}
mod.init_optimizer(optimizer=opt_name, optimizer_params=opt_params)
for epoch in range(3):
    it.reset()
    for batch in it:
        mod.forward_backward(batch)
        mod.update()
params = {k: v.asnumpy().tolist() for k, v in mod.get_params()[0].items()}
print(json.dumps(params))
"""
    import json
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(script % REPO)
        path = f.name
    env = dict(os.environ, TEST_OPT=opt_name)
    for k in ("DMLC_ROLE", "DMLC_NUM_WORKER", "DMLC_WORKER_ID"):
        env.pop(k, None)
    proc = subprocess.run([sys.executable, path], env=env, timeout=300,
                          capture_output=True, text=True)
    os.unlink(path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    return {k: np.asarray(v, np.float32) for k, v in out.items()}


_WORKER_BN_DROPOUT = r"""
import os, sys
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import mxnet_tpu as mx
from mxnet_tpu import io

kv = mx.kv.create("dist_sync")
rank, n = kv.rank, kv.num_workers
assert kv.in_graph_sync

rs = np.random.RandomState(13)
X = rs.rand(64, 10).astype(np.float32)
Y = rs.randint(0, 4, 64).astype(np.float32)
local_x = X[rank * 32:(rank + 1) * 32]
local_y = Y[rank * 32:(rank + 1) * 32]

data = mx.sym.Variable("data")
h = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
h = mx.sym.BatchNorm(h, name="bn1")  # aux stats update in-graph
h = mx.sym.Activation(h, act_type="relu")
h = mx.sym.Dropout(h, p=0.25)  # multihost rng must advance per step
h = mx.sym.FullyConnected(h, num_hidden=4, name="fc2")
net = mx.sym.SoftmaxOutput(h, name="softmax")

mod = mx.mod.Module(net, context=mx.cpu())
it = io.NDArrayIter(local_x, local_y, batch_size=8)
mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
np.random.seed(3 + rank)
mod.init_params(mx.init.Xavier())
mod.init_optimizer(kvstore=kv, optimizer="sgd",
                   optimizer_params={"learning_rate": 0.1,
                                     "momentum": 0.9})
rngs = []
for epoch in range(2):
    it.reset()
    for batch in it:
        mod.forward_backward(batch)
        mod.update()
        rngs.append(int(np.asarray(mod._exec._rng_step)))
assert rngs == sorted(set(rngs)), "rng step did not advance: %s" % rngs

out = {}
for k, v in mod.get_params()[0].items():
    out[k] = v.asnumpy()
for k, v in mod.get_params()[1].items():
    out["aux_" + k] = v.asnumpy()
np.savez(os.path.join(os.environ["OUT_DIR"], "bnp.%d.npz" % rank), **out)
open(os.path.join(os.environ["OUT_DIR"], "ok.%d" % rank), "w").write("1")
kv.close()
"""


def test_dist_sync_in_graph_bn_dropout(tmp_path):
    """BatchNorm aux stats and Dropout masks come from the in-graph
    global-batch computation: every worker must end with IDENTICAL
    params AND moving stats, and the shared rng key must advance every
    step (stale-key regression test)."""
    script = tmp_path / "worker.py"
    script.write_text(_WORKER_BN_DROPOUT)
    env = dict(os.environ, OUT_DIR=str(tmp_path), JAX_PLATFORMS="cpu")
    env.pop("DMLC_PS_ROOT_PORT", None)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", sys.executable, str(script)],
        env=env, timeout=300, capture_output=True, text=True)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-3000:])
    p0 = dict(np.load(tmp_path / "bnp.0.npz"))
    p1 = dict(np.load(tmp_path / "bnp.1.npz"))
    assert any(k.startswith("aux_") for k in p0)
    for k in p0:
        np.testing.assert_array_equal(p0[k], p1[k], err_msg=k)
    # training actually moved the BN stats
    assert np.abs(p0["aux_bn1_moving_mean"]).sum() > 0


_WORKER_BOTH_PLANES = r"""
import os, sys
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
import mxnet_tpu as mx
from mxnet_tpu import io

kv = mx.kv.create("dist_sync")
rank, n = kv.rank, kv.num_workers
assert n == 4 and kv.in_graph_sync and kv._num_servers == 2

# PS plane alongside the collective plane: sharded big-array exactness
big = np.arange(12, dtype=np.float32)
kv.init(3, mx.nd.zeros((12,)))
kv.push(3, mx.nd.array(big * (rank + 1)))
out = mx.nd.zeros((12,))
kv.pull(3, out=out)
np.testing.assert_array_equal(out.asnumpy(), big * 10)  # 1+2+3+4

# collective plane: 4-way in-graph DP
rs = np.random.RandomState(21)
X = rs.rand(64, 6).astype(np.float32)
Y = rs.randint(0, 3, 64).astype(np.float32)
lx = X[rank * 16:(rank + 1) * 16]
ly = Y[rank * 16:(rank + 1) * 16]
net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
    mx.sym.Variable("data"), num_hidden=3, name="fc"), name="softmax")
mod = mx.mod.Module(net, context=mx.cpu())
it = io.NDArrayIter(lx, ly, batch_size=8)
mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
np.random.seed(rank * 11 + 1)
mod.init_params(mx.init.Xavier())
mod.init_optimizer(kvstore=kv, optimizer="sgd",
                   optimizer_params={"learning_rate": 0.2})
for _ in range(2):
    it.reset()
    for b in it:
        mod.forward_backward(b)
        mod.update()
w = mod.get_params()[0]["fc_weight"].asnumpy()
np.save(os.path.join(os.environ["OUT_DIR"], "w%d.npy" % rank), w)
open(os.path.join(os.environ["OUT_DIR"], "ok.%d" % rank), "w").write("1")
kv.close()
"""


def test_four_workers_two_servers_both_planes(tmp_path):
    """4 workers x 2 PS shards: the sharded push/pull plane and the
    in-graph collective plane coexist in one job; weights identical on
    every worker."""
    script = tmp_path / "worker.py"
    script.write_text(_WORKER_BOTH_PLANES)
    env = dict(os.environ, OUT_DIR=str(tmp_path), JAX_PLATFORMS="cpu",
               MXNET_KVSTORE_BIGARRAY_BOUND="8")
    env.pop("DMLC_PS_ROOT_PORT", None)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "4", "-s", "2",
         "--env", "MXNET_KVSTORE_BIGARRAY_BOUND=8",
         sys.executable, str(script)],
        env=env, timeout=300, capture_output=True, text=True)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-3000:])
    ws = [np.load(tmp_path / ("w%d.npy" % r)) for r in range(4)]
    for r in range(1, 4):
        np.testing.assert_array_equal(ws[0], ws[r])
    assert np.abs(ws[0]).sum() > 0
