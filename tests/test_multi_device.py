"""Multi-device data parallelism on the 8-device virtual mesh.

Reference analog: ``tests/nightly/multi_lenet.py`` (multi-GPU parity — same
net trained single vs multi device must match) and
``tests/python/unittest/test_multi_device_exec.py`` — contexts are
fake-device fixtures; here they are the 8 virtual CPU devices standing in
for an 8-chip slice (SURVEY §4).
"""

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import io, sym
from mxnet_tpu.test_utils import assert_almost_equal


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip("needs %d devices" % n)


def _toy_data(n=512, num_class=4, dim=8, seed=0):
    rs = np.random.RandomState(seed)
    centers = rs.rand(num_class, dim).astype(np.float32)
    labels = rs.randint(0, num_class, n)
    x = centers[labels] + 0.1 * rs.rand(n, dim).astype(np.float32)
    return x, labels.astype(np.float32)


def _mlp():
    data = sym.Variable("data")
    fc1 = sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = sym.Activation(fc1, act_type="relu")
    fc2 = sym.FullyConnected(act, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(fc2, name="softmax")


def _train(contexts, seed=0):
    np.random.seed(seed)
    mx.random.seed(seed)
    x, y = _toy_data()
    it = io.NDArrayIter(x, y, batch_size=64)
    mod = mx.mod.Module(_mlp(), context=contexts)
    mod.fit(it, optimizer="sgd", optimizer_params={"learning_rate": 0.3},
            initializer=mx.init.Xavier(), num_epoch=2)
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}, mod


def test_single_vs_multi_device_parity():
    """Same data+init on 1 device vs 8-device mesh must give near-identical
    weights — the multi_lenet.py assertion."""
    _need_devices(8)
    w1, _ = _train([mx.cpu(0)])
    w8, _ = _train([mx.cpu(i) for i in range(8)])
    for k in w1:
        assert_almost_equal(w1[k], w8[k], rtol=1e-3, atol=1e-4)


def test_multi_device_sharded_forward():
    _need_devices(4)
    x, y = _toy_data(128)
    it = io.NDArrayIter(x, y, batch_size=32)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(4)])
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    batch = it.next()
    mod.forward(batch, is_train=True)
    out = mod.get_outputs()[0]
    assert out.shape == (32, 4)
    # data array is sharded over the mesh
    data_arr = mod._exec.arg_dict["data"]._jx
    assert len(data_arr.sharding.device_set) == 4
    mod.backward()
    mod.update()


def test_batch_not_divisible_raises():
    _need_devices(8)
    x, y = _toy_data(60)
    it = io.NDArrayIter(x, y, batch_size=30)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    with pytest.raises(mx.MXNetError):
        mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)


# -- group2ctx placement (model parallelism) --------------------------------
def _group2ctx_sym():
    data = sym.Variable("data")
    with mx.AttrScope(ctx_group="g0"):
        h = sym.FullyConnected(data, num_hidden=16, name="fc1")
        h = sym.Activation(h, act_type="relu")
    with mx.AttrScope(ctx_group="g1"):
        h = sym.FullyConnected(h, num_hidden=4, name="fc2")
        out = sym.SoftmaxOutput(h, name="softmax")
    return out


def test_group2ctx_places_params_on_groups():
    """ctx_group annotations must MOVE parameters onto the mapped devices
    (reference PlaceDevice, graph_executor.cc:231-305) — not silently run
    the whole graph on the bind context."""
    _need_devices(2)
    net = _group2ctx_sym()
    ex = net.simple_bind(mx.cpu(0),
                         group2ctx={"g0": mx.cpu(0), "g1": mx.cpu(1)},
                         data=(8, 10), softmax_label=(8,))
    d0 = mx.cpu(0).jax_device()
    d1 = mx.cpu(1).jax_device()
    assert list(ex.arg_dict["fc1_weight"]._jx.devices()) == [d0]
    assert list(ex.arg_dict["fc2_weight"]._jx.devices()) == [d1]
    assert list(ex.grad_dict["fc2_weight"]._jx.devices()) == [d1]
    devs = {next(iter(a._jx.devices())) for n, a in ex.arg_dict.items()}
    assert len(devs) >= 2


def test_group2ctx_matches_single_device():
    """Same net, same init: group2ctx placement across 2 devices must
    produce the same outputs and gradients as single-device execution."""
    _need_devices(2)
    rs = np.random.RandomState(0)
    x = rs.rand(8, 10).astype(np.float32)
    y = rs.randint(0, 4, 8).astype(np.float32)
    params = {"fc1_weight": rs.randn(16, 10).astype(np.float32) * 0.1,
              "fc1_bias": np.zeros(16, np.float32),
              "fc2_weight": rs.randn(4, 16).astype(np.float32) * 0.1,
              "fc2_bias": np.zeros(4, np.float32)}

    def run(group2ctx):
        net = _group2ctx_sym()
        ex = net.simple_bind(mx.cpu(0), group2ctx=group2ctx,
                             data=(8, 10), softmax_label=(8,))
        for n, v in params.items():
            ex.arg_dict[n][:] = v
        ex.arg_dict["data"][:] = x
        ex.arg_dict["softmax_label"][:] = y
        ex.forward(is_train=True)
        ex.backward()
        return (ex.outputs[0].asnumpy(),
                {n: g.asnumpy() for n, g in ex.grad_dict.items()
                 if g is not None and n not in ("data", "softmax_label")})

    out1, g1 = run(None)
    out2, g2 = run({"g0": mx.cpu(0), "g1": mx.cpu(1)})
    assert_almost_equal(out2, out1, rtol=1e-5, atol=1e-6)
    for k in g1:
        assert_almost_equal(g2[k], g1[k], rtol=1e-5, atol=1e-6)


def test_group2ctx_uniform_collapses_to_fast_path():
    """All groups on the bind device -> no segmentation."""
    net = _group2ctx_sym()
    ex = net.simple_bind(mx.cpu(0),
                         group2ctx={"g0": mx.cpu(0), "g1": mx.cpu(0)},
                         data=(8, 10), softmax_label=(8,))
    assert ex._segments is None


def test_group2ctx_predict_and_aux():
    """Segmented path handles aux-state ops (BatchNorm) and predict."""
    _need_devices(2)
    data = sym.Variable("data")
    with mx.AttrScope(ctx_group="g0"):
        h = sym.FullyConnected(data, num_hidden=8, name="fc1")
        h = sym.BatchNorm(h, name="bn1")
    with mx.AttrScope(ctx_group="g1"):
        h = sym.FullyConnected(h, num_hidden=2, name="fc2")
        net = sym.SoftmaxOutput(h, name="softmax")
    ex = net.simple_bind(mx.cpu(0),
                         group2ctx={"g0": mx.cpu(0), "g1": mx.cpu(1)},
                         data=(4, 6), softmax_label=(4,))
    rs = np.random.RandomState(1)
    for n, a in ex.arg_dict.items():
        if n not in ("data", "softmax_label"):
            a[:] = rs.randn(*a.shape).astype(np.float32) * 0.1
    ex.arg_dict["data"][:] = rs.rand(4, 6).astype(np.float32)
    ex.arg_dict["softmax_label"][:] = np.array([0, 1, 0, 1], np.float32)
    mean0 = ex.aux_dict["bn1_moving_mean"].asnumpy().copy()
    ex.forward(is_train=True)
    ex.backward()
    assert not np.allclose(ex.aux_dict["bn1_moving_mean"].asnumpy(), mean0)
    ex.forward(is_train=False)
    out = ex.outputs[0].asnumpy()
    assert out.shape == (4, 2)
    np.testing.assert_allclose(out.sum(axis=1), np.ones(4), rtol=1e-5)


# -- Module.fit on an explicit mesh with TP shard_rules ---------------------
def test_module_fit_on_mesh_with_tp_rules():
    """`Module.fit` — not a second trainer class —
    runs dp×tp: params sharded by shard_rules train to the same weights
    as a plain single-device module."""
    _need_devices(8)
    from jax.sharding import Mesh, PartitionSpec as P

    x, y = _toy_data(256, dim=8)
    rules = [("fc1_weight", P(None, "model")),
             ("fc2_weight", P("model", None))]

    def run(mesh_mode):
        mx.random.seed(0)
        train = io.NDArrayIter(x, y, batch_size=32)
        if mesh_mode:
            mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                        ("data", "model"))
            mod = mx.mod.Module(_mlp(), context=mesh, shard_rules=rules)
        else:
            mod = mx.mod.Module(_mlp(), context=mx.cpu())
        mod.bind(data_shapes=train.provide_data,
                 label_shapes=train.provide_label)
        np.random.seed(11)
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.2,
                                             "momentum": 0.9})
        for _ in range(2):
            train.reset()
            for batch in train:
                mod.forward_backward(batch)
                mod.update()
        if mesh_mode:
            w = mod._exec.arg_dict["fc1_weight"]._jx
            assert len(w.sharding.device_set) == 8
            spec = w.sharding.spec
            assert tuple(spec) == (None, "model"), spec
            d = mod._exec.arg_dict["data"]._jx
            assert "data" in tuple(d.sharding.spec), d.sharding.spec
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    single = run(False)
    meshed = run(True)
    for k in single:
        assert_almost_equal(meshed[k], single[k], rtol=2e-4, atol=1e-5)
