"""Executor bind/forward/backward semantics (reference
``tests/python/unittest/test_executor.py``)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.test_utils import assert_almost_equal

RS = np.random.RandomState(3)


def test_bind_forward_backward():
    a = sym.Variable("a")
    b = sym.Variable("b")
    out = a * b
    av = RS.rand(3, 3).astype(np.float32)
    bv = RS.rand(3, 3).astype(np.float32)
    ex = out.bind(mx.cpu(), {"a": nd.array(av), "b": nd.array(bv)},
                  args_grad={"a": nd.zeros((3, 3)), "b": nd.zeros((3, 3))})
    o = ex.forward(is_train=True)[0]
    assert_almost_equal(o, av * bv)
    head = RS.rand(3, 3).astype(np.float32)
    ex.backward([nd.array(head)])
    assert_almost_equal(ex.grad_dict["a"], head * bv, rtol=1e-5)
    assert_almost_equal(ex.grad_dict["b"], head * av, rtol=1e-5)


def test_grad_req_null_and_add():
    a = sym.Variable("a")
    out = sym.sum(a * a)
    av = RS.rand(4).astype(np.float32)
    ex = out.simple_bind(mx.cpu(), grad_req="add", a=(4,))
    ex.arg_dict["a"][:] = av
    for _ in range(3):
        ex.forward(is_train=True)
        ex.backward()
    assert_almost_equal(ex.grad_dict["a"], 3 * 2 * av, rtol=1e-5)
    ex2 = out.simple_bind(mx.cpu(), grad_req="null", a=(4,))
    ex2.forward(is_train=True)
    assert ex2.grad_dict == {} or ex2.grad_dict.get("a") is None


def test_forward_kwargs_update_inputs():
    data = sym.Variable("data")
    out = data * 2.0
    ex = out.simple_bind(mx.cpu(), grad_req="null", data=(2, 2))
    o1 = ex.forward(data=nd.ones((2, 2)))[0]
    assert_almost_equal(o1, 2 * np.ones((2, 2)))
    o2 = ex.forward(data=3 * np.ones((2, 2), np.float32))[0]
    assert_almost_equal(o2, 6 * np.ones((2, 2)))


def test_reshape_executor():
    data = sym.Variable("data")
    fc = sym.FullyConnected(data, num_hidden=4, name="fc")
    ex = fc.simple_bind(mx.cpu(), data=(8, 5))
    wv = RS.rand(4, 5).astype(np.float32)
    ex.arg_dict["fc_weight"][:] = wv
    ex2 = ex.reshape(data=(2, 5))
    assert ex2.arg_dict["data"].shape == (2, 5)
    # weights shared by identity
    assert ex2.arg_dict["fc_weight"] is ex.arg_dict["fc_weight"]
    dv = RS.rand(2, 5).astype(np.float32)
    out = ex2.forward(data=dv)[0]
    assert_almost_equal(out, dv.dot(wv.T), rtol=1e-5)


def test_shared_exec_bucketing():
    """shared_exec path: parameters shared across shapes (reference
    shared data_pool_, graph_executor.cc:336-340)."""
    def make(seq):
        d = sym.Variable("data")
        f = sym.FullyConnected(d, num_hidden=3, name="fc")
        return f

    ex_big = make(10).simple_bind(mx.cpu(), data=(10, 6))
    ex_small = make(4).simple_bind(mx.cpu(), data=(4, 6),
                                   shared_exec=ex_big)
    assert ex_small.arg_dict["fc_weight"] is ex_big.arg_dict["fc_weight"]


def test_multi_output_executor():
    d = sym.Variable("data")
    parts = sym.SliceChannel(d, num_outputs=2, axis=1, name="sc")
    ex = parts.simple_bind(mx.cpu(), grad_req="null", data=(2, 4))
    x = RS.rand(2, 4).astype(np.float32)
    outs = ex.forward(data=x)
    assert len(outs) == 2
    assert_almost_equal(outs[0], x[:, :2])
    assert_almost_equal(outs[1], x[:, 2:])


def test_monitor_callback():
    d = sym.Variable("data")
    out = d * 2.0
    ex = out.simple_bind(mx.cpu(), grad_req="null", data=(2,))
    seen = []
    ex.set_monitor_callback(lambda name, arr: seen.append(name))
    ex.forward(data=nd.ones((2,)))
    assert seen and seen[0].endswith("_output")


@pytest.mark.parametrize("consumers", ["relu-alone", "relu-and-a-sum"])
def test_batchnorm_then_relu_is_the_plain_composition(consumers):
    """BatchNorm -> relu Activation in a training graph, the relu BN's sole
    consumer or one of two: output, gradients and moving statistics are
    those of ``jax.grad`` over the composition written out in ``jax.numpy``
    (two-pass statistics, ``jax.nn.relu``)."""
    import jax
    import jax.numpy as jnp

    shape, eps, momentum = (8, 4, 6, 6), 1e-3, 0.9
    rs = np.random.RandomState(3)
    x = (rs.rand(*shape) * 5).astype(np.float32)
    gamma = rs.normal(1, 0.5, shape[1]).astype(np.float32)
    beta = rs.normal(0, 0.5, shape[1]).astype(np.float32)

    bn = sym.BatchNorm(sym.Variable("data"), fix_gamma=False, eps=eps,
                       momentum=momentum, name="bn")
    act = sym.Activation(bn, act_type="relu")
    net = sym.MakeLoss(sym.sum(act if consumers == "relu-alone"
                               else act + bn))
    ex = net.simple_bind(mx.cpu(), data=shape, grad_req="write")
    ex.arg_dict["data"][:] = x
    ex.arg_dict["bn_gamma"][:] = gamma
    ex.arg_dict["bn_beta"][:] = beta
    ex.aux_dict["bn_moving_mean"][:] = 0
    ex.aux_dict["bn_moving_var"][:] = 1
    out = ex.forward(is_train=True)[0].asnumpy()
    ex.backward()

    def stats(x):
        mean = x.mean(axis=(0, 2, 3), keepdims=True)
        return mean, ((x - mean) ** 2).mean(axis=(0, 2, 3), keepdims=True)

    def plain(x, gamma, beta):
        mean, var = stats(x)
        y = (x - mean) * jax.lax.rsqrt(var + eps) \
            * gamma.reshape(1, -1, 1, 1) + beta.reshape(1, -1, 1, 1)
        return jnp.sum(jax.nn.relu(y) if consumers == "relu-alone"
                       else jax.nn.relu(y) + y)

    ref, grads = jax.value_and_grad(plain, argnums=(0, 1, 2))(x, gamma, beta)
    assert_almost_equal(out, np.asarray(ref), rtol=1e-5)
    for name, g in zip(("data", "bn_gamma", "bn_beta"), grads):
        assert_almost_equal(ex.grad_dict[name], np.asarray(g), rtol=1e-3,
                            atol=1e-4)
    mean, var = (np.asarray(a).ravel() for a in stats(x))
    assert_almost_equal(ex.aux_dict["bn_moving_mean"],
                        (1 - momentum) * mean, rtol=1e-5)
    assert_almost_equal(ex.aux_dict["bn_moving_var"],
                        momentum + (1 - momentum) * var, rtol=1e-5)


def test_backward_mirror_exactness(monkeypatch):
    """MXNET_BACKWARD_DO_MIRROR trades FLOPs for memory but must be
    bit-compatible: same outputs and gradients (SURVEY §2.4 strategy 5)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import sym

    def build_and_grad():
        data = sym.Variable("data")
        net = sym.Convolution(data, num_filter=4, kernel=(3, 3), pad=(1, 1),
                              name="c1")
        net = sym.BatchNorm(net, name="bn1")
        net = sym.Activation(net, act_type="relu")
        net = sym.Convolution(net, num_filter=4, kernel=(3, 3), pad=(1, 1),
                              name="c2")
        net = sym.Flatten(net)
        net = sym.FullyConnected(net, num_hidden=3, name="fc")
        net = sym.SoftmaxOutput(net, name="softmax")
        ex = net.simple_bind(mx.cpu(), data=(2, 3, 8, 8),
                             softmax_label=(2,))
        rs = np.random.RandomState(0)
        for n, a in ex.arg_dict.items():
            a[:] = rs.rand(*a.shape).astype(np.float32)
        ex.arg_dict["softmax_label"][:] = np.array([1.0, 2.0])
        ex.forward(is_train=True)
        ex.backward()
        return (ex.outputs[0].asnumpy(),
                {k: v.asnumpy() for k, v in ex.grad_dict.items()
                 if v is not None})

    monkeypatch.delenv("MXNET_BACKWARD_DO_MIRROR", raising=False)
    out_off, g_off = build_and_grad()
    for mode in ("1", "2"):
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", mode)
        out_on, g_on = build_and_grad()
        np.testing.assert_allclose(out_off, out_on, rtol=1e-5, atol=1e-6)
        assert set(g_off) == set(g_on)
        for k in g_off:
            np.testing.assert_allclose(g_off[k], g_on[k], rtol=1e-4,
                                       atol=1e-5, err_msg="%s/%s"
                                       % (mode, k))
