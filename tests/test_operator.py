"""Per-op numerical checks vs numpy (reference ``tests/python/unittest/
test_operator.py``, 3018 LoC — same harness style via test_utils)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.test_utils import (assert_almost_equal, check_numeric_gradient,
                                  check_symbolic_backward,
                                  check_symbolic_forward)

RS = np.random.RandomState(7)


def test_elemwise_binary_forward():
    a = RS.rand(3, 4).astype(np.float32) + 0.5
    b = RS.rand(3, 4).astype(np.float32) + 0.5
    for name, ref in [("elemwise_add", a + b), ("elemwise_sub", a - b),
                      ("elemwise_mul", a * b), ("elemwise_div", a / b),
                      ("_power", a ** b), ("_maximum", np.maximum(a, b)),
                      ("_minimum", np.minimum(a, b)),
                      ("_hypot", np.hypot(a, b))]:
        out = getattr(nd, name)(nd.array(a), nd.array(b))
        assert_almost_equal(out, ref, rtol=1e-5, atol=1e-6)


def test_unary_forward():
    x = RS.rand(2, 5).astype(np.float32) * 0.8 + 0.1
    cases = [("sqrt", np.sqrt), ("exp", np.exp), ("log", np.log),
             ("square", np.square), ("abs", np.abs), ("sign", np.sign),
             ("sin", np.sin), ("cos", np.cos), ("tanh", np.tanh),
             ("arcsin", np.arcsin), ("log1p", np.log1p),
             ("expm1", np.expm1), ("rsqrt", lambda v: 1 / np.sqrt(v)),
             ("degrees", np.degrees), ("radians", np.radians)]
    for name, ref in cases:
        assert_almost_equal(getattr(nd, name)(nd.array(x)), ref(x),
                            rtol=1e-5, atol=1e-6)


def test_scalar_ops():
    x = RS.rand(3, 3).astype(np.float32)
    assert_almost_equal(nd._plus_scalar(nd.array(x), scalar=2.0), x + 2)
    assert_almost_equal(nd._rminus_scalar(nd.array(x), scalar=2.0), 2 - x)
    assert_almost_equal(nd._rdiv_scalar(nd.array(x + 1), scalar=2.0),
                        2 / (x + 1), rtol=1e-5)
    assert_almost_equal(nd._power_scalar(nd.array(x), scalar=2.0), x ** 2,
                        rtol=1e-5)


def test_broadcast_ops():
    a = RS.rand(3, 1, 5).astype(np.float32)
    b = RS.rand(1, 4, 5).astype(np.float32)
    assert_almost_equal(nd.broadcast_add(nd.array(a), nd.array(b)), a + b)
    assert_almost_equal(nd.broadcast_mul(nd.array(a), nd.array(b)), a * b)
    assert_almost_equal(
        nd.broadcast_to(nd.array(a), shape=(3, 4, 5)),
        np.broadcast_to(a, (3, 4, 5)))


def test_reductions():
    x = RS.rand(2, 3, 4).astype(np.float32)
    assert_almost_equal(nd.sum(nd.array(x)), x.sum(), rtol=1e-5)
    assert_almost_equal(nd.sum(nd.array(x), axis=1), x.sum(1), rtol=1e-5)
    assert_almost_equal(nd.sum(nd.array(x), axis=(0, 2), keepdims=True),
                        x.sum((0, 2), keepdims=True), rtol=1e-5)
    assert_almost_equal(nd.mean(nd.array(x), axis=2), x.mean(2), rtol=1e-5)
    assert_almost_equal(nd.max(nd.array(x), axis=0), x.max(0))
    assert_almost_equal(nd.min(nd.array(x), axis=1), x.min(1))
    assert_almost_equal(nd.argmax(nd.array(x), axis=1), x.argmax(1))
    assert_almost_equal(nd.norm(nd.array(x)),
                        np.array([np.sqrt((x ** 2).sum())]), rtol=1e-5)
    xn = x.copy()
    xn[0, 0, 0] = np.nan
    assert_almost_equal(nd.nansum(nd.array(xn)), np.nansum(xn), rtol=1e-5)


def test_matrix_ops():
    a = RS.rand(3, 4).astype(np.float32)
    b = RS.rand(4, 5).astype(np.float32)
    assert_almost_equal(nd.dot(nd.array(a), nd.array(b)), a.dot(b), rtol=1e-5)
    assert_almost_equal(
        nd.dot(nd.array(a), nd.array(b.T), transpose_b=True), a.dot(b),
        rtol=1e-5)
    ba = RS.rand(2, 3, 4).astype(np.float32)
    bb = RS.rand(2, 4, 5).astype(np.float32)
    assert_almost_equal(nd.batch_dot(nd.array(ba), nd.array(bb)),
                        np.matmul(ba, bb), rtol=1e-5)
    x = RS.rand(2, 3, 4).astype(np.float32)
    assert_almost_equal(nd.transpose(nd.array(x), axes=(2, 0, 1)),
                        x.transpose(2, 0, 1))
    assert_almost_equal(nd.Reshape(nd.array(x), shape=(3, -1)),
                        x.reshape(3, -1))
    assert_almost_equal(nd.Reshape(nd.array(x), shape=(0, -1)),
                        x.reshape(2, -1))
    assert_almost_equal(nd.slice(nd.array(x), begin=(0, 1, 0),
                                 end=(2, 3, 2)), x[0:2, 1:3, 0:2])
    assert_almost_equal(nd.slice_axis(nd.array(x), axis=1, begin=1, end=3),
                        x[:, 1:3])
    assert_almost_equal(nd.clip(nd.array(x), a_min=0.2, a_max=0.8),
                        np.clip(x, 0.2, 0.8))
    assert_almost_equal(nd.repeat(nd.array(x), repeats=2, axis=1),
                        np.repeat(x, 2, 1))
    assert_almost_equal(nd.tile(nd.array(x), reps=(1, 2, 1)),
                        np.tile(x, (1, 2, 1)))
    assert_almost_equal(nd.reverse(nd.array(x), axis=(1,)), x[:, ::-1])
    assert_almost_equal(nd.SwapAxis(nd.array(x), dim1=0, dim2=2),
                        x.swapaxes(0, 2))
    assert_almost_equal(nd.expand_dims(nd.array(x), axis=1),
                        np.expand_dims(x, 1))
    assert_almost_equal(nd.Flatten(nd.array(x)), x.reshape(2, -1))


def test_indexing_ops():
    w = RS.rand(10, 4).astype(np.float32)
    idx = np.array([1, 3, 5], dtype=np.float32)
    assert_almost_equal(
        nd.Embedding(nd.array(idx), nd.array(w), input_dim=10, output_dim=4),
        w[idx.astype(int)])
    assert_almost_equal(nd.take(nd.array(w), nd.array(idx)),
                        w[idx.astype(int)])
    assert_almost_equal(
        nd.one_hot(nd.array(idx), depth=10),
        np.eye(10, dtype=np.float32)[idx.astype(int)])
    data = RS.rand(3, 5).astype(np.float32)
    picks = np.array([0, 2, 4], dtype=np.float32)
    assert_almost_equal(nd.pick(nd.array(data), nd.array(picks), axis=1),
                        data[np.arange(3), picks.astype(int)])


def test_ordering_ops():
    x = RS.rand(4, 6).astype(np.float32)
    topv = nd.topk(nd.array(x), k=3, ret_typ="value")
    ref = np.sort(x, axis=1)[:, ::-1][:, :3]
    assert_almost_equal(topv, ref)
    assert_almost_equal(nd.sort(nd.array(x)), np.sort(x, 1))
    assert_almost_equal(nd.argsort(nd.array(x)), np.argsort(x, 1))


def test_softmax_output_backward():
    """SoftmaxOutput backward = p - onehot(label), reference semantics."""
    x = RS.rand(4, 5).astype(np.float32)
    lab = np.array([0, 1, 2, 3], dtype=np.float32)
    ex = np.exp(x - x.max(1, keepdims=True))
    p = ex / ex.sum(1, keepdims=True)
    expected_grad = p.copy()
    expected_grad[np.arange(4), lab.astype(int)] -= 1.0
    data = sym.Variable("data")
    label = sym.Variable("label")
    out = sym.SoftmaxOutput(data, label)
    check_symbolic_forward(out, {"data": x, "label": lab}, [p], rtol=1e-5,
                           atol=1e-6)
    check_symbolic_backward(out, {"data": x, "label": lab}, None,
                            {"data": expected_grad}, rtol=1e-5, atol=1e-6)


def test_regression_outputs():
    x = RS.rand(4, 3).astype(np.float32)
    y = RS.rand(4, 3).astype(np.float32)
    data, label = sym.Variable("data"), sym.Variable("label")
    lin = sym.LinearRegressionOutput(data, label)
    check_symbolic_forward(lin, {"data": x, "label": y}, [x])
    check_symbolic_backward(lin, {"data": x, "label": y}, None,
                            {"data": (x - y) / 3.0}, rtol=1e-5, atol=1e-6)
    log = sym.LogisticRegressionOutput(data, label)
    s = 1 / (1 + np.exp(-x))
    check_symbolic_forward(log, {"data": x, "label": y}, [s], rtol=1e-5,
                           atol=1e-6)
    check_symbolic_backward(log, {"data": x, "label": y}, None,
                            {"data": (s - y) / 3.0}, rtol=1e-4, atol=1e-5)
    mae = sym.MAERegressionOutput(data, label)
    check_symbolic_backward(mae, {"data": x, "label": y}, None,
                            {"data": np.sign(x - y) / 3.0}, rtol=1e-5,
                            atol=1e-6)


def test_fc_gradient():
    data = sym.Variable("data")
    fc = sym.FullyConnected(data, num_hidden=4, name="fc")
    loss = sym.make_loss(sym.sum(fc * fc))
    check_numeric_gradient(
        fc, {"data": RS.rand(3, 5).astype(np.float32),
             "fc_weight": RS.rand(4, 5).astype(np.float32) * 0.1,
             "fc_bias": np.zeros(4, np.float32)},
        rtol=5e-2)


def test_conv_pool_gradient():
    data = sym.Variable("data")
    conv = sym.Convolution(data, kernel=(3, 3), num_filter=2, name="conv")
    pool = sym.Pooling(conv, kernel=(2, 2), stride=(2, 2), pool_type="avg")
    check_numeric_gradient(
        pool, {"data": RS.rand(2, 1, 6, 6).astype(np.float32),
               "conv_weight": RS.rand(2, 1, 3, 3).astype(np.float32) * 0.3,
               "conv_bias": np.zeros(2, np.float32)},
        rtol=7e-2)


def test_conv_stem_s2d_exact():
    """The space-to-depth stem rewrite (7x7/s2/p3, few channels ->
    s2d(2x2) + 4x4/s1) must reproduce the direct convolution exactly
    (ops/nn.py _stem_s2d_conv; MLPerf TPU stem transform), fwd and
    grads: it is what a Convolution of that shape lowers to."""
    import jax

    from mxnet_tpu.ops import nn as ops_nn

    rs = np.random.RandomState(0)
    x = rs.rand(2, 3, 32, 32).astype(np.float32)
    w = rs.rand(8, 3, 7, 7).astype(np.float32)

    data = sym.Variable("data")
    net = sym.Convolution(data, num_filter=8, kernel=(7, 7),
                          stride=(2, 2), pad=(3, 3), no_bias=True,
                          name="c0")
    ex = net.simple_bind(mx.cpu(), data=x.shape, grad_req="write")
    ex.arg_dict["data"][:] = x
    ex.arg_dict["c0_weight"][:] = w
    out_s2d = ex.forward(is_train=True)[0].asnumpy()
    ex.backward(nd.ones(out_s2d.shape))
    g_s2d = ex.grad_dict["c0_weight"].asnumpy()

    # the reference: the plain convolution on the same arrays
    direct = ops_nn._conv_f32acc((2, 2), ((3, 3), (3, 3)), (1, 1), (1, 1),
                                 ops_nn._CONV_DIMNUMS[2], 1)
    out_direct, vjp = jax.vjp(direct, x, w)
    g_direct = vjp(np.ones(out_direct.shape, np.float32))[1]
    assert out_s2d.shape == out_direct.shape == (2, 8, 16, 16)
    assert_almost_equal(out_s2d, np.asarray(out_direct), rtol=1e-4,
                        atol=1e-4)
    assert_almost_equal(g_s2d, np.asarray(g_direct), rtol=1e-3, atol=1e-3)


def test_activation_grads():
    for act in ["relu", "sigmoid", "tanh", "softrelu"]:
        data = sym.Variable("data")
        a = sym.Activation(data, act_type=act)
        x = (RS.rand(3, 4).astype(np.float32) - 0.5) * 2
        if act == "relu":
            x[np.abs(x) < 0.1] += 0.3  # avoid kink
        check_numeric_gradient(a, {"data": x}, rtol=5e-2)


def test_batchnorm_forward():
    x = RS.rand(4, 3, 5, 5).astype(np.float32)
    gamma = np.ones(3, np.float32)
    beta = np.zeros(3, np.float32)
    mean = x.mean(axis=(0, 2, 3))
    var = x.var(axis=(0, 2, 3))
    ref = (x - mean.reshape(1, 3, 1, 1)) / np.sqrt(
        var.reshape(1, 3, 1, 1) + 1e-3)
    d = sym.Variable("d")
    bn = sym.BatchNorm(d, name="bn")
    ex = bn.simple_bind(mx.cpu(), d=(4, 3, 5, 5))
    ex.arg_dict["d"][:] = x
    ex.arg_dict["bn_gamma"][:] = gamma
    ex.arg_dict["bn_beta"][:] = beta
    out = ex.forward(is_train=True)[0]
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-4)


def _bn_train_reference(x, gamma, beta, w, eps, fix_gamma, relu):
    """Train-mode BatchNorm (then ReLU) in float64, two passes over ``x``,
    and the gradients of ``sum(out * w)`` in closed form."""
    x, gamma, beta, w = (a.astype(np.float64) for a in (x, gamma, beta, w))
    red = (0,) + tuple(range(2, x.ndim))
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    m = x.size // x.shape[1]
    mean = x.mean(axis=red).reshape(bshape)
    var = ((x - mean) ** 2).mean(axis=red).reshape(bshape)
    inv = 1.0 / np.sqrt(var + eps)
    xhat = (x - mean) * inv
    g = np.ones_like(gamma) if fix_gamma else gamma
    y = xhat * g.reshape(bshape) + beta.reshape(bshape)
    dy = w * (y > 0) if relu else w
    dbeta = dy.sum(axis=red)
    dgamma = np.zeros_like(gamma) if fix_gamma \
        else (dy * xhat).sum(axis=red)
    dxhat = dy * g.reshape(bshape)
    dx = inv / m * (m * dxhat - dxhat.sum(axis=red).reshape(bshape)
                    - xhat * (dxhat * xhat).sum(axis=red).reshape(bshape))
    return (np.maximum(y, 0) if relu else y), dx, dgamma, dbeta, \
        mean.ravel(), var.ravel()


@pytest.mark.parametrize("shape,fix_gamma,relu", [
    ((8, 6, 5, 7), False, True),     # BN -> ReLU, odd spatial
    ((8, 16, 4, 4), True, True),     # fix_gamma (zero dgamma)
    ((8, 12), False, False),         # 2D input, plain BN
    ((4, 8, 3, 2, 2), False, True),  # 5D (3D-conv style)
])
def test_batchnorm_train_matches_float64_reference(shape, fix_gamma, relu):
    """Train-mode BatchNorm through the executor, as a graph trains it:
    output, the three gradients and the moving statistics against a
    two-pass float64 reference.  The inputs sit at mean 2.5 with a spread
    under 1, where a one-pass ``E[x^2] - E[x]^2`` in float32 would lose
    digits the shifted sums keep."""
    rs = np.random.RandomState(0)
    x = (rs.rand(*shape) * 3 + 1).astype(np.float32)
    gamma = rs.normal(1, 0.5, shape[1]).astype(np.float32)
    beta = rs.normal(0, 0.5, shape[1]).astype(np.float32)
    w = rs.normal(0, 1, shape).astype(np.float32)
    eps, momentum = 1e-3, 0.9

    h = sym.BatchNorm(sym.Variable("data"), fix_gamma=fix_gamma, eps=eps,
                      momentum=momentum, name="bn")
    if relu:
        h = sym.Activation(h, act_type="relu")
    ex = h.simple_bind(mx.cpu(), data=shape, grad_req="write")
    ex.arg_dict["data"][:] = x
    ex.arg_dict["bn_gamma"][:] = gamma
    ex.arg_dict["bn_beta"][:] = beta
    ex.aux_dict["bn_moving_mean"][:] = 0
    ex.aux_dict["bn_moving_var"][:] = 1
    out = ex.forward(is_train=True)[0].asnumpy()
    ex.backward(nd.array(w))

    ref_out, dx, dgamma, dbeta, mean, var = _bn_train_reference(
        x, gamma, beta, w, eps, fix_gamma, relu)
    assert_almost_equal(out, ref_out, rtol=1e-4, atol=1e-5)
    assert_almost_equal(ex.grad_dict["data"], dx, rtol=1e-3, atol=1e-4)
    assert_almost_equal(ex.grad_dict["bn_gamma"], dgamma, rtol=1e-3,
                        atol=1e-4)
    assert_almost_equal(ex.grad_dict["bn_beta"], dbeta, rtol=1e-3,
                        atol=1e-4)
    assert_almost_equal(ex.aux_dict["bn_moving_mean"],
                        (1 - momentum) * mean, rtol=1e-4, atol=1e-6)
    assert_almost_equal(ex.aux_dict["bn_moving_var"],
                        momentum + (1 - momentum) * var, rtol=1e-4,
                        atol=1e-6)


def test_concat_slicechannel():
    a = RS.rand(2, 3, 4).astype(np.float32)
    b = RS.rand(2, 5, 4).astype(np.float32)
    assert_almost_equal(nd.Concat(nd.array(a), nd.array(b), dim=1),
                        np.concatenate([a, b], 1))
    x = RS.rand(2, 6, 4).astype(np.float32)
    parts = nd.SliceChannel(nd.array(x), num_outputs=3, axis=1)
    for i, p in enumerate(parts):
        assert_almost_equal(p, x[:, 2 * i:2 * i + 2])


def test_dropout():
    mx.random.seed(0)
    x = np.ones((200, 200), np.float32)
    out = nd.Dropout(nd.array(x), p=0.5).asnumpy()
    frac = (out == 0).mean()
    assert 0.4 < frac < 0.6
    kept = out[out != 0]
    assert np.allclose(kept, 2.0)


def test_where_op():
    cond = np.array([[1, 0], [0, 1]], dtype=np.float32)
    x = np.ones((2, 2), np.float32)
    y = np.zeros((2, 2), np.float32)
    assert_almost_equal(nd.where(nd.array(cond), nd.array(x), nd.array(y)),
                        np.where(cond != 0, x, y))


def test_optimizer_kernels():
    w = np.ones((4,), np.float32)
    g = np.full((4,), 2.0, np.float32)
    out = nd.sgd_update(nd.array(w), nd.array(g), lr=0.1)
    assert_almost_equal(out, w - 0.1 * 2.0)
    mom = np.zeros_like(w)
    new_w, new_m = nd.sgd_mom_update(nd.array(w), nd.array(g), nd.array(mom),
                                     lr=0.1, momentum=0.9)
    assert_almost_equal(new_m, -0.1 * 2.0 * np.ones(4))
    assert_almost_equal(new_w, w - 0.2)


def test_embedding_gradient():
    data = sym.Variable("data")
    w = sym.Variable("w")
    emb = sym.Embedding(data, w, input_dim=6, output_dim=3)
    x = np.array([0, 2, 2, 5], dtype=np.float32)
    wv = RS.rand(6, 3).astype(np.float32)
    grads = check_symbolic_backward(
        emb, {"data": x, "w": wv},
        [np.ones((4, 3), np.float32)],
        {"w": np.array([[1, 1, 1], [0, 0, 0], [2, 2, 2], [0, 0, 0],
                        [0, 0, 0], [1, 1, 1]], np.float32)},
        rtol=1e-5)


def test_block_grad():
    data = sym.Variable("data")
    blocked = sym.BlockGrad(data * 2.0)
    out = blocked * 3.0
    x = RS.rand(2, 2).astype(np.float32)
    check_symbolic_backward(out, {"data": x}, [np.ones((2, 2), np.float32)],
                            {"data": np.zeros((2, 2), np.float32)})


def test_legacy_ndarray_funs():
    """census ops from ``src/ndarray/ndarray.cc:748-867`` + slice assign."""
    a = nd.array(np.arange(24, dtype=np.float32).reshape(4, 6))
    r = nd._slice_assign(a, nd.zeros((2, 3)), begin=(1, 1), end=(3, 4))
    out = r.asnumpy()
    assert out[1:3, 1:4].sum() == 0 and out[0].sum() > 0
    r = nd._crop_assign_scalar(a, begin=(0, 0), end=(2, 2), scalar=7)
    assert (r.asnumpy()[:2, :2] == 7).all()
    assert (nd._set_value(a, src=3.5).asnumpy() == 3.5).all()
    oh = nd._onehot_encode(nd.array(np.array([1.0, 0.0, 2.0])),
                           nd.zeros((3, 4)))
    assert oh.asnumpy().argmax(1).tolist() == [1, 0, 2]
    assert nd._broadcast(nd.ones((1, 3)), shape=(5, 3)).shape == (5, 3)
    assert_almost_equal(nd._copyto(a), a.asnumpy())


def test_convolution_v1_alias():
    s = sym.Convolution_v1(sym.Variable("data"), num_filter=2, kernel=(3, 3))
    ex = s.simple_bind(mx.cpu(), data=(1, 1, 8, 8))
    ex.forward(is_train=False)
    assert ex.outputs[0].shape == (1, 2, 6, 6)


def test_ctc_loss():
    """WarpCTC plugin analog (plugin/warpctc/warpctc-inl.h)."""
    S, B, A, L = 8, 2, 5, 3
    lab = np.array([[1, 2, 3], [2, 4, 0]], np.float32)
    loss = nd.ctc_loss(nd.array(np.zeros((S, B, A), np.float32)),
                       nd.array(lab)).asnumpy()
    assert loss.shape == (B,) and (loss > 0).all()
    # a sharp correct path scores much better than uniform logits
    logits = np.full((S, B, A), -10.0, np.float32)
    path = [1, 0, 2, 0, 3, 0, 0, 0]
    for t, c in enumerate(path):
        logits[t, 0, c] = 10.0
    sharp = nd.ctc_loss(nd.array(logits), nd.array(lab)).asnumpy()
    assert sharp[0] < loss[0]
    # gradient flows and is finite
    d, l = sym.Variable("data"), sym.Variable("label")
    s = sym.make_loss(sym.sum(sym.CTCLoss(d, l)))
    ex = s.simple_bind(mx.cpu(), data=(S, B, A), label=(B, L))
    ex.arg_dict["data"][:] = RS.rand(S, B, A).astype(np.float32)
    ex.arg_dict["label"][:] = lab
    ex.forward(is_train=True)
    ex.backward()
    g = ex.grad_dict["data"].asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_softmax_output_multi_output_grad():
    """multi_output: data (n,k,x...), label (n,x...) or flattened (n,prod) —
    gradient is softmax - onehot laid out over axis 1 (softmax_output-inl.h)."""
    B, C, H, W = 2, 3, 4, 4
    rs = np.random.RandomState(3)
    dval = rs.rand(B, C, H, W).astype(np.float32)
    lval = rs.randint(0, C, (B, H * W)).astype(np.float32)
    d, l = sym.Variable("data"), sym.Variable("label")
    s = sym.SoftmaxOutput(d, l, multi_output=True)
    ex = s.simple_bind(mx.cpu(), data=(B, C, H, W), label=(B, H * W))
    ex.arg_dict["data"][:] = dval
    ex.arg_dict["label"][:] = lval
    out = ex.forward(is_train=True)[0].asnumpy()
    ex.backward()
    g = ex.grad_dict["data"].asnumpy()
    # numpy reference
    e = np.exp(dval - dval.max(1, keepdims=True))
    p = e / e.sum(1, keepdims=True)
    onehot = np.zeros_like(p)
    lab = lval.reshape(B, H, W).astype(int)
    for b in range(B):
        for i in range(H):
            for j in range(W):
                onehot[b, lab[b, i, j], i, j] = 1.0
    assert_almost_equal(out, p, rtol=1e-5, atol=1e-6)
    assert_almost_equal(g, p - onehot, rtol=1e-5, atol=1e-6)
