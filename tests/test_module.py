"""Module training (reference ``tests/python/unittest/test_module.py`` +
``tests/python/train/test_mlp.py`` convergence style)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io, nd, sym
from mxnet_tpu.test_utils import assert_almost_equal


def _toy_data(n=800, num_class=4, dim=10, seed=0):
    rs = np.random.RandomState(seed)
    centers = rs.rand(num_class, dim).astype(np.float32)
    labels = rs.randint(0, num_class, n)
    x = centers[labels] + 0.1 * rs.rand(n, dim).astype(np.float32)
    return x, labels.astype(np.float32)


def _mlp_sym(num_class=4):
    data = sym.Variable("data")
    fc1 = sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = sym.Activation(fc1, act_type="relu")
    fc2 = sym.FullyConnected(act, num_hidden=num_class, name="fc2")
    return sym.SoftmaxOutput(fc2, name="softmax")


def test_module_fit_converges():
    # the initializer and the iterator's shuffle draw from the global
    # generators: unseeded, whatever test ran before on this worker
    # decides the draw, and at this learning rate one in some tens sits
    # at chance (the driver's six workers deal the files anew each run)
    mx.random.seed(0)
    x, y = _toy_data()
    train = io.NDArrayIter(x[:600], y[:600], batch_size=32, shuffle=True)
    val = io.NDArrayIter(x[600:], y[600:], batch_size=32)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(train, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            initializer=mx.init.Xavier(), num_epoch=8)
    score = mod.score(val, "acc")
    assert score[0][1] > 0.95, "MLP did not converge: %s" % score


def test_module_forward_shapes_and_outputs():
    x, y = _toy_data(64)
    it = io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    batch = it.next()
    mod.forward(batch, is_train=False)
    outs = mod.get_outputs()
    assert len(outs) == 1 and outs[0].shape == (16, 4)
    assert mod.data_shapes == [("data", (16, 10))]
    assert mod.label_shapes == [("softmax_label", (16,))]
    assert mod.output_names == ["softmax_output"]


def test_module_checkpoint_roundtrip(tmp_path):
    x, y = _toy_data(128)
    it = io.NDArrayIter(x, y, batch_size=32)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(it, optimizer="sgd", num_epoch=1,
            optimizer_params={"learning_rate": 0.1})
    prefix = str(tmp_path / "toy")
    mod.save_checkpoint(prefix, 1, save_optimizer_states=True)
    mod2 = mx.mod.Module.load(prefix, 1)
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
              for_training=False)
    it.reset()
    b = it.next()
    mod.forward(b, is_train=False)
    o1 = mod.get_outputs()[0].asnumpy()
    mod2.forward(b, is_train=False)
    o2 = mod2.get_outputs()[0].asnumpy()
    assert np.allclose(o1, o2, rtol=1e-5)


def test_module_predict_and_score():
    x, y = _toy_data(96)
    it = io.NDArrayIter(x, y, batch_size=32)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    preds = mod.predict(it)
    assert preds.shape == (96, 4)
    res = mod.score(it, "acc")
    assert 0.0 <= res[0][1] <= 1.0


def test_module_input_grads():
    x, y = _toy_data(32)
    it = io.NDArrayIter(x, y, batch_size=32)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True, inputs_need_grad=True)
    mod.init_params()
    batch = it.next()
    mod.forward(batch, is_train=True)
    mod.backward()
    ig = mod.get_input_grads()
    assert ig[0] is not None and ig[0].shape == (32, 10)
    assert float(np.abs(ig[0].asnumpy()).sum()) > 0


def test_fixed_params():
    x, y = _toy_data(64)
    it = io.NDArrayIter(x, y, batch_size=32)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu(),
                        fixed_param_names=["fc1_weight"])
    mod.fit(it, optimizer="sgd", num_epoch=1,
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Xavier())
    # fixed param has no grad array
    assert mod._exec.grad_dict.get("fc1_weight") is None


def test_feedforward_api():
    x, y = _toy_data(128)
    model = mx.model.FeedForward(_mlp_sym(), ctx=mx.cpu(), num_epoch=2,
                                 learning_rate=0.5, numpy_batch_size=32)
    model.fit(x, y)
    preds = model.predict(x)
    assert preds.shape == (128, 4)


def test_fused_full_step_matches_two_phase():
    """MXNET_FUSE_TRAIN_STEP=1 runs fwd+bwd+update as one XLA dispatch;
    the resulting params must match the two-phase path bit-for-bit-ish."""
    import os

    rs = np.random.RandomState(0)
    x = rs.rand(32, 8).astype(np.float32)
    y = rs.randint(0, 3, 32).astype(np.float32)

    def run(fused):
        os.environ["MXNET_FUSE_TRAIN_STEP"] = "1" if fused else "0"
        try:
            data = mx.sym.Variable("data")
            h = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
            h = mx.sym.Activation(h, act_type="relu")
            h = mx.sym.FullyConnected(h, num_hidden=3, name="fc2")
            net = mx.sym.SoftmaxOutput(h, name="softmax")
            mod = mx.mod.Module(net, context=mx.cpu())
            mod.bind(data_shapes=[("data", (32, 8))],
                     label_shapes=[("softmax_label", (32,))])
            mod.init_params(mx.init.Zero())
            irs = np.random.RandomState(7)
            mod.set_params({n: mx.nd.array(
                irs.normal(0, 0.1, a.shape).astype(np.float32))
                for n, a in mod.get_params()[0].items()}, {})
            mod.init_optimizer(optimizer="sgd",
                               optimizer_params={"learning_rate": 0.1,
                                                 "momentum": 0.9,
                                                 "wd": 1e-3})
            batch = mx.io.DataBatch(data=[mx.nd.array(x)],
                                    label=[mx.nd.array(y)])
            for _ in range(3):
                mod.forward_backward(batch)
                mod.update()
            out = mod.get_outputs()[0].asnumpy()
            params = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
            return out, params
        finally:
            os.environ.pop("MXNET_FUSE_TRAIN_STEP", None)

    out_f, p_f = run(True)
    out_n, p_n = run(False)
    assert_almost_equal(out_f, out_n, rtol=1e-5, atol=1e-6)
    for k in p_n:
        assert_almost_equal(p_f[k], p_n[k], rtol=1e-5, atol=1e-6)


def test_fused_full_step_observed_before_update():
    """get_outputs() between a staged forward_backward and update() must
    fall back to the exact two-phase path (outputs available, update OK)."""
    import os

    os.environ["MXNET_FUSE_TRAIN_STEP"] = "1"
    try:
        rs = np.random.RandomState(1)
        data = mx.sym.Variable("data")
        net = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(data, num_hidden=3, name="fc"),
            name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=[("data", (4, 5))],
                 label_shapes=[("softmax_label", (4,))])
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        batch = mx.io.DataBatch(
            data=[mx.nd.array(rs.rand(4, 5).astype(np.float32))],
            label=[mx.nd.array(np.array([0, 1, 2, 0], np.float32))])
        mod.forward_backward(batch)
        out = mod.get_outputs()[0].asnumpy()   # observe BEFORE update
        assert out.shape == (4, 3)
        before = mod.get_params()[0]["fc_weight"].asnumpy().copy()
        mod.update()
        after = mod.get_params()[0]["fc_weight"].asnumpy()
        assert np.abs(after - before).sum() > 0
    finally:
        os.environ.pop("MXNET_FUSE_TRAIN_STEP", None)


def test_python_loss_module_chain():
    """SequentialModule: Symbol feature module + PythonLossModule loss head
    (reference module/python_module.py): train a tiny softmax classifier
    where the loss gradient comes from a python callback."""
    rs = np.random.RandomState(0)
    n, d, k = 64, 8, 3
    w = rs.randn(d, k)
    x = rs.randn(n, d).astype(np.float32)
    y = np.argmax(x @ w + 0.1 * rs.randn(n, k), axis=1).astype(np.float32)

    data = mx.sym.Variable("data")
    feat = mx.sym.FullyConnected(data, num_hidden=k, name="fc")

    def ce_grad(scores, labels):
        s = scores.asnumpy()
        lab = labels.asnumpy().astype(int)
        p = np.exp(s - s.max(axis=1, keepdims=True))
        p /= p.sum(axis=1, keepdims=True)
        p[np.arange(len(lab)), lab] -= 1.0
        return p / len(lab)

    seq = mx.mod.SequentialModule()
    seq.add(mx.mod.Module(feat, data_names=("data",), label_names=()))
    seq.add(mx.mod.PythonLossModule(grad_func=ce_grad),
            take_labels=True, auto_wiring=True)

    it = mx.io.NDArrayIter(data=x, label=y, batch_size=16, shuffle=False,
                           label_name="softmax_label")
    metric = mx.metric.Accuracy()
    seq.fit(it, eval_metric=metric, num_epoch=30,
            optimizer="sgd", optimizer_params={"learning_rate": 2.0},
            initializer=mx.init.Xavier())
    _, acc = metric.get()
    assert acc > 0.9, acc


def test_python_module_root_namespace():
    """Reference-parity namespace probes: mx.viz, mx.image, mx.recordio,
    mx.mod.PythonModule/PythonLossModule all reachable from the root."""
    assert mx.viz is mx.visualization
    assert hasattr(mx.viz, "plot_network")
    assert hasattr(mx.image, "imdecode")
    assert hasattr(mx.recordio, "unpack_img")
    assert issubclass(mx.mod.PythonLossModule, mx.mod.PythonModule)


def test_feedforward_predict_then_fit_keeps_labels():
    """predict() at a different batch size must not clobber the module's
    label shapes — a later fit() would silently train on zero labels."""
    mx.random.seed(42)
    x, y = _toy_data(200)
    model = mx.model.FeedForward(_mlp_sym(), ctx=mx.cpu(), num_epoch=8,
                                 initializer=mx.init.Xavier(),
                                 learning_rate=0.1, momentum=0.9,
                                 numpy_batch_size=20)
    model.fit(x, y)
    preds = model.predict(x[:10])  # smaller batch -> reshape path
    assert preds.shape == (10, 4)
    mod = model._get_module()
    assert mod.label_shapes and mod.label_shapes[0][1][0] == 10
    # training again still learns (labels still flow)
    model.fit(x, y)
    acc = (np.argmax(np.asarray(model.predict(x)), axis=1) ==
           y.astype(int)).mean()
    assert acc > 0.9, acc


def test_feedforward_list_input_batch_clamp():
    """list-of-arrays input clamps batch on the SAMPLE count."""
    x, y = _toy_data(50)
    model = mx.model.FeedForward(_mlp_sym(), ctx=mx.cpu(), num_epoch=1,
                                 learning_rate=0.1, numpy_batch_size=128)
    model.fit([x], y)
    it = model._prepare_data([x])
    assert it.batch_size == 50


def test_feedforward_predict_first_then_fit_learns():
    """predict() before any fit() binds for inference; fit() must rebind
    for training (not reshape) or gradients silently never flow."""
    mx.random.seed(42)
    x, y = _toy_data(200)
    model = mx.model.FeedForward(_mlp_sym(), ctx=mx.cpu(), num_epoch=8,
                                 initializer=mx.init.Xavier(),
                                 learning_rate=0.1, momentum=0.9,
                                 numpy_batch_size=20)
    model.predict(x[:10])  # inference-first bind
    model.fit(x, y)
    acc = (np.argmax(np.asarray(model.predict(x)), axis=1) ==
           y.astype(int)).mean()
    assert acc > 0.9, acc


def test_run_bulk_matches_sequential():
    """run_bulk (K steps in one scanned dispatch) must produce the same
    params/aux as K sequential fused steps."""
    import os

    rs = np.random.RandomState(0)
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(16, 8).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 3, 16).astype(np.float32))])
        for _ in range(4)]

    def build():
        data = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
        h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.BatchNorm(h, name="bn")
        h = mx.sym.FullyConnected(h, num_hidden=3, name="fc2")
        net = mx.sym.SoftmaxOutput(h, name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=[("data", (16, 8))],
                 label_shapes=[("softmax_label", (16,))])
        mod.init_params(mx.init.Zero())
        irs = np.random.RandomState(5)
        mod.set_params({n: mx.nd.array(
            irs.normal(0, 0.1, a.shape).astype(np.float32))
            for n, a in mod.get_params()[0].items()},
            {n: a for n, a in mod.get_params()[1].items()})
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9, "wd": 1e-3})
        return mod

    os.environ["MXNET_FUSE_TRAIN_STEP"] = "1"
    try:
        seq = build()
        for b in batches:
            seq.forward_backward(b)
            seq.update()
        out_seq = seq.get_outputs()[0].asnumpy()
        blk = build()
        # return_outputs=True: the default no-collect path leaves
        # get_outputs() stale by contract (no K-step output stack)
        blk.run_bulk(batches, return_outputs=True)
        out_blk = blk.get_outputs()[0].asnumpy()
    finally:
        os.environ.pop("MXNET_FUSE_TRAIN_STEP", None)
    assert_almost_equal(out_blk, out_seq, rtol=1e-5, atol=1e-6)
    ps, pb = seq.get_params(), blk.get_params()
    for k in ps[0]:
        assert_almost_equal(pb[0][k].asnumpy(), ps[0][k].asnumpy(),
                            rtol=1e-5, atol=1e-6)
    for k in ps[1]:
        assert_almost_equal(pb[1][k].asnumpy(), ps[1][k].asnumpy(),
                            rtol=1e-5, atol=1e-6)


def test_run_bulk_fallback_without_fuse_flag():
    """Without MXNET_FUSE_TRAIN_STEP, run_bulk falls back to the exact
    per-batch path (and still trains)."""
    rs = np.random.RandomState(1)
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(8, 4).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 2, 8).astype(np.float32))])
        for _ in range(2)]
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=2, name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 4))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    w0 = mod.get_params()[0]["fc_weight"].asnumpy().copy()
    mod.run_bulk(batches)
    w1 = mod.get_params()[0]["fc_weight"].asnumpy()
    assert not np.allclose(w0, w1)
    assert mod.get_outputs()[0].shape == (8, 2)


def test_predict_bulk_matches_forward():
    """predict_bulk (K scanned forwards) == per-batch forward outputs."""
    rs = np.random.RandomState(2)
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=3, name="fc"),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))], for_training=False)
    mod.init_params(mx.init.Xavier())
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(4, 6).astype(np.float32))],
        label=[mx.nd.zeros((4,))]) for _ in range(3)]
    bulk = mod.predict_bulk(batches)
    for b, outs in zip(batches, bulk):
        mod.forward(b, is_train=False)
        ref = mod.get_outputs()[0].asnumpy()
        assert_almost_equal(outs[0].asnumpy(), ref, rtol=1e-5, atol=1e-6)


def test_fit_with_bulk_train_steps_matches_classic():
    """MXNET_BULK_TRAIN_STEPS=K: fit() trains through run_bulk with
    per-batch metric updates; final params and the train metric must
    match the classic per-batch loop."""
    import os

    x, y = _toy_data(192)

    def run(bulk):
        os.environ["MXNET_FUSE_TRAIN_STEP"] = "1"
        if bulk:
            os.environ["MXNET_BULK_TRAIN_STEPS"] = "4"
        try:
            mx.random.seed(0)
            np.random.seed(0)
            train = io.NDArrayIter(x, y, batch_size=16)
            mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
            accs = []
            mod.fit(train, optimizer="sgd",
                    optimizer_params={"learning_rate": 0.2,
                                      "momentum": 0.9},
                    initializer=mx.init.Xavier(), num_epoch=3,
                    batch_end_callback=lambda p: accs.append(
                        p.eval_metric.get()[1]))
            return ({k: v.asnumpy() for k, v in mod.get_params()[0].items()},
                    accs)
        finally:
            os.environ.pop("MXNET_FUSE_TRAIN_STEP", None)
            os.environ.pop("MXNET_BULK_TRAIN_STEPS", None)

    p_classic, acc_classic = run(False)
    p_bulk, acc_bulk = run(True)
    assert len(acc_bulk) == len(acc_classic) > 0
    for k in p_classic:
        assert_almost_equal(p_bulk[k], p_classic[k], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(acc_bulk, acc_classic, rtol=1e-6)


def test_bulk_cost_analysis_measures_step_flops():
    """bulk_cost_analysis returns the XLA-measured FLOPs of ONE training
    step (the scan body is counted once), close to the analytic count —
    the benchmark's MFU must rest on this, not a hand-derived constant."""
    import os

    rs = np.random.RandomState(0)
    B, D, H = 16, 8, 32
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(B, D).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 3, B).astype(np.float32))])
        for _ in range(3)]
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=H, name="fc1")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=3, name="fc2"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (B, D))],
             label_shapes=[("softmax_label", (B,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    assert mod.bulk_cost_analysis() is None  # no bulk signature yet
    os.environ["MXNET_FUSE_TRAIN_STEP"] = "1"
    try:
        mod.run_bulk(batches)
    finally:
        os.environ.pop("MXNET_FUSE_TRAIN_STEP", None)
    cost = mod.bulk_cost_analysis()
    assert cost is not None and cost.get("flops", 0) > 0
    # analytic: fc1 fwd+dgrad+wgrad 3*2*B*D*H + fc2 3*2*B*H*3 (2 flops/MAC)
    analytic = 3 * 2 * B * D * H + 3 * 2 * B * H * 3
    # one step only (scan body once), within 3x for elementwise overhead
    assert analytic * 0.5 < cost["flops"] < analytic * 3, \
        (cost["flops"], analytic)
