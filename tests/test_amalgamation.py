"""Amalgamation (reference ``amalgamation/``): single-file numpy-only
deploys must match the framework's own inference."""

import os
import subprocess
import sys

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train_tiny(tmp_path, net, data_shape, nclass):
    rs = np.random.RandomState(0)
    x = rs.rand(64, *data_shape).astype(np.float32)
    y = rs.randint(0, nclass, 64).astype(np.float32)
    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(x, y, batch_size=16, label_name="softmax_label")
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier())
    prefix = str(tmp_path / "model")
    mod.save_checkpoint(prefix, 1)
    return prefix, mod


def test_amalgamated_lenet_matches_framework(tmp_path):
    from mxnet_tpu.models import lenet

    net = lenet.get_symbol(num_classes=10)
    prefix, mod = _train_tiny(tmp_path, net, (1, 28, 28), 10)

    sys.path.insert(0, os.path.join(REPO, "amalgamation"))
    try:
        from amalgamation import amalgamate
    finally:
        sys.path.pop(0)
    out_py = str(tmp_path / "deploy.py")
    amalgamate(prefix, 1, out_py, example_shape=(2, 1, 28, 28))

    x = np.random.RandomState(1).rand(2, 1, 28, 28).astype(np.float32)
    np.save(str(tmp_path / "x.npy"), x)
    # run the generated file in a clean interpreter with only numpy
    script = ("import numpy as np, runpy, sys; "
              "m = runpy.run_path(%r); "
              "np.save(%r, m['predict'](np.load(%r)))"
              % (out_py, str(tmp_path / "out.npy"),
                 str(tmp_path / "x.npy")))
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH",)}
    subprocess.run([sys.executable, "-c", script], check=True, env=env,
                   cwd=str(tmp_path), timeout=300)
    got = np.load(str(tmp_path / "out.npy"))

    # framework reference forward
    ex = net.simple_bind(mx.cpu(), data=(2, 1, 28, 28),
                         softmax_label=(2,), grad_req="null")
    arg_params, aux_params = mod.get_params()
    for n, v in arg_params.items():
        ex.arg_dict[n][:] = v
    for n, v in aux_params.items():
        ex.aux_dict[n][:] = v
    ex.arg_dict["data"][:] = x
    ref = ex.forward(is_train=False)[0].asnumpy()
    assert_almost_equal(got, ref, rtol=1e-4, atol=1e-5)


def test_predictor_op_coverage_resnet(tmp_path):
    """The minimal runtime interprets a ResNet-18 graph (BN/add/pool mix)."""
    from mxnet_tpu.models import resnet

    sys.path.insert(0, os.path.join(REPO, "amalgamation"))
    try:
        from mxnet_predict import Predictor
    finally:
        sys.path.pop(0)

    net = resnet.get_symbol(num_classes=10, num_layers=18,
                            image_shape=(3, 32, 32))
    ex = net.simple_bind(mx.cpu(), data=(2, 3, 32, 32),
                         softmax_label=(2,), grad_req="null")
    rs = np.random.RandomState(0)
    for n, a in ex.arg_dict.items():
        if n not in ("data", "softmax_label"):
            a[:] = rs.normal(0, 0.05, a.shape).astype(np.float32)
    for n, a in ex.aux_dict.items():
        a[:] = (np.zeros(a.shape, np.float32) if "mean" in n
                else np.ones(a.shape, np.float32))
    x = rs.rand(2, 3, 32, 32).astype(np.float32)
    ex.arg_dict["data"][:] = x
    ref = ex.forward(is_train=False)[0].asnumpy()

    params = {n: a.asnumpy() for n, a in ex.arg_dict.items()
              if n not in ("data", "softmax_label")}
    params.update({n: a.asnumpy() for n, a in ex.aux_dict.items()})
    pred = Predictor(net.tojson(), params)
    got = pred.forward(data=x)[0]
    assert_almost_equal(got, ref, rtol=1e-4, atol=1e-5)


def test_predictor_pooling_and_leakyrelu_parity():
    """Interpreter matches the framework on default-stride pooling,
    pooling_convention='full', and every LeakyReLU act_type."""
    sys.path.insert(0, os.path.join(REPO, "amalgamation"))
    try:
        from mxnet_predict import Predictor
    finally:
        sys.path.pop(0)
    rs = np.random.RandomState(0)

    def parity(net, feeds, params=None):
        shapes = {k: v.shape for k, v in feeds.items()}
        ex = net.simple_bind(mx.cpu(), grad_req="null", **shapes)
        for k, v in feeds.items():
            ex.arg_dict[k][:] = v
        for k, v in (params or {}).items():
            ex.arg_dict[k][:] = v
        ref = ex.forward(is_train=False)[0].asnumpy()
        got = Predictor(net.tojson(), params or {}).forward(**feeds)[0]
        assert_almost_equal(got, ref, rtol=1e-5, atol=1e-6)

    x = rs.rand(2, 3, 7, 7).astype(np.float32)
    d = mx.sym.Variable("data")
    # stride omitted -> framework default stride 1
    parity(mx.sym.Pooling(d, kernel=(3, 3), pool_type="max"), {"data": x})
    # ceil ('full') convention, avg with padding
    parity(mx.sym.Pooling(d, kernel=(2, 2), stride=(2, 2), pad=(0, 0),
                          pool_type="avg", pooling_convention="full"),
           {"data": x})
    parity(mx.sym.Pooling(d, kernel=(3, 3), stride=(2, 2),
                          pool_type="sum"), {"data": x})
    for act in ("leaky", "elu", "rrelu"):
        parity(mx.sym.LeakyReLU(d, act_type=act, slope=0.3),
           {"data": x.astype(np.float32) - 0.5})
    gamma = rs.rand(3).astype(np.float32)
    parity(mx.sym.LeakyReLU(d, act_type="prelu", name="pr"),
           {"data": x - 0.5}, params={"pr_gamma": gamma})


def test_predictor_legacy_reference_json():
    """0.9.x reference JSON (op params under 'param', implicit BN aux)
    deploys through the numpy-only predictor unchanged."""
    import json as _json

    sys.path.insert(0, os.path.join(REPO, "amalgamation"))
    try:
        from mxnet_predict import Predictor
    finally:
        sys.path.pop(0)
    legacy = {
        "nodes": [
            {"op": "null", "param": {}, "name": "data", "inputs": [],
             "backward_source_id": -1},
            {"op": "null", "param": {}, "name": "fc_weight", "inputs": [],
             "backward_source_id": -1},
            {"op": "null", "param": {}, "name": "fc_bias", "inputs": [],
             "backward_source_id": -1},
            {"op": "FullyConnected",
             "param": {"no_bias": "False", "num_hidden": "6"},
             "name": "fc", "inputs": [[0, 0], [1, 0], [2, 0]],
             "backward_source_id": -1},
            {"op": "null", "param": {}, "name": "bn_gamma", "inputs": [],
             "backward_source_id": -1},
            {"op": "null", "param": {}, "name": "bn_beta", "inputs": [],
             "backward_source_id": -1},
            {"op": "BatchNorm",
             "param": {"eps": "0.001", "fix_gamma": "False",
                       "momentum": "0.9", "use_global_stats": "False"},
             "name": "bn", "inputs": [[3, 0], [4, 0], [5, 0]],
             "backward_source_id": -1},
            {"op": "null", "param": {}, "name": "softmax_label",
             "inputs": [], "backward_source_id": -1},
            {"op": "SoftmaxOutput", "param": {"grad_scale": "1"},
             "name": "softmax", "inputs": [[6, 0], [7, 0]],
             "backward_source_id": -1},
        ],
        "arg_nodes": [0, 1, 2, 4, 5, 7],
        "heads": [[8, 0]],
    }
    js = _json.dumps(legacy)
    net = mx.sym.load_json(js)
    ex = net.simple_bind(mx.cpu(), data=(3, 4), softmax_label=(3,))
    rs = np.random.RandomState(0)
    for n, a in ex.arg_dict.items():
        a[:] = rs.rand(*a.shape).astype(np.float32)
    for n, a in ex.aux_dict.items():
        a[:] = (np.zeros(a.shape, np.float32) if "mean" in n
                else np.ones(a.shape, np.float32))
    x = rs.rand(3, 4).astype(np.float32)
    ex.arg_dict["data"][:] = x
    ref = ex.forward(is_train=False)[0].asnumpy()
    params = {n: a.asnumpy() for n, a in ex.arg_dict.items()
              if n not in ("data", "softmax_label")}
    params.update({n: a.asnumpy() for n, a in ex.aux_dict.items()})
    got = Predictor(js, params).forward(data=x)[0]
    assert_almost_equal(got, ref, rtol=1e-3, atol=1e-4)
