"""Fused Pallas LSTM (ops/rnn_pallas.py) parity on CPU (interpret mode).

The kernel is OFF by default (measured at parity, not faster, on v5e —
docs/how_to/perf.md round-4 negative); these tests pin that turning it
ON cannot change numerics: the RNN op's outputs AND parameter gradients
match the scan path exactly, through the public symbol API.
"""

import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym
from mxnet_tpu.test_utils import assert_almost_equal


def _run_rnn(flag, seq=7, batch=4, nin=6, nh=8):
    os.environ["MXNET_RNN_PALLAS"] = flag
    try:
        rs = np.random.RandomState(3)
        from mxnet_tpu.ops.rnn import rnn_param_size

        psize = rnn_param_size(nin, nh, 2, "lstm", False)
        net = sym.RNN(sym.Variable("x"), sym.Variable("p"),
                      sym.Variable("hs"), sym.Variable("cs"),
                      state_size=nh, num_layers=2, mode="lstm",
                      state_outputs=True, name="rnn")
        ex = net.simple_bind(mx.cpu(), x=(seq, batch, nin),
                             p=(psize,), hs=(2, batch, nh),
                             cs=(2, batch, nh), grad_req="write")
        ex.arg_dict["x"][:] = rs.randn(seq, batch, nin) * 0.5
        ex.arg_dict["p"][:] = rs.randn(psize) * 0.2
        ex.arg_dict["hs"][:] = rs.randn(2, batch, nh) * 0.1
        ex.arg_dict["cs"][:] = rs.randn(2, batch, nh) * 0.1
        outs = [o.asnumpy() for o in ex.forward(is_train=True)]
        ex.backward([mx.nd.ones(o.shape) for o in ex.outputs])
        grads = {k: g.asnumpy() for k, g in ex.grad_dict.items()
                 if g is not None}
        return outs, grads
    finally:
        os.environ.pop("MXNET_RNN_PALLAS", None)


def test_fused_lstm_kernel_matches_scan_path():
    outs_ref, grads_ref = _run_rnn("0")
    outs_k, grads_k = _run_rnn("interpret")
    assert len(outs_k) == len(outs_ref) == 3  # y, h, c (state_outputs)
    for a, b in zip(outs_k, outs_ref):
        assert_almost_equal(a, b, rtol=1e-5, atol=1e-5)
    assert set(grads_k) == set(grads_ref)
    for k in grads_ref:
        assert_almost_equal(grads_k[k], grads_ref[k], rtol=1e-4,
                            atol=1e-4)


def test_fused_lstm_vmem_guard():
    from mxnet_tpu.ops import rnn_pallas
    import jax.numpy as jnp

    assert rnn_pallas.fits(35, 32, 200, jnp.float32)
    assert not rnn_pallas.fits(2048, 128, 1024, jnp.float32)
    assert not rnn_pallas.fits(35, 32, 200, jnp.bfloat16)
