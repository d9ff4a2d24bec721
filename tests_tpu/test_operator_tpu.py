"""CPU-vs-TPU parity for the core op/layer set.

Reference: ``tests/python/gpu/test_operator_gpu.py`` — reuses the CPU op
checks through ``check_consistency`` across ``[mx.cpu(), mx.gpu()]``;
here the context pair is ``[mx.cpu(), mx.tpu()]``. Tolerances allow the
TPU's default-bf16 matmul/conv passes.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import check_consistency

RTOL, ATOL = 2e-2, 2e-2


def _ctx_list(**shapes):
    return [dict(ctx=mx.cpu(), **shapes), dict(ctx=mx.tpu(), **shapes)]


def test_fullyconnected_parity():
    sym = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                name="fc")
    check_consistency(sym, _ctx_list(data=(4, 10)), rtol=RTOL, atol=ATOL)


def test_convolution_parity():
    sym = mx.sym.Convolution(mx.sym.Variable("data"), num_filter=4,
                             kernel=(3, 3), pad=(1, 1), name="conv")
    # scale inputs down: bf16 conv error is relative to magnitude, and
    # 3x3x3 accumulations at unit scale exceed a fixed atol
    check_consistency(sym, _ctx_list(data=(2, 3, 8, 8)), scale=0.3,
                      rtol=RTOL, atol=ATOL)


def test_batchnorm_relu_pool_parity():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3), pad=(1, 1))
    net = mx.sym.BatchNorm(net, fix_gamma=False)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    check_consistency(net, _ctx_list(data=(2, 3, 8, 8)), scale=0.3,
                      rtol=RTOL, atol=ATOL)


def test_softmax_output_parity():
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=5), name="softmax")
    check_consistency(net, _ctx_list(data=(6, 12),
                                     softmax_label=(6,)),
                      rtol=RTOL, atol=ATOL)


def test_elemwise_broadcast_reduce_parity():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    net = mx.sym.broadcast_add(a * 2.0, b)
    net = mx.sym.sum(net, axis=1)
    check_consistency(net, _ctx_list(a=(3, 4), b=(1, 4)), rtol=RTOL,
                      atol=ATOL)


def test_rnn_cell_parity():
    data = mx.sym.Variable("data")
    cell = mx.rnn.LSTMCell(num_hidden=6, prefix="l_")
    outs, _ = cell.unroll(4, inputs=data, merge_outputs=True,
                          layout="NTC")
    check_consistency(outs, _ctx_list(data=(2, 4, 5)), rtol=RTOL,
                      atol=ATOL)


def test_imperative_ops_parity():
    rs = np.random.RandomState(0)
    x = rs.rand(4, 5).astype(np.float32)
    for op in ("exp", "sqrt", "sigmoid", "tanh"):
        c = getattr(mx.nd, op)(mx.nd.array(x, ctx=mx.cpu())).asnumpy()
        t = getattr(mx.nd, op)(mx.nd.array(x, ctx=mx.tpu())).asnumpy()
        np.testing.assert_allclose(c, t, rtol=1e-3, atol=1e-5)


def test_module_train_step_parity():
    """One fwd/bwd/update step yields near-identical params on both
    backends (nightly multi_lenet-style determinism check)."""
    rs = np.random.RandomState(3)
    x = rs.rand(8, 6).astype(np.float32)
    y = rs.randint(0, 3, 8).astype(np.float32)
    params = {}
    for ctx in (mx.cpu(), mx.tpu()):
        net = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                                  name="fc"), name="softmax")
        mod = mx.mod.Module(net, context=ctx)
        mod.bind(data_shapes=[("data", (8, 6))],
                 label_shapes=[("softmax_label", (8,))])
        irs = np.random.RandomState(7)
        mod.init_params(mx.init.Zero())
        mod.set_params({n: mx.nd.array(
            irs.normal(0, 0.1, a.shape).astype(np.float32))
            for n, a in mod.get_params()[0].items()}, {})
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        batch = mx.io.DataBatch(data=[mx.nd.array(x, ctx=ctx)],
                                label=[mx.nd.array(y, ctx=ctx)])
        mod.forward_backward(batch)
        mod.update()
        params[str(ctx)] = {k: v.asnumpy()
                            for k, v in mod.get_params()[0].items()}
    (ca, ta) = params.values()
    for k in ca:
        np.testing.assert_allclose(ca[k], ta[k], rtol=2e-2, atol=2e-3)


def test_run_bulk_parity_on_tpu():
    """run_bulk (scanned steps) must match sequential fused steps ON THE
    CHIP — guards the scan lowering against backend regressions."""
    import os

    rs = np.random.RandomState(0)
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(8, 6).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 3, 8).astype(np.float32))])
        for _ in range(3)]

    def build():
        net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            mx.sym.Variable("data"), num_hidden=3, name="fc"),
            name="softmax")
        mod = mx.mod.Module(net, context=mx.tpu())
        mod.bind(data_shapes=[("data", (8, 6))],
                 label_shapes=[("softmax_label", (8,))])
        mod.init_params(mx.init.Zero())
        irs = np.random.RandomState(5)
        mod.set_params({n: mx.nd.array(
            irs.normal(0, 0.1, a.shape).astype(np.float32))
            for n, a in mod.get_params()[0].items()}, {})
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        return mod

    os.environ["MXNET_FUSE_TRAIN_STEP"] = "1"
    try:
        seq = build()
        for b in batches:
            seq.forward_backward(b)
            seq.update()
        blk = build()
        blk.run_bulk(batches)
    finally:
        os.environ.pop("MXNET_FUSE_TRAIN_STEP", None)
    ps, pb = seq.get_params()[0], blk.get_params()[0]
    for k in ps:
        np.testing.assert_allclose(pb[k].asnumpy(), ps[k].asnumpy(),
                                   rtol=2e-3, atol=1e-4)


def test_flash_attention_pallas_on_chip():
    """FlashAttention op end-to-end on hardware at a small shape, forward
    through the Pallas kernel (a head of 32 lowers: the gate is what the
    compiler accepts, ops/attention._kernel_refusal) and backward through
    the blockwise scan, against the CPU and a dense reference."""
    rs = np.random.RandomState(0)
    b, h, l, d = 1, 2, 128, 32
    q = rs.normal(0, 1, (b, h, l, d)).astype(np.float32)
    k = rs.normal(0, 1, (b, h, l, d)).astype(np.float32)
    v = rs.normal(0, 1, (b, h, l, d)).astype(np.float32)

    def run(ctx):
        qs = mx.sym.Variable("q")
        ks = mx.sym.Variable("k")
        vs = mx.sym.Variable("v")
        net = mx.sym.FlashAttention(qs, ks, vs, causal=True)
        ex = net.bind(ctx, {"q": mx.nd.array(q, ctx=ctx),
                            "k": mx.nd.array(k, ctx=ctx),
                            "v": mx.nd.array(v, ctx=ctx)},
                      args_grad={n: mx.nd.zeros((b, h, l, d), ctx=ctx)
                                 for n in ("q", "k", "v")})
        out = ex.forward(is_train=True)[0].asnumpy()
        ex.backward()
        return out, {n: g.asnumpy() for n, g in ex.grad_dict.items()}

    out_c, g_c = run(mx.cpu())
    out_t, g_t = run(mx.tpu())
    # dense reference
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    mask = np.arange(l)[:, None] >= np.arange(l)[None, :]
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, v)
    np.testing.assert_allclose(out_t, ref, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(out_t, out_c, rtol=2e-2, atol=2e-2)
    for n in g_c:
        np.testing.assert_allclose(g_t[n], g_c[n], rtol=3e-2, atol=3e-2)


def test_optimizer_kernels_parity():
    """Fused optimizer update kernels (the reference's sgd_update/
    adam_update .cu kernels) produce the same results on TPU as CPU."""
    rs = np.random.RandomState(7)
    w = rs.randn(64, 32).astype(np.float32)
    g = rs.randn(64, 32).astype(np.float32) * 0.1
    m = rs.randn(64, 32).astype(np.float32) * 0.01
    v = np.abs(rs.randn(64, 32)).astype(np.float32) * 0.01

    def on(ctx):
        res = {}
        out = mx.nd.sgd_update(mx.nd.array(w, ctx=ctx),
                               mx.nd.array(g, ctx=ctx), lr=0.1, wd=0.01)
        res["sgd"] = (out[0] if isinstance(out, list) else out).asnumpy()
        out = mx.nd.sgd_mom_update(mx.nd.array(w, ctx=ctx),
                                   mx.nd.array(g, ctx=ctx),
                                   mx.nd.array(m, ctx=ctx),
                                   lr=0.1, momentum=0.9, wd=0.01)
        res["sgdm"] = (out[0] if isinstance(out, list) else out).asnumpy()
        out = mx.nd.adam_update(mx.nd.array(w, ctx=ctx),
                                mx.nd.array(g, ctx=ctx),
                                mx.nd.array(m, ctx=ctx),
                                mx.nd.array(v, ctx=ctx),
                                lr=0.01, beta1=0.9, beta2=0.999,
                                epsilon=1e-8, wd=0.0)
        res["adam"] = (out[0] if isinstance(out, list) else out).asnumpy()
        out = mx.nd.rmsprop_update(mx.nd.array(w, ctx=ctx),
                                   mx.nd.array(g, ctx=ctx),
                                   mx.nd.array(v, ctx=ctx),
                                   lr=0.01, gamma1=0.95, epsilon=1e-8,
                                   wd=0.0)
        res["rmsprop"] = (out[0] if isinstance(out, list) else out).asnumpy()
        return res

    cpu, tpu = on(mx.cpu()), on(mx.tpu())
    for k in cpu:
        np.testing.assert_allclose(tpu[k], cpu[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)


@pytest.mark.parametrize("d", [128, 64])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_pallas_kernel_routes_on_chip(dtype, d):
    """At kernel-eligible shapes the REAL Pallas kernel must (a) be
    selected, (b) lower and run on hardware, and (c) match the dense
    reference — in f32 AND bf16 (training dtype), at a head of 128 and
    at the head of 64 the LM serves with."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as att

    b, h, l = 2, 4, 512
    assert att._kernel_refusal(np.zeros((b, h, l, d)),
                               np.zeros((b, h, l, d)), 256, 512) is None
    rs = np.random.RandomState(3)
    jdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    q = jnp.asarray(rs.normal(0, 1, (b, h, l, d)).astype(np.float32),
                    dtype=jdt)
    k = jnp.asarray(rs.normal(0, 1, (b, h, l, d)).astype(np.float32),
                    dtype=jdt)
    v = jnp.asarray(rs.normal(0, 1, (b, h, l, d)).astype(np.float32),
                    dtype=jdt)
    scale = float(1.0 / np.sqrt(d))
    tol = 2e-2 if dtype == "float32" else 5e-2
    lse_tol = 1e-4 if dtype == "float32" else 1e-3
    for causal in (False, True):
        out, lse = att._flash_pallas(q, k, v, causal, scale)
        assert out.dtype == jdt
        ref = att._attn_reference(q, k, v, causal=causal, scale=scale)
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float32),
            np.asarray(ref, dtype=np.float32), rtol=tol, atol=tol)
        _, lse_scan = att._flash_scan(q, k, v, causal, scale)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_scan),
                                   rtol=lse_tol, atol=lse_tol)
