"""CPU-vs-TPU parity for the HARD op families the round-2 sweep skipped.

Round-2 verdict #4: spatial ops (ROIPooling, SpatialTransformer,
BilinearSampler, GridGenerator, Correlation), contrib SSD ops, RNN
fwd+bwd, the loss heads, and the fused optimizer kernels at bf16 had no
on-chip coverage.  Reference analog:
``tests/python/gpu/test_operator_gpu.py`` re-runs everything via
``check_consistency`` — this file closes the gap family by family.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym
from mxnet_tpu.test_utils import assert_almost_equal, check_consistency

RTOL, ATOL = 2e-2, 2e-2


def _ctx_list(**shapes):
    return [dict(ctx=mx.cpu(), **shapes), dict(ctx=mx.tpu(), **shapes)]


# ---- spatial ops ----------------------------------------------------------

def test_roi_pooling_parity():
    data = sym.Variable("data")
    rois = sym.Variable("rois")
    net = sym.ROIPooling(data, rois, pooled_size=(2, 2), spatial_scale=0.5)
    rs = np.random.RandomState(0)
    x = rs.rand(2, 3, 8, 8).astype(np.float32)
    r = np.array([[0, 0, 0, 7, 7], [1, 2, 2, 12, 12]], np.float32)
    check_consistency(net, _ctx_list(data=(2, 3, 8, 8), rois=(2, 5)),
                      rtol=RTOL, atol=ATOL,
                      arg_params={"data": x, "rois": r})


def test_grid_generator_bilinear_sampler_parity():
    data = sym.Variable("data")
    affine = sym.Variable("affine")
    grid = sym.GridGenerator(affine, transform_type="affine",
                             target_shape=(6, 6))
    net = sym.BilinearSampler(data, grid)
    rs = np.random.RandomState(1)
    aff = np.tile(np.array([[0.9, 0.1, 0.05, -0.1, 0.8, 0.0]],
                           np.float32), (2, 1))
    check_consistency(net, _ctx_list(data=(2, 3, 6, 6), affine=(2, 6)),
                      rtol=RTOL, atol=ATOL,
                      arg_params={"affine": aff,
                                  "data": rs.rand(2, 3, 6, 6)
                                  .astype(np.float32)})


def test_spatial_transformer_parity():
    data = sym.Variable("data")
    loc = sym.Variable("loc")
    net = sym.SpatialTransformer(data, loc, target_shape=(6, 6),
                                 transform_type="affine",
                                 sampler_type="bilinear")
    rs = np.random.RandomState(2)
    lc = np.tile(np.array([[1.0, 0.0, 0.0, 0.0, 1.0, 0.0]], np.float32),
                 (2, 1)) + rs.rand(2, 6).astype(np.float32) * 0.05
    # smooth image: bilinear-sampling gradients on white noise flip sign
    # across cell boundaries under bf16 grid rounding — a low-frequency
    # field keeps the parity check meaningful
    yy, xx = np.meshgrid(np.linspace(0, 1, 6), np.linspace(0, 1, 6),
                         indexing="ij")
    img = np.stack([np.sin(3 * xx + yy), np.cos(2 * yy - xx)])
    data = np.tile(img[None], (2, 1, 1, 1)).astype(np.float32)
    check_consistency(net, _ctx_list(data=(2, 2, 6, 6), loc=(2, 6)),
                      rtol=RTOL, atol=ATOL,
                      arg_params={"loc": lc, "data": data})


def test_correlation_parity():
    a = sym.Variable("a")
    b = sym.Variable("b")
    net = sym.Correlation(a, b, kernel_size=1, max_displacement=2,
                          stride1=1, stride2=1, pad_size=2)
    check_consistency(net, _ctx_list(a=(1, 2, 8, 8), b=(1, 2, 8, 8)),
                      scale=0.5, rtol=RTOL, atol=ATOL)


def test_crop_swapaxis_slicechannel_concat_parity():
    data = sym.Variable("data")
    c = sym.Crop(data, offset=(1, 1), h_w=(5, 5))
    s = sym.SwapAxis(c, dim1=2, dim2=3)
    parts = sym.SliceChannel(s, num_outputs=2, axis=1)
    net = sym.Concat(parts[0], parts[1], dim=1)
    check_consistency(net, _ctx_list(data=(2, 4, 7, 7)),
                      rtol=RTOL, atol=ATOL)


# ---- contrib SSD / RCNN ops ----------------------------------------------

def test_multibox_chain_parity():
    """MultiBoxPrior -> Target forward parity on chip (detection-side
    ops; Detection covered via the same anchors)."""
    feat = sym.Variable("feat")
    anchors = sym.MultiBoxPrior(feat, sizes=(0.4, 0.7),
                                        ratios=(1.0, 2.0))
    cls_pred = sym.Variable("cls_pred")
    label = sym.Variable("label")
    tgt = sym.MultiBoxTarget(anchors, label, cls_pred)
    net = sym.Group(list(tgt))
    rs = np.random.RandomState(3)
    lab = -np.ones((1, 2, 5), np.float32)
    lab[0, 0] = [0, 0.1, 0.1, 0.6, 0.6]
    cp = rs.rand(1, 2, 48).astype(np.float32)
    outs = []
    for ctx in (mx.cpu(), mx.tpu()):
        ex = net.simple_bind(ctx, grad_req="null", feat=(1, 4, 4, 4),
                             cls_pred=(1, 2, 48), label=(1, 2, 5))
        ex.arg_dict["cls_pred"][:] = cp
        ex.arg_dict["label"][:] = lab
        ex.arg_dict["feat"][:] = rs.rand(1, 4, 4, 4).astype(np.float32)
        outs.append([o.asnumpy() for o in ex.forward(is_train=False)])
    for a, b in zip(*outs):
        assert_almost_equal(a, b, rtol=1e-3, atol=1e-4)


def test_proposal_parity():
    # round 4: Proposal runs fully ON-DEVICE (the NMS scatter that
    # SIGABRTed XLA:TPU was replaced with an argsort inverse
    # permutation), so no callback probe / skip is needed anymore
    cls_prob = sym.Variable("cls_prob")
    bbox_pred = sym.Variable("bbox_pred")
    im_info = sym.Variable("im_info")
    net = sym.Proposal(cls_prob, bbox_pred, im_info,
                               feature_stride=4, scales=(4,),
                               ratios=(1.0,), rpn_pre_nms_top_n=12,
                               rpn_post_nms_top_n=4)
    rs = np.random.RandomState(4)
    args = {"cls_prob": rs.rand(1, 2, 6, 6).astype(np.float32),
            "bbox_pred": (rs.rand(1, 4, 6, 6).astype(np.float32) - 0.5)
            * 0.1,
            "im_info": np.array([[24, 24, 1.0]], np.float32)}
    outs = []
    for ctx in (mx.cpu(), mx.tpu()):
        ex = net.simple_bind(ctx, grad_req="null", cls_prob=(1, 2, 6, 6),
                             bbox_pred=(1, 4, 6, 6), im_info=(1, 3))
        for k, v in args.items():
            ex.arg_dict[k][:] = v
        outs.append([o.asnumpy() for o in ex.forward(is_train=False)])
    for a, b in zip(*outs):
        assert_almost_equal(a, b, rtol=1e-3, atol=1e-4)


def test_proposal_parity_streaming_nms():
    """>2048 anchors takes the O(A)-memory row-streaming NMS branch
    (_greedy_nms) on BOTH devices — this is a cpu-vs-tpu parity check of
    the streaming branch itself; streaming-vs-matrix equivalence is
    pinned directly (same inputs, forced switch) in
    tests/test_contrib_ops.py::
    test_greedy_nms_branch_equivalence_identical_inputs."""
    cls_prob = sym.Variable("cls_prob")
    bbox_pred = sym.Variable("bbox_pred")
    im_info = sym.Variable("im_info")
    net = sym.Proposal(cls_prob, bbox_pred, im_info,
                       feature_stride=8, scales=(4, 8, 16),
                       ratios=(0.5, 1.0, 2.0), rpn_pre_nms_top_n=2304,
                       rpn_post_nms_top_n=16)
    rs = np.random.RandomState(11)
    # 16x16 grid x 9 anchors = 2304 > 2048 -> streaming branch
    args = {"cls_prob": rs.rand(1, 18, 16, 16).astype(np.float32),
            "bbox_pred": (rs.rand(1, 36, 16, 16).astype(np.float32)
                          - 0.5) * 0.1,
            "im_info": np.array([[128, 128, 1.0]], np.float32)}
    outs = []
    for ctx in (mx.cpu(), mx.tpu()):
        ex = net.simple_bind(ctx, grad_req="null",
                             cls_prob=(1, 18, 16, 16),
                             bbox_pred=(1, 36, 16, 16), im_info=(1, 3))
        for k, v in args.items():
            ex.arg_dict[k][:] = v
        outs.append([o.asnumpy() for o in ex.forward(is_train=False)])
    for a, b in zip(*outs):
        assert_almost_equal(a, b, rtol=1e-3, atol=1e-4)


# ---- RNN op + sequence ops ------------------------------------------------

@pytest.mark.parametrize("mode", ["rnn_tanh", "gru", "lstm"])
def test_rnn_op_parity(mode):
    data = sym.Variable("data")
    params = sym.Variable("params")
    state = sym.Variable("state")
    kwargs = dict(state_size=4, num_layers=1, mode=mode)
    if mode == "lstm":
        cell = sym.Variable("state_cell")
        net = sym.RNN(data, params, state, cell, **kwargs)
        shapes = dict(data=(5, 2, 3), state=(1, 2, 4),
                      state_cell=(1, 2, 4))
    else:
        net = sym.RNN(data, params, state, **kwargs)
        shapes = dict(data=(5, 2, 3), state=(1, 2, 4))
    np_per = {"rnn_tanh": 1, "gru": 3, "lstm": 4}[mode]
    psize = np_per * (4 * 3 + 4 * 4 + 4 + 4)
    shapes["params"] = (psize,)
    check_consistency(net, _ctx_list(**shapes), scale=0.4,
                      rtol=RTOL, atol=ATOL)


def test_sequence_ops_parity():
    data = sym.Variable("data")
    slen = sym.Variable("slen")
    rev = sym.SequenceReverse(data, slen, use_sequence_length=True)
    msk = sym.SequenceMask(rev, slen, use_sequence_length=True, value=0.0)
    net = sym.SequenceLast(msk, slen, use_sequence_length=True)
    rs = np.random.RandomState(5)
    check_consistency(net, _ctx_list(data=(6, 3, 4), slen=(3,)),
                      rtol=RTOL, atol=ATOL,
                      arg_params={"slen": np.array([6, 4, 2], np.float32),
                                  "data": rs.rand(6, 3, 4)
                                  .astype(np.float32)})


# ---- loss heads -----------------------------------------------------------

@pytest.mark.parametrize("head", ["LinearRegressionOutput",
                                  "LogisticRegressionOutput",
                                  "MAERegressionOutput", "SVMOutput"])
def test_regression_heads_parity(head):
    data = sym.Variable("data")
    label = sym.Variable("label")
    net = getattr(sym, head)(data, label)
    rs = np.random.RandomState(6)
    lab = (rs.rand(4, 5) > 0.5).astype(np.float32) \
        if head != "SVMOutput" else rs.randint(0, 5, (4,)) \
        .astype(np.float32)
    shapes = dict(data=(4, 5),
                  label=(4,) if head == "SVMOutput" else (4, 5))
    check_consistency(net, _ctx_list(**shapes), rtol=RTOL, atol=ATOL,
                      arg_params={"label": lab})


def test_makeloss_smoothl1_xent_parity():
    data = sym.Variable("data")
    label = sym.Variable("label")
    l1 = sym.MakeLoss(sym.sum(sym.smooth_l1(data - label, scalar=1.0)))
    check_consistency(l1, _ctx_list(data=(4, 6), label=(4, 6)),
                      rtol=RTOL, atol=ATOL)
    xent = sym.softmax_cross_entropy(sym.Variable("d"), sym.Variable("y"))
    rs = np.random.RandomState(7)
    outs = []
    for ctx in (mx.cpu(), mx.tpu()):
        ex = xent.simple_bind(ctx, grad_req="null", d=(6, 4), y=(6,))
        ex.arg_dict["d"][:] = rs.rand(6, 4).astype(np.float32)
        ex.arg_dict["y"][:] = rs.randint(0, 4, (6,)).astype(np.float32)
        outs.append(ex.forward(is_train=False)[0].asnumpy())
        rs = np.random.RandomState(7)
    assert_almost_equal(outs[0], outs[1], rtol=1e-3, atol=1e-4)


def test_misc_norm_layers_parity():
    data = sym.Variable("data")
    net = sym.L2Normalization(sym.InstanceNorm(data))
    net = sym.SoftmaxActivation(sym.LRN(net, nsize=3))
    check_consistency(net, _ctx_list(data=(2, 4, 5, 5)),
                      rtol=RTOL, atol=ATOL)


def test_dropout_eval_and_blockgrad_parity():
    data = sym.Variable("data")
    net = sym.BlockGrad(sym.Dropout(data, p=0.5)) * 2.0
    # eval mode: dropout is identity -> deterministic cross-backend
    rs = np.random.RandomState(8)
    x = rs.rand(3, 7).astype(np.float32)
    outs = []
    for ctx in (mx.cpu(), mx.tpu()):
        ex = net.simple_bind(ctx, grad_req="null", data=(3, 7))
        ex.arg_dict["data"][:] = x
        outs.append(ex.forward(is_train=False)[0].asnumpy())
    assert_almost_equal(outs[0], outs[1], rtol=1e-5, atol=1e-6)


# ---- fused optimizer kernels at bf16 --------------------------------------

@pytest.mark.parametrize("op,extra_state", [
    ("sgd_update", 0), ("sgd_mom_update", 1), ("adam_update", 2),
    ("rmsprop_update", 1), ("rmspropalex_update", 3)])
def test_optimizer_kernels_bf16_parity(op, extra_state):
    rs = np.random.RandomState(9)
    w = rs.rand(4, 6).astype(np.float32)
    g = (rs.rand(4, 6).astype(np.float32) - 0.5)
    states = [np.zeros_like(w) for _ in range(extra_state)]
    kwargs = {"lr": 0.1}
    if op == "adam_update":
        kwargs.update(beta1=0.9, beta2=0.99, epsilon=1e-8)
    if op.startswith("rmsprop"):
        kwargs.update(gamma1=0.9, epsilon=1e-8)
    if op == "rmspropalex_update":
        kwargs.update(gamma2=0.9)
    results = []
    for ctx, dtype in ((mx.cpu(), "float32"), (mx.tpu(), "bfloat16")):
        arrs = [mx.nd.array(a, ctx=ctx, dtype=dtype)
                for a in [w, g] + states]
        outs = getattr(mx.nd, op)(*arrs, **kwargs)
        outs = outs if isinstance(outs, list) else [outs]
        results.append(np.asarray(outs[0].asnumpy(), np.float32))
    # bf16 state/weight pass: coarse tolerance, but the update direction
    # and magnitude must match
    assert_almost_equal(results[0], results[1], rtol=2e-2, atol=2e-2)


# ---- scalar / comparison / indexing sweep ---------------------------------

_SCALAR_OPS = ["_plus_scalar", "_minus_scalar", "_rminus_scalar",
               "_mul_scalar", "_div_scalar", "_rdiv_scalar",
               "_power_scalar", "_rpower_scalar", "_maximum_scalar",
               "_minimum_scalar", "_hypot_scalar"]


@pytest.mark.parametrize("op", _SCALAR_OPS)
def test_scalar_op_parity(op):
    rs = np.random.RandomState(10)
    x = (rs.rand(3, 4) * 1.5 + 0.5).astype(np.float32)
    s = getattr(sym, op)(sym.Variable("x"), scalar=1.7)
    check_consistency(s, _ctx_list(x=(3, 4)), rtol=RTOL, atol=ATOL,
                      arg_params={"x": x})


_CMP_OPS = ["_equal", "_not_equal", "_greater", "_greater_equal",
            "_lesser", "_lesser_equal", "_power", "_maximum", "_minimum",
            "_hypot", "_grad_add"]


@pytest.mark.parametrize("op", _CMP_OPS)
def test_binary_extended_parity(op):
    rs = np.random.RandomState(11)
    a = (rs.rand(3, 4) * 1.5 + 0.5).astype(np.float32)
    b = (rs.rand(3, 4) * 1.5 + 0.5).astype(np.float32)
    s = getattr(sym, op)(sym.Variable("a"), sym.Variable("b"))
    check_consistency(s, _ctx_list(a=(3, 4), b=(3, 4)), rtol=RTOL,
                      atol=ATOL, arg_params={"a": a, "b": b})


_BCMP_OPS = ["broadcast_equal", "broadcast_not_equal", "broadcast_greater",
             "broadcast_greater_equal", "broadcast_lesser",
             "broadcast_lesser_equal", "broadcast_axis", "broadcast_to"]


@pytest.mark.parametrize("op", _BCMP_OPS)
def test_broadcast_extended_parity(op):
    rs = np.random.RandomState(12)
    if op in ("broadcast_axis", "broadcast_to"):
        a = rs.rand(2, 1, 3).astype(np.float32)
        kw = {"axis": 1, "size": 4} if op == "broadcast_axis" \
            else {"shape": (2, 4, 3)}
        s = getattr(sym, op)(sym.Variable("a"), **kw)
        check_consistency(s, _ctx_list(a=(2, 1, 3)), rtol=RTOL, atol=ATOL,
                          arg_params={"a": a})
    else:
        a = rs.rand(2, 3, 4).astype(np.float32)
        b = rs.rand(1, 3, 1).astype(np.float32)
        s = getattr(sym, op)(sym.Variable("a"), sym.Variable("b"))
        check_consistency(s, _ctx_list(a=(2, 3, 4), b=(1, 3, 1)),
                          rtol=RTOL, atol=ATOL,
                          arg_params={"a": a, "b": b})


def test_matmul_family_parity():
    a = sym.Variable("a")
    b = sym.Variable("b")
    net = sym.dot(a, b)
    check_consistency(net, _ctx_list(a=(4, 6), b=(6, 5)), scale=0.5,
                      rtol=RTOL, atol=ATOL)
    net = sym.batch_dot(sym.Variable("x"), sym.Variable("y"))
    check_consistency(net, _ctx_list(x=(2, 3, 4), y=(2, 4, 5)), scale=0.5,
                      rtol=RTOL, atol=ATOL)


def test_indexing_ordering_parity():
    """take / batch_take / one_hot / pick / topk / sort / argsort /
    argmax / argmin / argmax_channel / norm — forward parity (integer
    outputs exact)."""
    rs = np.random.RandomState(13)
    x = rs.rand(4, 6).astype(np.float32)
    idx = rs.randint(0, 4, (3,)).astype(np.float32)
    bidx = rs.randint(0, 6, (4,)).astype(np.float32)

    cases = [
        (sym.take(sym.Variable("w"), sym.Variable("i")),
         {"w": (4, 6), "i": (3,)}, {"w": x, "i": idx}),
        (sym.batch_take(sym.Variable("w"), sym.Variable("i")),
         {"w": (4, 6), "i": (4,)}, {"w": x, "i": bidx}),
        (sym.one_hot(sym.Variable("i"), depth=5), {"i": (3,)},
         {"i": idx}),
        (sym.pick(sym.Variable("w"), sym.Variable("i"), axis=1),
         {"w": (4, 6), "i": (4,)}, {"w": x, "i": bidx}),
        (sym.topk(sym.Variable("w"), k=3, ret_typ="value"),
         {"w": (4, 6)}, {"w": x}),
        (sym.sort(sym.Variable("w"), axis=1), {"w": (4, 6)}, {"w": x}),
        (sym.argsort(sym.Variable("w"), axis=1), {"w": (4, 6)},
         {"w": x}),
        (sym.argmax(sym.Variable("w"), axis=1), {"w": (4, 6)}, {"w": x}),
        (sym.argmin(sym.Variable("w"), axis=1), {"w": (4, 6)}, {"w": x}),
        (sym.argmax_channel(sym.Variable("w")), {"w": (4, 6)}, {"w": x}),
        (sym.norm(sym.Variable("w")), {"w": (4, 6)}, {"w": x}),
    ]
    for net, shapes, args in cases:
        outs = []
        for ctx in (mx.cpu(), mx.tpu()):
            ex = net.simple_bind(ctx, grad_req="null", **shapes)
            for k, v in args.items():
                ex.arg_dict[k][:] = v
            outs.append([o.asnumpy() for o in ex.forward(is_train=False)])
        for a, b in zip(*outs):
            assert_almost_equal(a, b, rtol=1e-3, atol=1e-4)


def test_creation_ops_parity():
    """_zeros/_ones/_arange + random ops produce correct shapes/stats on
    chip (random draws differ across backends by design — check
    moments)."""
    for ctx in (mx.tpu(),):
        z = mx.nd.zeros((3, 4), ctx=ctx)
        o = mx.nd.ones((3, 4), ctx=ctx)
        ar = mx.nd.arange(0, 10, step=2, ctx=ctx)
        assert (z.asnumpy() == 0).all() and (o.asnumpy() == 1).all()
        np.testing.assert_array_equal(ar.asnumpy(),
                                      np.arange(0, 10, 2, np.float32))
        mx.random.seed(42)
        u = mx.nd.uniform(low=0, high=1, shape=(2000,), ctx=ctx)
        n = mx.nd.normal(loc=0, scale=1, shape=(2000,), ctx=ctx)
        uu, nn = u.asnumpy(), n.asnumpy()
        assert 0.4 < uu.mean() < 0.6 and uu.min() >= 0 and uu.max() <= 1
        assert abs(nn.mean()) < 0.15 and 0.85 < nn.std() < 1.15


def test_legacy_internals_parity():
    """Legacy NDArray-function registry ops + graph internals
    (reference src/ndarray/ndarray.cc:748-867): parity of the small
    mutate/index helpers and the KL-reg identity on chip."""
    rs = np.random.RandomState(14)
    x = rs.rand(4, 5).astype(np.float32)
    idx = rs.randint(0, 5, (4,)).astype(np.float32)

    results = []
    for ctx in (mx.cpu(), mx.tpu()):
        out = {}
        a = mx.nd.array(x, ctx=ctx)
        out["set_value"] = mx.nd._set_value(a, src=3.5).asnumpy()
        out["onehot"] = mx.nd._onehot_encode(
            mx.nd.array(idx, ctx=ctx), mx.nd.zeros((4, 5), ctx=ctx)) \
            .asnumpy()
        out["choose"] = mx.nd.choose_element_0index(
            mx.nd.array(x, ctx=ctx), mx.nd.array(idx, ctx=ctx)).asnumpy()
        out["fill"] = mx.nd.fill_element_0index(
            mx.nd.array(x, ctx=ctx), mx.nd.ones((4,), ctx=ctx),
            mx.nd.array(idx, ctx=ctx)).asnumpy()
        out["bcast"] = mx.nd._broadcast(
            mx.nd.array(x[:1], ctx=ctx), shape=(4, 5)).asnumpy()
        out["addn"] = mx.nd.add_n(mx.nd.array(x, ctx=ctx),
                                  mx.nd.array(x, ctx=ctx),
                                  mx.nd.array(x, ctx=ctx)).asnumpy()
        results.append(out)
    for k in results[0]:
        assert_almost_equal(results[0][k], results[1][k], rtol=1e-5,
                            atol=1e-6)


def test_slice_assign_and_klreg_parity():
    data = sym.Variable("data")
    src = sym.Variable("src")
    net = sym._slice_assign(data, src, begin=(1, 1), end=(3, 4))
    check_consistency(net, _ctx_list(data=(4, 5), src=(2, 3)),
                      rtol=RTOL, atol=ATOL)
    net2 = sym._crop_assign_scalar(sym.Variable("d"), scalar=2.5,
                                   begin=(0, 1), end=(2, 3))
    check_consistency(net2, _ctx_list(d=(3, 4)), rtol=RTOL, atol=ATOL)
    net3 = sym.IdentityAttachKLSparseReg(sym.Variable("p"),
                                         sparseness_target=0.1)
    rs = np.random.RandomState(15)
    check_consistency(net3, _ctx_list(p=(3, 4)), rtol=RTOL, atol=ATOL,
                      arg_params={"p": (rs.rand(3, 4) * 0.8 + 0.1)
                                  .astype(np.float32)})


def test_multibox_detection_and_identity_rhs_parity():
    """MultiBoxDetection (NMS path) + _identity_with_attr_like_rhs +
    make_loss on chip."""
    rs = np.random.RandomState(16)
    A = 8
    anchors = np.sort(rs.rand(1, A, 4).astype(np.float32) * 0.8, axis=2)
    cls_prob = rs.rand(1, 3, A).astype(np.float32)
    loc_pred = (rs.rand(1, A * 4).astype(np.float32) - 0.5) * 0.1
    net = sym.MultiBoxDetection(sym.Variable("cls_prob"),
                                sym.Variable("loc_pred"),
                                sym.Variable("anchors"),
                                nms_threshold=0.5, nms_topk=4)
    outs = []
    for ctx in (mx.cpu(), mx.tpu()):
        ex = net.simple_bind(ctx, grad_req="null", cls_prob=(1, 3, A),
                             loc_pred=(1, A * 4), anchors=(1, A, 4))
        ex.arg_dict["cls_prob"][:] = cls_prob
        ex.arg_dict["loc_pred"][:] = loc_pred
        ex.arg_dict["anchors"][:] = anchors
        outs.append(ex.forward(is_train=False)[0].asnumpy())
    assert_almost_equal(outs[0], outs[1], rtol=1e-3, atol=1e-4)

    lhs = sym.Variable("lhs")
    rhs = sym.Variable("rhs")
    net2 = sym._identity_with_attr_like_rhs(lhs, rhs)
    check_consistency(net2, _ctx_list(lhs=(3, 4), rhs=(3, 4)),
                      rtol=RTOL, atol=ATOL)
    net3 = sym.make_loss(sym.sum(sym.Variable("p") * 2.0))
    check_consistency(net3, _ctx_list(p=(3, 4)), rtol=RTOL, atol=ATOL)


def test_imperative_jit_cache_keys_on_device():
    """An imperative op traced for one backend must not be replayed for
    the other: a trace bakes in what its ops decided for the device they
    asked about (``registry.on_tpu()``), so the jit cache keys on the
    trace device."""
    rs = np.random.RandomState(0)
    x = rs.rand(8, 16, 4, 4).astype(np.float32)
    g = np.ones((16,), np.float32)
    b = np.zeros((16,), np.float32)
    mm = np.zeros((16,), np.float32)
    mv = np.ones((16,), np.float32)

    def run(ctx):
        return mx.nd.BatchNorm(
            mx.nd.array(x, ctx=ctx), mx.nd.array(g, ctx=ctx),
            mx.nd.array(b, ctx=ctx), mx.nd.array(mm, ctx=ctx),
            mx.nd.array(mv, ctx=ctx), fix_gamma=False).asnumpy()

    out_tpu = run(mx.tpu())   # traces for the TPU
    out_cpu = run(mx.cpu())   # must retrace for CPU, not reuse
    assert_almost_equal(out_cpu, out_tpu, rtol=2e-3, atol=2e-3)


def test_census_tail_ops_execute_tpu():
    """The 6 hardware-runnable ops the TPU invocation census caught
    with zero executions (Cast, softmax, where, _arange, _zeros,
    _ones) — each runs imperatively ON THE CHIP with a value check, so
    the census TPU column is execution-backed for every row."""
    rs = np.random.RandomState(9)
    a = rs.rand(4, 6).astype(np.float32)
    ta = mx.nd.array(a, ctx=mx.tpu())

    c = mx.nd.Cast(ta, dtype="float16").asnumpy()
    assert c.dtype == np.float16 and np.allclose(c, a, atol=1e-2)

    s = mx.nd.softmax(ta, axis=-1).asnumpy()
    want = np.exp(a) / np.exp(a).sum(-1, keepdims=True)
    assert np.allclose(s, want, rtol=1e-4, atol=1e-5)

    cond = mx.nd.array((a > 0.5).astype(np.float32), ctx=mx.tpu())
    tb = mx.nd.array(-a, ctx=mx.tpu())
    w = mx.nd.where(cond, ta, tb).asnumpy()
    assert np.allclose(w, np.where(a > 0.5, a, -a))

    z = mx.nd._zeros(shape=(3, 2), ctx=mx.tpu())
    o = mx.nd._ones(shape=(3, 2), ctx=mx.tpu())
    r = mx.nd._arange(start=2.0, stop=11.0, step=3.0, ctx=mx.tpu())
    assert (z.asnumpy() == 0).all() and (o.asnumpy() == 1).all()
    assert (r.asnumpy() == np.arange(2.0, 11.0, 3.0,
                                     dtype=np.float32)).all()
    for nd_arr in (z, o, r):
        assert "tpu" in str(nd_arr.context).lower() \
            or nd_arr.context.device_typeid != 1, nd_arr.context
