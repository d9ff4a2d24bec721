"""Neural-net layer ops (the legacy-registry census, SURVEY §2.3).

Reference kernels: ``src/operator/{fully_connected,convolution,pooling,
batch_norm,activation,dropout,concat,slice_channel,pad,lrn,instance_norm,
l2_normalization,upsampling,swapaxis,leaky_relu,sequence_*}-inl.h``.

TPU design: none of these are hand kernels — Convolution/FullyConnected lower
to XLA conv/dot_general (MXU), BatchNorm/Pooling/activations are XLA
elementwise/reduce-window that fuse around them.  The reference's
im2col+GEMM (``src/operator/nn/im2col.h``) and cuDNN dispatch disappear:
XLA picks the conv algorithm.  Layout is NCHW to match the reference API;
XLA relayouts internally for the MXU.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from .helpers import acc_dtype as _acc_dtype, simple
from .registry import (REQUIRED, pbool, pfloat, pint, pstr, ptuple, register)


@lru_cache(maxsize=None)
def _conv_f32acc(stride, padding, lhs_dilation, rhs_dilation, dn, groups):
    """Conv whose primal accumulates f32 for low-precision inputs (the
    reference's cuDNN conv accumulates f32; bf16 partials would drift
    top-1), output cast back to the input dtype.

    JAX 0.9's conv transpose rule rejects the mixed-dtype cotangent that
    ``preferred_element_type`` + ``astype`` produces, so the backward is a
    custom_vjp that casts the cotangent to the primal dtype and reuses the
    plain same-dtype conv vjp (whose grad convs still accumulate f32
    inside the MXU)."""
    kw = dict(window_strides=stride, padding=padding,
              lhs_dilation=lhs_dilation, rhs_dilation=rhs_dilation,
              dimension_numbers=dn, feature_group_count=groups)

    def plain(data, weight):
        return jax.lax.conv_general_dilated(data, weight, **kw)

    @jax.custom_vjp
    def conv(data, weight):
        return jax.lax.conv_general_dilated(
            data, weight, preferred_element_type=_acc_dtype(data.dtype),
            **kw).astype(data.dtype)

    def fwd(data, weight):
        return conv(data, weight), (data, weight)

    def bwd(res, g):
        data, weight = res
        _, vjp = jax.vjp(plain, data, weight)
        # the barrier keeps XLA:TPU from fusing a pad/slice-produced
        # cotangent into the transposed convs — that fusion miscompiles
        # on the current TPU toolchain (wrong data-gradients for any
        # Pad/Crop/slice directly after a conv; verified against CPU and
        # finite differences)
        g = jax.lax.optimization_barrier(g.astype(data.dtype))
        return vjp(g)

    conv.defvjp(fwd, bwd)
    return conv


def _norm_stp(kernel, stride, dilate, pad):
    nd = len(kernel)
    stride = tuple(stride) if stride else (1,) * nd
    dilate = tuple(dilate) if dilate else (1,) * nd
    pad = tuple(pad) if pad else (0,) * nd
    return stride, dilate, pad


# ---------------------------------------------------------------------------
# FullyConnected — reference ``fully_connected-inl.h:47-81`` (mshadow dot)
# ---------------------------------------------------------------------------
def _fully_connected(attrs, inputs, aux, is_train, rng):
    data = inputs[0]
    weight = inputs[1]
    data = _match_param_dtype(data, weight)
    if attrs["flatten"] and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = jnp.dot(data, weight.T,
                  preferred_element_type=_acc_dtype(data.dtype)).astype(data.dtype)
    if not attrs["no_bias"]:
        out = out + inputs[2]
    return [out]


register("FullyConnected", _fully_connected,
         arguments=lambda a: ["data", "weight"] + ([] if a["no_bias"] else ["bias"]),
         params={"num_hidden": (pint, REQUIRED), "no_bias": (pbool, False),
                 "flatten": (pbool, True)},
         hint="fullyconnected")


# ---------------------------------------------------------------------------
# Convolution — reference ``convolution-inl.h`` (im2col+GEMM) / cuDNN.
# N-D (1/2/3): XLA conv_general_dilated on NC[DHW] layouts.
# ---------------------------------------------------------------------------
_CONV_DIMNUMS = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
                 3: ("NCDHW", "OIDHW", "NCDHW")}


def _match_param_dtype(data, weight):
    """Mixed precision: the parameter dtype defines the net's compute
    precision (the reference's fp16-net pattern casts data at the input),
    so f32 iterator data into a bf16-cast net runs — and stays — bf16."""
    if data.dtype != weight.dtype:
        data = data.astype(weight.dtype)
    return data


def _stem_s2d_conv(data, weight):
    """EXACT rewrite of the 7x7/stride-2/pad-3 few-channel stem conv as
    space-to-depth(2x2) + 4x4/stride-1 conv (the MLPerf TPU ResNet stem
    transform).  C_in=3 wastes the MXU's 128-wide contraction lanes; the
    rewrite contracts over C*4=12 channels with 16 taps instead of 3
    with 49 — measured ~2x on the stem cluster (fwd+dgrad+wgrad).  Same
    weights, same math: tap p=2a+b of the 7x7 kernel (zero-padded to
    8x8) becomes block-tap a, in-block offset b of a 4x4 kernel over
    2x2-blocked input; outputs are bit-identical shapes.

    Reference analog: none — cuDNN handled the stem natively
    (``cudnn_convolution``); this is the TPU-first equivalent.
    """
    import jax.numpy as jnp

    n, c, h, w = data.shape
    k = weight.shape[0]
    xp = jnp.pad(data, ((0, 0), (0, 0), (3, 3), (3, 3)))
    hb, wb = (h + 6) // 2, (w + 6) // 2
    xb = xp.reshape(n, c, hb, 2, wb, 2)
    xb = xb.transpose(0, 1, 3, 5, 2, 4).reshape(n, c * 4, hb, wb)
    wp = jnp.pad(weight, ((0, 0), (0, 0), (0, 1), (0, 1)))
    wb4 = wp.reshape(k, c, 4, 2, 4, 2)
    wb4 = wb4.transpose(0, 1, 3, 5, 2, 4).reshape(k, c * 4, 4, 4)
    return _conv_f32acc((1, 1), ((0, 0), (0, 0)), (1, 1), (1, 1),
                        _CONV_DIMNUMS[2], 1)(xb, wb4)


def _convolution(attrs, inputs, aux, is_train, rng):
    data, weight = inputs[0], inputs[1]
    data = _match_param_dtype(data, weight)
    kernel = attrs["kernel"]
    nd = len(kernel)
    stride, dilate, pad = _norm_stp(kernel, attrs["stride"], attrs["dilate"],
                                    attrs["pad"])
    if (nd == 2 and tuple(kernel) == (7, 7)
            and stride == (2, 2) and pad == (3, 3) and dilate == (1, 1)
            and attrs["num_group"] == 1 and data.shape[1] <= 4
            and data.shape[2] % 2 == 0 and data.shape[3] % 2 == 0):
        out = _stem_s2d_conv(data, weight)
    else:
        out = _conv_f32acc(stride, tuple((p, p) for p in pad), (1,) * nd,
                           dilate, _CONV_DIMNUMS[nd],
                           attrs["num_group"])(data, weight)
    if not attrs["no_bias"]:
        bias = inputs[2].reshape((1, -1) + (1,) * nd)
        out = out + bias
    return [out]


_CONV_PARAMS = {
    "kernel": (ptuple, REQUIRED), "stride": (ptuple, ()), "dilate": (ptuple, ()),
    "pad": (ptuple, ()), "num_filter": (pint, REQUIRED), "num_group": (pint, 1),
    "workspace": (pint, 1024), "no_bias": (pbool, False),
    "cudnn_tune": (pstr, None), "cudnn_off": (pbool, False),
    "layout": (pstr, None),
}

register("Convolution", _convolution,
         arguments=lambda a: ["data", "weight"] + ([] if a["no_bias"] else ["bias"]),
         params=_CONV_PARAMS, hint="convolution")


def _deconvolution(attrs, inputs, aux, is_train, rng):
    data, weight = inputs[0], inputs[1]
    data = _match_param_dtype(data, weight)
    kernel = attrs["kernel"]
    nd = len(kernel)
    stride, dilate, pad = _norm_stp(kernel, attrs["stride"], attrs["dilate"],
                                    attrs["pad"])
    adj = tuple(attrs["adj"]) if attrs["adj"] else (0,) * nd
    # Transposed conv = lhs-dilated conv with spatially-flipped kernel;
    # weight layout is (C_in, C_out/g, *k) = IOHW, matching the reference's
    # deconvolution weight shape.
    flip = (slice(None), slice(None)) + (slice(None, None, -1),) * nd
    padding = [(k - 1 - p, k - 1 - p + a)
               for k, p, a in zip(kernel, pad, adj)]
    dn = {1: ("NCH", "IOH", "NCH"), 2: ("NCHW", "IOHW", "NCHW"),
          3: ("NCDHW", "IODHW", "NCDHW")}[nd]
    out = _conv_f32acc(tuple((1,) * nd), tuple(padding), stride, dilate,
                       dn, attrs["num_group"])(data, weight[flip])
    if not attrs["no_bias"]:
        out = out + inputs[2].reshape((1, -1) + (1,) * nd)
    return [out]


register("Deconvolution", _deconvolution,
         arguments=lambda a: ["data", "weight"] + ([] if a["no_bias"] else ["bias"]),
         params={**_CONV_PARAMS, "adj": (ptuple, ()), "target_shape": (ptuple, ())},
         hint="deconvolution")


# ---------------------------------------------------------------------------
# Pooling — reference ``pooling-inl.h`` + ``nn/pool.h``; reduce_window on TPU
# ---------------------------------------------------------------------------
def _pool_out_dim(x, k, p, s, convention):
    if convention == "full":
        return int(np.ceil(float(x + 2 * p - k) / s)) + 1
    return int(np.floor(float(x + 2 * p - k) / s)) + 1


def _pooling(attrs, inputs, aux, is_train, rng):
    data = inputs[0]
    nd = data.ndim - 2
    if attrs["global_pool"]:
        kernel = data.shape[2:]
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = attrs["kernel"]
        stride, _, pad = _norm_stp(kernel, attrs["stride"], (), attrs["pad"])
    # 'full' convention (ceil) may need extra right-padding
    extra = []
    for i in range(nd):
        o = _pool_out_dim(data.shape[2 + i], kernel[i], pad[i], stride[i],
                          attrs["pooling_convention"] if not attrs["global_pool"]
                          else "valid")
        need = (o - 1) * stride[i] + kernel[i] - data.shape[2 + i] - pad[i]
        extra.append(max(need, pad[i]))
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    padding = ((0, 0), (0, 0)) + tuple((p, e) for p, e in zip(pad, extra))
    pt = attrs["pool_type"]
    if pt == "max":
        # literal -inf init so JAX recognises the differentiable
        # reduce-window-max pattern (select-and-scatter transpose)
        out = jax.lax.reduce_window(data, -jnp.inf, jax.lax.max,
                                    window, strides, padding)
    elif pt in ("avg", "sum"):
        out = jax.lax.reduce_window(data, 0.0, jax.lax.add,
                                    window, strides, padding)
        if pt == "avg":
            # reference counts the full window incl. padding (mshadow pool)
            out = out / float(np.prod(kernel))
    else:
        raise MXNetError("Pooling: bad pool_type %r" % pt)
    return [out]


register("Pooling", _pooling,
         params={"kernel": (ptuple, ()), "pool_type": (pstr, "max"),
                 "global_pool": (pbool, False), "stride": (ptuple, ()),
                 "pad": (ptuple, ()), "pooling_convention": (pstr, "valid")},
         aliases=("Pooling_v1",), hint="pooling")


# ---------------------------------------------------------------------------
# Activation / LeakyReLU / SoftmaxActivation
# ---------------------------------------------------------------------------
_ACTS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
}


def _activation(attrs, inputs, aux, is_train, rng):
    return [_ACTS[attrs["act_type"]](inputs[0])]


register("Activation", _activation,
         params={"act_type": (pstr, REQUIRED)}, hint="activation")


def _leaky_relu(attrs, inputs, aux, is_train, rng):
    x = inputs[0]
    t = attrs["act_type"]
    if t == "leaky":
        return [jnp.where(x > 0, x, attrs["slope"] * x)]
    if t == "elu":
        return [jnp.where(x > 0, x, attrs["slope"] * jnp.expm1(x))]
    if t == "prelu":
        gamma = inputs[1].reshape((1, -1) + (1,) * (x.ndim - 2))
        return [jnp.where(x > 0, x, gamma * x)]
    if t == "rrelu":
        lo, up = attrs["lower_bound"], attrs["upper_bound"]
        if is_train:
            slope = jax.random.uniform(rng, x.shape, dtype=x.dtype,
                                       minval=lo, maxval=up)
        else:
            slope = jnp.asarray((lo + up) / 2.0, x.dtype)
        return [jnp.where(x > 0, x, slope * x)]
    raise MXNetError("LeakyReLU: bad act_type %r" % t)


register("LeakyReLU", _leaky_relu,
         arguments=lambda a: ["data", "gamma"] if a["act_type"] == "prelu"
         else ["data"],
         params={"act_type": (pstr, "leaky"), "slope": (pfloat, 0.25),
                 "lower_bound": (pfloat, 0.125), "upper_bound": (pfloat, 0.334)},
         needs_rng=True, hint="leakyrelu")


def _softmax_activation(attrs, inputs, aux, is_train, rng):
    x = inputs[0]
    if attrs["mode"] == "channel":
        return [jax.nn.softmax(x, axis=1)]
    return [jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)]


register("SoftmaxActivation", _softmax_activation,
         params={"mode": (pstr, "instance")}, hint="softmaxactivation")


# ---------------------------------------------------------------------------
# BatchNorm — reference ``batch_norm-inl.h`` / cudnn_batch_norm.
# aux moving_mean/moving_var updated in train mode (functional aux-update).
# ---------------------------------------------------------------------------
def _batch_norm(attrs, inputs, aux, is_train, rng, act_type=None):
    """``act_type="relu"`` applies the relu here, as ``jnp.maximum(out,
    0)``: set only by the executor's BN->ReLU peephole (the registered op
    always passes None)."""
    x, gamma, beta = inputs
    moving_mean, moving_var = aux
    red = (0,) + tuple(range(2, x.ndim))
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    use_batch = is_train and not attrs["use_global_stats"]
    if use_batch:
        # Stats ACCUMULATE in f32 always; what varies is the dtype of the
        # elementwise read pass.  For bf16 activations the read stays
        # bf16: materializing x.astype(f32) made XLA emit a second
        # full-size f32 copy of every conv output as a fusion epilogue
        # (+wider reduce reads): pure bandwidth, paid at every
        # BatchNorm of a ResNet step.
        # The probe-shift below
        # bounds the bf16 rounding of d to ~2^-8 relative of the
        # *deviation*, and round-to-nearest is unbiased, so the
        # batch-mean/var error vanishes as 1/sqrt(N) — validated by the
        # bf16 convergence-parity harness.
        xf = x if x.dtype == jnp.bfloat16 else x.astype(jnp.float32)
        # shifted single-pass variance: center on a per-channel probe
        # (first element, gradient-stopped — the shifts cancel exactly in
        # mean and var) so E[d^2]-E[d]^2 cancels catastrophically only
        # when |mean-probe| >> std, not |mean| >> std (raw 0-255 inputs)
        probe = jax.lax.stop_gradient(
            xf[(0, slice(None)) + (0,) * (x.ndim - 2)])
        d = xf - probe.reshape(bshape)
        cnt = 1
        for ax in red:
            cnt *= x.shape[ax]
        mean_d = jnp.sum(d, axis=red, dtype=jnp.float32) / cnt
        sq = jnp.sum(jnp.square(d.astype(jnp.float32)), axis=red) / cnt
        var = jnp.maximum(sq - jnp.square(mean_d), 0.0)
        mean = mean_d + probe.astype(jnp.float32)
    else:
        mean, var = moving_mean, moving_var
    g = jnp.ones_like(gamma) if attrs["fix_gamma"] else gamma
    scale = (g.astype(jnp.float32)
             * jax.lax.rsqrt(var + attrs["eps"])).astype(x.dtype)
    shift = (beta.astype(jnp.float32)
             - mean * scale.astype(jnp.float32)).astype(x.dtype)
    out = x * scale.reshape(bshape) + shift.reshape(bshape)
    if act_type == "relu":
        out = jnp.maximum(out, 0)
    outs = [out, mean, var] if attrs["output_mean_var"] else [out]
    if use_batch:
        m = attrs["momentum"]
        new_mean = moving_mean * m + jax.lax.stop_gradient(mean) * (1 - m)
        new_var = moving_var * m + jax.lax.stop_gradient(var) * (1 - m)
        return outs, [new_mean, new_var]
    return outs, None


register("BatchNorm", _batch_norm,
         arguments=("data", "gamma", "beta"),
         aux_states=("moving_mean", "moving_var"),
         outputs=lambda a: ["output", "mean", "var"] if a["output_mean_var"]
         else ["output"],
         params={"eps": (pfloat, 1e-3), "momentum": (pfloat, 0.9),
                 "fix_gamma": (pbool, True), "use_global_stats": (pbool, False),
                 "output_mean_var": (pbool, False)},
         aliases=("CuDNNBatchNorm",), hint="batchnorm")


def _instance_norm(attrs, inputs, aux, is_train, rng):
    x, gamma, beta = inputs
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    out = (x - mean) * jax.lax.rsqrt(var + attrs["eps"])
    return [out * gamma.reshape(bshape) + beta.reshape(bshape)]


register("InstanceNorm", _instance_norm, arguments=("data", "gamma", "beta"),
         params={"eps": (pfloat, 1e-3)}, hint="instancenorm")


def _l2_normalization(attrs, inputs, aux, is_train, rng):
    x = inputs[0]
    mode, eps = attrs["mode"], attrs["eps"]
    if mode == "instance":
        red, keep = tuple(range(1, x.ndim)), True
    elif mode == "channel":
        red, keep = (1,), True
    elif mode == "spatial":
        red, keep = tuple(range(2, x.ndim)), True
    else:
        raise MXNetError("L2Normalization: bad mode %r" % mode)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=red, keepdims=keep) + eps)
    return [x / norm]


register("L2Normalization", _l2_normalization,
         params={"eps": (pfloat, 1e-10), "mode": (pstr, "instance")},
         hint="l2normalization")


def _lrn(attrs, inputs, aux, is_train, rng):
    x = inputs[0]
    n = attrs["nsize"]
    sq = jnp.square(x)
    half = n // 2
    win = (1, n) + (1,) * (x.ndim - 2)
    pad = ((0, 0), (half, n - 1 - half)) + ((0, 0),) * (x.ndim - 2)
    ssum = jax.lax.reduce_window(sq, 0.0, jax.lax.add,
                                 win, (1,) * x.ndim, pad)
    scale = attrs["knorm"] + (attrs["alpha"] / n) * ssum
    return [x * jnp.power(scale, -attrs["beta"])]


register("LRN", _lrn,
         params={"alpha": (pfloat, 1e-4), "beta": (pfloat, 0.75),
                 "knorm": (pfloat, 2.0), "nsize": (pint, REQUIRED)}, hint="lrn")


# ---------------------------------------------------------------------------
# Dropout — needs rng; identity at inference (reference ``dropout-inl.h``)
# ---------------------------------------------------------------------------
def _dropout(attrs, inputs, aux, is_train, rng):
    x = inputs[0]
    p = attrs["p"]
    if not is_train or p <= 0.0:
        return [x]
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return [jnp.where(mask, x / keep, jnp.zeros_like(x))]


register("Dropout", _dropout, params={"p": (pfloat, 0.5)}, needs_rng=True,
         hint="dropout")


# ---------------------------------------------------------------------------
# Concat / SliceChannel / Pad / UpSampling / Sequence ops
# ---------------------------------------------------------------------------
def _concat(attrs, inputs, aux, is_train, rng):
    return [jnp.concatenate(inputs, axis=attrs["dim"])]


register("Concat", _concat,
         arguments=lambda a: ["arg%d" % i for i in range(a["num_args"])],
         params={"num_args": (pint, REQUIRED), "dim": (pint, 1)},
         key_var_num_args="num_args", aliases=("concat",), hint="concat")


def _slice_channel(attrs, inputs, aux, is_train, rng):
    x = inputs[0]
    parts = jnp.split(x, attrs["num_outputs"], axis=attrs["axis"])
    if attrs["squeeze_axis"]:
        parts = [jnp.squeeze(p, axis=attrs["axis"]) for p in parts]
    return list(parts)


register("SliceChannel", _slice_channel,
         outputs=lambda a: ["output%d" % i for i in range(a["num_outputs"])],
         params={"num_outputs": (pint, REQUIRED), "axis": (pint, 1),
                 "squeeze_axis": (pbool, False)},
         aliases=("split",), hint="slicechannel")


def _pad(attrs, inputs, aux, is_train, rng):
    x = inputs[0]
    pw = attrs["pad_width"]
    pads = [(pw[2 * i], pw[2 * i + 1]) for i in range(x.ndim)]
    mode = attrs["mode"]
    if mode == "constant":
        return [jnp.pad(x, pads, constant_values=attrs["constant_value"])]
    return [jnp.pad(x, pads, mode={"edge": "edge", "reflect": "reflect"}[mode])]


register("Pad", _pad,
         params={"mode": (pstr, "constant"), "pad_width": (ptuple, REQUIRED),
                 "constant_value": (pfloat, 0.0)},
         aliases=("pad",), hint="pad")


def _upsampling(attrs, inputs, aux, is_train, rng):
    s = attrs["scale"]
    if attrs["sample_type"] == "nearest":
        outs = []
        for x in inputs:
            r = jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3)
            outs.append(r)
        if len(outs) == 1:
            return [outs[0]]
        return [jnp.concatenate(outs, axis=1)]
    # bilinear: reference uses an internal Deconvolution with a learnable
    # kernel (data, weight); XLA-native resize is used for the interpolation.
    x = inputs[0]
    new = x.shape[:2] + (x.shape[2] * s, x.shape[3] * s)
    return [jax.image.resize(x, new, method="bilinear")]


register("UpSampling", _upsampling,
         arguments=lambda a: (["arg%d" % i for i in range(a["num_args"])]
                              if a["sample_type"] == "nearest"
                              else ["data", "weight"]),
         params={"scale": (pint, REQUIRED), "num_filter": (pint, 0),
                 "sample_type": (pstr, REQUIRED), "multi_input_mode": (pstr, "concat"),
                 "num_args": (pint, 1), "workspace": (pint, 512)},
         key_var_num_args="num_args", hint="upsampling")


# Sequence ops (time-major (T, N, ...), reference ``sequence_*-inl.h``)
def _seq_args(a):
    return ["data", "sequence_length"] if a["use_sequence_length"] else ["data"]


def _sequence_last(attrs, inputs, aux, is_train, rng):
    x = inputs[0]
    if attrs["use_sequence_length"]:
        idx = (inputs[1].astype(jnp.int32) - 1).clip(0, x.shape[0] - 1)
        return [jnp.take_along_axis(
            x, idx.reshape((1, -1) + (1,) * (x.ndim - 2)), axis=0)[0]]
    return [x[-1]]


register("SequenceLast", _sequence_last, arguments=_seq_args,
         params={"use_sequence_length": (pbool, False)}, hint="sequencelast")


def _seq_mask_array(x, seqlen):
    t = x.shape[0]
    steps = jnp.arange(t).reshape((t, 1))
    return steps < seqlen.astype(jnp.int32).reshape((1, -1))


def _sequence_mask(attrs, inputs, aux, is_train, rng):
    x = inputs[0]
    if not attrs["use_sequence_length"]:
        return [x]
    mask = _seq_mask_array(x, inputs[1]).reshape(
        x.shape[:2] + (1,) * (x.ndim - 2))
    return [jnp.where(mask, x, jnp.asarray(attrs["value"], x.dtype))]


register("SequenceMask", _sequence_mask, arguments=_seq_args,
         params={"use_sequence_length": (pbool, False), "value": (pfloat, 0.0)},
         hint="sequencemask")


def _sequence_reverse(attrs, inputs, aux, is_train, rng):
    x = inputs[0]
    if not attrs["use_sequence_length"]:
        return [jnp.flip(x, axis=0)]
    t = x.shape[0]
    seqlen = inputs[1].astype(jnp.int32).reshape((1, -1))
    steps = jnp.arange(t).reshape((t, 1))
    src = jnp.where(steps < seqlen, seqlen - 1 - steps, steps)
    src = src.reshape(x.shape[:2] + (1,) * (x.ndim - 2))
    src = jnp.broadcast_to(src, x.shape)
    return [jnp.take_along_axis(x, src, axis=0)]


register("SequenceReverse", _sequence_reverse, arguments=_seq_args,
         params={"use_sequence_length": (pbool, False)}, hint="sequencereverse")
