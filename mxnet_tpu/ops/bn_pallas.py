"""Fused train-mode BatchNorm as Pallas TPU kernels.

Reference: ``src/operator/batch_norm-inl.h`` / ``cudnn_batch_norm*`` compute
batch statistics with cuDNN's fused kernel; on TPU the XLA lowering of the
same math costs three HBM passes over the activation in the forward
(stats read, normalize read, write) and five in the backward (stats-grad
reads of x and dy, dx reads of x and dy, dx write).  At ResNet-50 b128 the
measured cost of batch statistics is ~26% of the whole training step
(``MXNET_BN_ABLATION=frozen`` ablation) — BN is the bandwidth hot spot the
round-2 profile pointed at.

These kernels cut the passes to the minimum:

* forward: ONE read of ``x`` — the per-lane-group block lives in VMEM,
  stats (f32, two-pass mean/variance) and normalization [+ optional fused
  ReLU] happen in-register, one write of ``y``.
* backward: ONE read each of ``x`` and ``dy`` — dgamma/dbeta reductions
  and the dx formula share the same VMEM residency, one write of ``dx``.

Layout (the part that actually matters on TPU): XLA assigns conv
activations a FEATURE-MINOR layout — ``bf16[N,C,H,W]{1,0,3,2}``, i.e.
physically ``[H][W][N][C]`` with the (8,128) tile on (N,C).  A Pallas
operand is constrained to the default row-major layout of its logical
shape, so a kernel over the logical NCHW (or a (N,C,S) flatten) forces a
relayout COPY of every activation in and out — measured net SLOWER than
no kernel at all.  Instead the wrapper views x as ``(H*W, N, C)`` via
transpose+reshape, whose row-major layout IS the physical layout: XLA
elides every copy (verified: zero ``copy`` ops in the compiled module).

The channel axis (lanes) is the grid: block = (S, N, L) with L = C when
C <= 128, else 128 (C must divide into 128-lane groups).  S and N stay
whole so each grid step owns its lanes' complete statistics.  Blocks are
admitted while S*N*L*itemsize fits MXNET_BN_PALLAS_BLOCK_BYTES (default
8 MB — ResNet stages at 14x14/7x7; the 56x56/28x28 stages exceed VMEM for
a 128-lane group and fall back to the XLA path).

Mosaic notes for this toolchain: 4D blocks with multi-axis reductions
SIGABRT the compiler, and in-kernel reshape of a loaded 4D vector is
unsupported — hence the 3D view with lane-preserving reductions over
(sublane, major) axes only, which compiles and runs.

The public entry is :func:`bn_train`, a ``jax.custom_vjp`` whose forward
returns ``(y, mean, var)``.  The mean/var outputs exist for the moving-stat
update, which the caller wraps in ``stop_gradient`` — the backward ignores
their (symbolically zero) cotangents.

Used by ``ops/nn.py`` ``_batch_norm`` (plain) and by the executor's
BN->ReLU peephole (``executor.py`` ``_graph_forward_plain``), which fuses
the activation into the kernel so the ReLU costs zero extra passes.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

# byte budget for one (S, N, L) input block; the kernels hold the block
# plus an f32 working set (~5x in the backward), which must clear the
# scoped-VMEM ceiling below
_BLOCK_BUDGET = int(os.environ.get("MXNET_BN_PALLAS_BLOCK_BYTES",
                                   str(8 * 1024 * 1024)))
# scoped-VMEM ceiling for the kernels (the toolchain default of 16 MB is
# too small for an 8 MB block plus its f32 working set)
_VMEM_LIMIT = int(os.environ.get("MXNET_BN_PALLAS_VMEM_BYTES",
                                 str(100 * 1024 * 1024)))


def _lane_group(c):
    """Lane-block size: full C up to 128 lanes, else 128-lane groups."""
    if c <= 128:
        return c
    return 128 if c % 128 == 0 else None


def _refusal(n, c, s, itemsize):
    """Why a shape cannot take the kernel (``"tile"``: C does not divide
    into 128-lane groups; ``"vmem"``: one block exceeds the budget), or
    None when it can."""
    lg = _lane_group(c)
    if lg is None:
        return "tile"
    if s * n * lg * itemsize > _BLOCK_BUDGET:
        return "vmem"
    return None


def _bn_fwd_kernel(x_ref, gamma_ref, beta_ref, y_ref, mean_ref, var_ref, *,
                   eps, fix_gamma, relu):
    xf = x_ref[...].astype(jnp.float32)            # (S, N, L)
    m = xf.shape[0] * xf.shape[1]
    mean = jnp.sum(xf, axis=(0, 1)) / m            # (L,)
    # two-pass variance: the block is already in VMEM, so the second pass
    # is free of HBM traffic and avoids E[x^2]-E[x]^2 cancellation
    ctr = xf - mean[None, None, :]
    var = jnp.sum(ctr * ctr, axis=(0, 1)) / m
    rstd = jax.lax.rsqrt(var + eps)
    if fix_gamma:
        scale = rstd
    else:
        scale = gamma_ref[0].astype(jnp.float32) * rstd
    shift = beta_ref[0].astype(jnp.float32) - mean * scale
    y = xf * scale[None, None, :] + shift[None, None, :]
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[0] = mean
    var_ref[0] = var


def _bn_bwd_kernel(x_ref, g_ref, mean_ref, var_ref, gamma_ref, beta_ref,
                   dx_ref, dgamma_ref, dbeta_ref, *, eps, fix_gamma, relu):
    xf = x_ref[...].astype(jnp.float32)            # (S, N, L)
    gf = g_ref[...].astype(jnp.float32)
    m = xf.shape[0] * xf.shape[1]
    mean = mean_ref[0]
    rstd = jax.lax.rsqrt(var_ref[0] + eps)
    xhat = (xf - mean[None, None, :]) * rstd[None, None, :]
    if fix_gamma:
        gamma = jnp.ones_like(mean)
    else:
        gamma = gamma_ref[0].astype(jnp.float32)
    if relu:
        # recompute the relu mask from the saved stats instead of saving
        # (or re-reading) the activation output
        shift = beta_ref[0].astype(jnp.float32) - mean * gamma * rstd
        pre = xf * (gamma * rstd)[None, None, :] + shift[None, None, :]
        gf = jnp.where(pre > 0.0, gf, 0.0)
    dbeta = jnp.sum(gf, axis=(0, 1))               # (L,)
    dgamma = jnp.sum(gf * xhat, axis=(0, 1))
    k = (gamma * rstd)[None, None, :]
    dx = k * (gf - dbeta[None, None, :] / m
              - xhat * dgamma[None, None, :] / m)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dgamma_ref[0] = dgamma
    dbeta_ref[0] = dbeta


def _pallas_mode():
    # Default OFF: benchmarked END-TO-END SLOWER than the XLA path on
    # ResNet-50 b128 (2081 vs 2215 img/s) — the eligible mid/late stages
    # lane-block a feature-minor array, so each (S, N, 128-lane) block is
    # a strided HBM read (256B bursts out of 2048B rows), and the early
    # stages don't fit a full-C block in VMEM at all.  XLA's own schedule
    # (2R+1W fwd, 4R+1W bwd, reductions fused multi-output) is already at
    # the streaming lower bound for HBM-resident activations.  Kept as an
    # opt-in ("1"/"auto") for toolchains/shapes where the tradeoff
    # differs, and "interpret" for CPU tests of the kernel math.
    return os.environ.get("MXNET_BN_PALLAS", "0")


def eligible(x):
    """Whether the Pallas path applies for this input (trace-time).

    ``MXNET_BN_PALLAS=1``/``auto`` asks for the compiled kernel, which
    exists on a TPU trace only; ``interpret`` asks for the Pallas
    interpreter (the CPU tests of the kernel math) and nothing else ever
    selects it.  A request that cannot be met takes the XLA path and is
    counted with its reason (``ops.kernel_path``)."""
    from .registry import count_kernel_path, on_tpu

    mode = _pallas_mode()
    if mode not in ("1", "auto", "interpret"):
        return False
    if x.ndim < 2:
        reason = "tile"
    else:
        s = 1
        for d in x.shape[2:]:
            s *= d
        reason = _refusal(x.shape[0], x.shape[1], s, x.dtype.itemsize)
    if reason is None and mode != "interpret" and not on_tpu():
        reason = "not_tpu"
    if reason is not None:
        count_kernel_path("BatchNorm", "xla", reason)
        return False
    count_kernel_path("BatchNorm",
                      "interpret" if mode == "interpret" else "pallas", "ok")
    return True


def _bn_fwd_call(xt, gamma2, beta2, eps, fix_gamma, relu, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, n, c = xt.shape
    lg = _lane_group(c)   # the caller checked eligible()
    kernel = functools.partial(_bn_fwd_kernel, eps=eps,
                               fix_gamma=fix_gamma, relu=relu)
    y, mean, var = pl.pallas_call(
        kernel,
        grid=(c // lg,),
        in_specs=[
            pl.BlockSpec((s, n, lg), lambda i: (0, 0, i)),
            pl.BlockSpec((1, lg), lambda i: (0, i)),
            pl.BlockSpec((1, lg), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((s, n, lg), lambda i: (0, 0, i)),
            pl.BlockSpec((1, lg), lambda i: (0, i)),
            pl.BlockSpec((1, lg), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, n, c), xt.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(xt, gamma2, beta2)
    return y, mean, var


def _bn_bwd_call(xt, gt, mean2, var2, gamma2, beta2, eps, fix_gamma, relu,
                 interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, n, c = xt.shape
    lg = _lane_group(c)   # the caller checked eligible()
    kernel = functools.partial(_bn_bwd_kernel, eps=eps,
                               fix_gamma=fix_gamma, relu=relu)
    dx, dgamma, dbeta = pl.pallas_call(
        kernel,
        grid=(c // lg,),
        in_specs=[
            pl.BlockSpec((s, n, lg), lambda i: (0, 0, i)),
            pl.BlockSpec((s, n, lg), lambda i: (0, 0, i)),
            pl.BlockSpec((1, lg), lambda i: (0, i)),
            pl.BlockSpec((1, lg), lambda i: (0, i)),
            pl.BlockSpec((1, lg), lambda i: (0, i)),
            pl.BlockSpec((1, lg), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((s, n, lg), lambda i: (0, 0, i)),
            pl.BlockSpec((1, lg), lambda i: (0, i)),
            pl.BlockSpec((1, lg), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, n, c), xt.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(xt, gt, mean2, var2, gamma2, beta2)
    return dx, dgamma, dbeta


@functools.lru_cache(maxsize=None)
def _bn_fused_fn(eps, fix_gamma, relu, interpret):
    @jax.custom_vjp
    def f(xt, gamma2, beta2):
        return _bn_fwd_call(xt, gamma2, beta2, eps, fix_gamma, relu,
                            interpret)

    def fwd(xt, gamma2, beta2):
        y, mean, var = _bn_fwd_call(xt, gamma2, beta2, eps, fix_gamma,
                                    relu, interpret)
        return (y, mean, var), (xt, gamma2, beta2, mean, var)

    def bwd(res, cts):
        xt, gamma2, beta2, mean, var = res
        gy, _gmean, _gvar = cts
        # mean/var feed only the stop_gradient'd moving-stat update — their
        # cotangents are symbolically zero (the caller guarantees this by
        # excluding output_mean_var graphs from the Pallas path)
        dx, dgamma, dbeta = _bn_bwd_call(
            xt, gy, mean, var, gamma2, beta2, eps, fix_gamma, relu,
            interpret)
        if fix_gamma:
            dgamma = jnp.zeros_like(dgamma)
        return (dx, dgamma.astype(gamma2.dtype),
                dbeta.astype(beta2.dtype))

    f.defvjp(fwd, bwd)
    return f


def bn_train(x, gamma, beta, eps, fix_gamma, relu=False):
    """Fused train-mode BN over NC[spatial] ``x``; returns
    ``(y, mean, var)`` with mean/var of shape (C,).  Caller must have
    checked :func:`eligible`.

    The kernel sees the layout-native (S, N, C) view (see module
    docstring); the transpose/reshape pair on each side is a bitcast
    against the activations' physical feature-minor layout, so no data
    moves outside the kernel itself.
    """
    n, c = x.shape[0], x.shape[1]
    spatial_axes = tuple(range(2, x.ndim))
    s = 1
    for d in x.shape[2:]:
        s *= d
    xt = x.transpose(spatial_axes + (0, 1)).reshape(s, n, c)
    interpret = _pallas_mode() == "interpret"
    f = _bn_fused_fn(float(eps), bool(fix_gamma), bool(relu), interpret)
    y, mean, var = f(xt, gamma.reshape(1, c), beta.reshape(1, c))
    y = y.reshape(x.shape[2:] + (n, c)).transpose(
        (x.ndim - 2, x.ndim - 1) + tuple(range(x.ndim - 2)))
    return y, mean.reshape(c), var.reshape(c)
