"""Plain reference of ResNet (v2, pre-activation) training, family
``module_fit``: forward pass, softmax cross-entropy, gradients and the
SGD-with-momentum update in straightforward ``jax.numpy`` and
``lax.conv_general_dilated``, float32.

Written from He et al. (arXiv:1603.05027) and the semantics MXNet's
``symbols/resnet.py`` and ``SGD`` publish, not from the program:

* BatchNorm in training mode normalises with the batch's own mean and
  biased variance over (N, H, W), eps 2e-5; the input's BatchNorm has its
  gain fixed at 1 (``fix_gamma``);
* the loss layer's gradient is ``softmax - onehot`` per row, and the
  optimizer scales the summed gradient by 1 / batch;
* ``mom = momentum * mom - lr * (grad + wd * w)``, ``w += mom``, with weight
  decay on names ending ``_weight`` or ``_gamma`` only.

It imports nothing of ``mxnet_tpu`` and takes nothing the program made: the
weights come from :func:`init_weights` and the batch from :func:`make_batch`,
pure functions of the seed that the harness also hands to the program.
"""

import functools
import math

import jax
import jax.numpy as jnp

BN_EPS = 2e-5


def _unit_names(config):
    for stage, n_units in enumerate(config["units"], 1):
        for unit in range(1, n_units + 1):
            yield stage, unit, "stage%d_unit%d" % (stage, unit)


def param_shapes(config):
    """Name -> shape of every trained parameter, in the order the network
    uses them."""
    filters = config["filter_list"]
    chans = config["image_shape"][0]
    shapes = {"bn_data_gamma": (chans,), "bn_data_beta": (chans,),
              "conv0_weight": (filters[0], chans, 7, 7),
              "bn0_gamma": (filters[0],), "bn0_beta": (filters[0],)}
    width = filters[0]
    for stage, unit, name in _unit_names(config):
        out = filters[stage]
        mid = out // 4
        convs = [("conv1", (mid, width, 1, 1)), ("conv2", (mid, mid, 3, 3)),
                 ("conv3", (out, mid, 1, 1))]
        for i, (conv, shape) in enumerate(convs, 1):
            shapes["%s_bn%d_gamma" % (name, i)] = (shape[1],)
            shapes["%s_bn%d_beta" % (name, i)] = (shape[1],)
            shapes["%s_%s_weight" % (name, conv)] = shape
        if unit == 1:
            shapes[name + "_sc_weight"] = (out, width, 1, 1)
        width = out
    shapes["bn1_gamma"] = (width,)
    shapes["bn1_beta"] = (width,)
    shapes["fc1_weight"] = (config["num_classes"], width)
    shapes["fc1_bias"] = (config["num_classes"],)
    return shapes


def aux_shapes(config):
    """Name -> shape of the BatchNorm running statistics (the program keeps
    them; training never reads them)."""
    out = {}
    for name, shape in param_shapes(config).items():
        if name.endswith("_gamma"):
            stem = name[:-len("_gamma")]
            out[stem + "_moving_mean"] = shape
            out[stem + "_moving_var"] = shape
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _init(shapes, key):
    out = {}
    for i, (name, shape) in enumerate(shapes):
        if name.endswith("_weight"):
            # Xavier, gaussian, fan-in, magnitude 2 (common/fit.py's)
            fan_in = math.prod(shape[1:])
            out[name] = jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32) \
                * math.sqrt(2.0 / fan_in)
        elif name.endswith("_gamma"):
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = jnp.zeros(shape, jnp.float32)
    return out


def _key(seed, stream):
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                             seed // (2 ** 31))
    return jax.random.fold_in(key, stream)


def init_weights(config, seed, device):
    """The float32 parameters, drawn on ``device`` in one jitted call."""
    with jax.default_device(device):
        return _init(tuple(param_shapes(config).items()), _key(seed, 0))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _batch(shape, num_classes, key):
    k1, k2 = jax.random.split(key)
    return (jax.random.uniform(k1, shape, jnp.float32, -1.0, 1.0),
            jax.random.randint(k2, shape[:1], 0, num_classes))


def make_batch(config, batch, seed, device):
    """One batch of rows that all differ, uniform in [-1, 1), and its
    labels, drawn on ``device`` from the seed."""
    with jax.default_device(device):
        return _batch((batch,) + tuple(config["image_shape"]),
                      config["num_classes"], _key(seed, 1))


def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bn(x, gamma, beta):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=(0, 2, 3), keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + BN_EPS)
    return (y * gamma[None, :, None, None]
            + beta[None, :, None, None]).astype(x.dtype)


def _relu(x):
    return jnp.maximum(x, 0)


def forward(config, params, data):
    """Logits ``(N, num_classes)`` of ``data (N, C, H, W)``, training-mode
    BatchNorm, computed in the dtype of ``data``."""
    p = params
    x = _bn(data, jnp.ones_like(p["bn_data_gamma"]), p["bn_data_beta"])
    x = _conv(x, p["conv0_weight"], 2, 3)
    x = _relu(_bn(x, p["bn0_gamma"], p["bn0_beta"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
    for stage, unit, name in _unit_names(config):
        stride = 2 if (unit == 1 and stage > 1) else 1
        act1 = _relu(_bn(x, p[name + "_bn1_gamma"], p[name + "_bn1_beta"]))
        y = _conv(act1, p[name + "_conv1_weight"], 1, 0)
        y = _relu(_bn(y, p[name + "_bn2_gamma"], p[name + "_bn2_beta"]))
        y = _conv(y, p[name + "_conv2_weight"], stride, 1)
        y = _relu(_bn(y, p[name + "_bn3_gamma"], p[name + "_bn3_beta"]))
        y = _conv(y, p[name + "_conv3_weight"], 1, 0)
        if unit == 1:
            x = _conv(act1, p[name + "_sc_weight"], stride, 0)
        x = y + x
    x = _relu(_bn(x, p["bn1_gamma"], p["bn1_beta"]))
    x = jnp.mean(x.astype(jnp.float32), axis=(2, 3)).astype(x.dtype)
    return (x @ p["fc1_weight"].astype(x.dtype).T
            + p["fc1_bias"].astype(x.dtype)).astype(jnp.float32)


def _loss_sum(config, params, data, labels):
    logp = jax.nn.log_softmax(forward(config, params, data), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def _step(config_key, optimizer_key, dtype, w, mom, data, labels):
    """One SGD step: (mean loss, the gradient's per-leaf norms as the
    optimizer gets it, new weights, new momentum)."""
    config = dict(config_key)
    opt = dict(optimizer_key)
    lr, momentum, wd = opt["learning_rate"], opt["momentum"], opt["wd"]
    batch = data.shape[0]
    loss, grad = jax.value_and_grad(
        lambda q: _loss_sum(config, q, data.astype(dtype), labels))(w)
    grad = {k: g / batch for k, g in grad.items()}
    new_w, new_mom = {}, {}
    for k in w:
        decay = wd if k.endswith(("_weight", "_gamma")) else 0.0
        m = momentum * mom[k] - lr * (grad[k] + decay * w[k])
        new_mom[k] = m.astype(dtype)
        new_w[k] = (w[k] + m).astype(dtype)
    return loss / batch, _norms(grad), new_w, new_mom


_step_jit = jax.jit(_step, static_argnums=(0, 1, 2))


@jax.jit
def _moved(w, w0):
    return _norms({k: w[k].astype(jnp.float32) - w0[k] for k in w})


def _hashable(d):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()
                        if isinstance(v, (int, float, str, bool, list))))


def train_steps(config, optimizer, n_steps, params, data, labels,
                lower_precision=False):
    """Follow ``n_steps`` SGD steps on the one batch.  Returns the mean
    cross-entropy of each step, the norm of every parameter's first
    gradient as the optimizer gets it (summed over the batch, times
    1 / batch), and the norm of every parameter's change after the steps.

    ``lower_precision`` is the control: parameters, activations and the
    momentum held in bfloat16, the nearest precision below the float32
    the configuration states; the reference itself is float32 at
    ``highest`` matmul precision."""
    dtype = jnp.bfloat16 if lower_precision else jnp.float32
    precision = None if lower_precision else "highest"
    keys = (_hashable(config), _hashable(optimizer), dtype)
    w = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    mom = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad = [], None
    with jax.default_matmul_precision(precision):
        for step in range(n_steps):
            loss, grad_norms, w, mom = _step_jit(*keys, w, mom, data,
                                                 labels)
            losses.append(loss)
            if step == 0:
                first_grad = grad_norms
    return jnp.stack(losses), first_grad, _moved(w, params)
