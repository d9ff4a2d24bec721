"""Plain reference of the served decoder-only LM (family ``decode_engine``).

Written from the equations, not from the program: a pre-norm block with
RMSNorm (eps 1e-6, gain only), one fused QKV projection, causal softmax
attention over ``heads`` heads of ``embed // heads``, GELU (the tanh form
GPT-2 publishes as ``gelu_new``) in a 4x feed-forward, learned positions, a
final RMSNorm and an untied output head; no biases.  No cache, no batching,
no kernels: one whole sequence per call, float32, and ``highest`` matmul
precision where the caller asks for the reference itself (on a TPU a
float32 matmul is otherwise rounded to bfloat16 passes).

It imports nothing of ``mxnet_tpu`` and takes nothing the program made: the
weights come from :func:`init_weights`, a pure function of the seed that the
harness also hands to the program.
"""

import functools
import math

import jax
import jax.numpy as jnp

#: standard deviation of every matrix, as GPT-2 initialises them; the two
#: projections that write into the residual stream are scaled by
#: 1/sqrt(2 * layers), also as published
INIT_STD = 0.02


def sizes(config):
    """(vocab, embed, heads, layers, ffn, max_len) of a config file; the
    vocabulary is the padded one the program holds."""
    return (int(config["assumed"]["vocab_padded"]), int(config["n_embd"]),
            int(config["n_head"]), int(config["n_layer"]),
            int(config["n_inner"]), int(config["n_positions"]))


@functools.partial(jax.jit, static_argnums=(0,))
def _init(shape, key):
    vocab, embed, _heads, layers, ffn, max_len = shape
    ks = jax.random.split(key, 7)
    resid = INIT_STD / math.sqrt(2.0 * layers)

    def nrm(k, s, std=INIT_STD):
        return jax.random.normal(k, s, jnp.float32) * std

    return {
        "embed": nrm(ks[0], (vocab, embed)),
        "pos": nrm(ks[1], (max_len, embed)),
        "head": nrm(ks[2], (embed, vocab)),
        "ln_f": jnp.ones((embed,), jnp.float32),
        "blocks": {
            "ln1": jnp.ones((layers, embed), jnp.float32),
            "qkv_w": nrm(ks[3], (layers, embed, 3 * embed)),
            "out_w": nrm(ks[4], (layers, embed, embed), resid),
            "ln2": jnp.ones((layers, embed), jnp.float32),
            "up_w": nrm(ks[5], (layers, embed, ffn)),
            "down_w": nrm(ks[6], (layers, ffn, embed), resid),
        },
    }


def init_weights(config, seed, device):
    """The model's float32 weights, drawn on ``device`` in one jitted call
    from ``seed`` (any whole number; folded to 32 bits for the key)."""
    with jax.default_device(device):
        key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                                 seed // (2 ** 31))
        return _init(sizes(config), key)


def _rmsnorm(x, gain):
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(ms + 1e-6).astype(x.dtype)) * gain


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward_logits(heads, params, tokens):
    """``tokens (T,) int32 -> (T, vocab) float32`` logits of one sequence,
    every position attending to itself and everything before it.  Computed
    in the dtype of ``params``."""
    (t,) = tokens.shape
    embed = params["embed"].shape[1]
    hd = embed // heads
    x = params["embed"][tokens] + params["pos"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, w):
        h = _rmsnorm(x, w["ln1"])
        q, k, v = jnp.split(h @ w["qkv_w"], 3, axis=-1)
        q, k, v = (a.reshape(t, heads, hd) for a in (q, k, v))
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        scores = jnp.where(causal[None], scores.astype(jnp.float32), -1e30)
        att = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("hqk,khd->qhd", att, v).reshape(t, embed)
        x = x + ctx @ w["out_w"]
        h = _rmsnorm(x, w["ln2"])
        return x + _gelu(h @ w["up_w"]) @ w["down_w"], None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = _rmsnorm(x, params["ln_f"])
    return (x @ params["head"]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def reference_logits(heads, params, tokens):
    """The reference itself: float32 at ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        return forward_logits(heads, params, tokens)


def _to_fp8(w):
    """Round a stack of matrices to float8 (e4m3) and back, each matrix
    with a scale of its own that puts its largest entry at e4m3's largest
    (448): what holding the weights in fp8 does to them."""
    axes = tuple(range(w.ndim - 2, w.ndim))
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 448.0
    return ((w / scale).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
            * scale.astype(jnp.bfloat16))


@functools.partial(jax.jit, static_argnums=(0, 1))
def lower_precision_argmax(heads, step, params, tokens):
    """The control: the same forward pass one precision step down, and the
    token it puts first at every position.

    ``step`` "bfloat16": weights and activations held in bfloat16, the
    nearest precision below the float32 the configuration states: the
    control that has to come out as not correct.  (The configuration's
    default matmul precision already multiplies in bfloat16 on a TPU, so
    what this step adds is the rounding of the residual stream, the norms,
    the softmax and the embedding rows.)  ``step`` "fp8": the matrices
    further rounded to float8 e4m3, one more step down: a reading kept
    beside the control."""
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    if step == "fp8":
        low = dict(low, head=_to_fp8(low["head"]), blocks=dict(
            low["blocks"], **{k: _to_fp8(low["blocks"][k])
                              for k in ("qkv_w", "out_w", "up_w",
                                        "down_w")}))
    return jnp.argmax(forward_logits(heads, low, tokens), axis=-1)


@jax.jit
def gaps_below_best(logits, chosen):
    """By how much the logit of ``chosen[i]`` lies below the largest logit
    of row ``i``: 0 where the chosen token is the reference's own."""
    took = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - took
