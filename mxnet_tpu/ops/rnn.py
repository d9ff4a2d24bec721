"""Fused multi-layer RNN op — the cuDNN-RNN analog.

Reference: ``src/operator/rnn-inl.h`` (CPU unfused LSTM/GRU) and
``src/operator/cudnn_rnn-inl.h:22`` (fused ``cudnnRNNForwardTraining``,
one opaque parameter blob, modes rnn_relu/rnn_tanh/lstm/gru, multi-layer,
bidirectional, inter-layer dropout).

TPU-native design (NOT a kernel translation):

* The input projection of a whole layer is ONE large matmul over the full
  ``(T*N, I)`` activation — that is where the FLOPs are and it tiles onto
  the MXU; only the ``h @ Wh`` recurrence runs inside ``lax.scan`` (static
  trip count, compiler-friendly control flow, no per-step Python).
* Bidirectional = the same scan over a time-flipped copy, outputs
  concatenated on the feature axis.
* Parameter blob layout (this framework's canonical layout — simpler than
  cuDNN's all-weights-then-all-biases split): for each layer, for each
  direction: ``[Wx (G*H, I), Wh (G*H, H), bx (G*H), bh (G*H)]`` flattened
  and concatenated.  ``rnn.FusedRNNCell.unpack_weights`` slices it.
* Gate order: LSTM ``i, f, g, o``; GRU ``r, z, n`` — shared with the
  unfused ``mx.rnn`` cells so fused/unfused weights interchange.

Data layout is time-major ``(T, N, C)`` like the reference RNN op.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import MXNetError
from .registry import REQUIRED, pbool, pfloat, pint, pstr, ptuple, register

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def rnn_param_size(input_size, state_size, num_layers, mode,
                   bidirectional=False):
    """Total length of the flat parameter blob (python int, static)."""
    g = _GATES[mode]
    d = 2 if bidirectional else 1
    h = state_size
    total = 0
    for layer in range(num_layers):
        i = input_size if layer == 0 else h * d
        total += d * (g * h * i + g * h * h + 2 * g * h)
    return total


def _layer_param_slices(input_size, state_size, num_layers, mode,
                        bidirectional):
    """Yields (layer, direction, offsets dict) describing the blob layout."""
    g = _GATES[mode]
    d = 2 if bidirectional else 1
    h = state_size
    off = 0
    out = []
    for layer in range(num_layers):
        i = input_size if layer == 0 else h * d
        for direction in range(d):
            sl = {}
            sl["wx"] = (off, (g * h, i)); off += g * h * i
            sl["wh"] = (off, (g * h, h)); off += g * h * h
            sl["bx"] = (off, (g * h,)); off += g * h
            sl["bh"] = (off, (g * h,)); off += g * h
            out.append((layer, direction, sl))
    return out


def _take(params, spec):
    off, shape = spec
    n = 1
    for s in shape:
        n *= s
    return jax.lax.dynamic_slice_in_dim(params, off, n).reshape(shape)


def _scan_layer(x, wx, wh, bx, bh, h0, c0, mode):
    """One direction of one layer. x: (T, N, I) -> (T, N, H)."""
    xproj = jnp.einsum("tni,gi->tng", x, wx) + bx  # one big MXU matmul

    if mode == "lstm":
        def step(carry, xp):
            h, c = carry
            gates = xp + jnp.dot(h, wh.T) + bh
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        (h, c), ys = jax.lax.scan(step, (h0, c0), xproj)
        return ys, h, c

    if mode == "gru":
        def step(h, xp):
            hproj = jnp.dot(h, wh.T) + bh
            xr, xz, xn = jnp.split(xp, 3, axis=-1)
            hr, hz, hn = jnp.split(hproj, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h = (1.0 - z) * n + z * h
            return h, h

        h, ys = jax.lax.scan(step, h0, xproj)
        return ys, h, None

    act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh

    def step(h, xp):
        h = act(xp + jnp.dot(h, wh.T) + bh)
        return h, h

    h, ys = jax.lax.scan(step, h0, xproj)
    return ys, h, None


def _rnn(attrs, inputs, aux, is_train, rng):
    mode = attrs["mode"]
    if mode not in _GATES:
        raise MXNetError("RNN: bad mode %r" % mode)
    lstm = mode == "lstm"
    x, params, state = inputs[0], inputs[1], inputs[2]
    state_cell = inputs[3] if lstm else None
    num_layers = attrs["num_layers"]
    h = attrs["state_size"]
    bidir = attrs["bidirectional"]
    d = 2 if bidir else 1
    p = attrs["p"]

    layout = _layer_param_slices(x.shape[2], h, num_layers, mode, bidir)
    cur = x
    hs, cs = [], []
    for layer in range(num_layers):
        if layer > 0 and is_train and p > 0.0:
            rng, sub = jax.random.split(rng)
            keep = 1.0 - p
            mask = jax.random.bernoulli(sub, keep, cur.shape)
            cur = jnp.where(mask, cur / keep, jnp.zeros_like(cur))
        outs = []
        for direction in range(d):
            sl = next(s for (l, dd, s) in layout
                      if l == layer and dd == direction)
            wx, wh = _take(params, sl["wx"]), _take(params, sl["wh"])
            bx, bh = _take(params, sl["bx"]), _take(params, sl["bh"])
            idx = layer * d + direction
            h0 = state[idx]
            c0 = state_cell[idx] if lstm else None
            xin = cur if direction == 0 else jnp.flip(cur, axis=0)
            ys, hT, cT = _scan_layer(xin, wx, wh, bx, bh, h0, c0, mode)
            if direction == 1:
                ys = jnp.flip(ys, axis=0)
            outs.append(ys)
            hs.append(hT)
            if lstm:
                cs.append(cT)
        cur = outs[0] if d == 1 else jnp.concatenate(outs, axis=-1)

    result = [cur]
    if attrs["state_outputs"]:
        result.append(jnp.stack(hs, axis=0))
        if lstm:
            result.append(jnp.stack(cs, axis=0))
    return result


def _rnn_begin_state(attrs, inputs, aux, is_train, rng):
    """Zeros of ``shape`` with the 0 entry replaced by the data batch dim.

    The reference writes ``sym.zeros(shape=(0, H))`` and lets nnvm shape
    inference fill the 0; in a traced functional graph the state must be
    *derived* from the data symbol instead — this op is how ``mx.rnn``
    cells' default ``begin_state`` stays shape-polymorphic.
    """
    data = inputs[0]
    n = data.shape[attrs["batch_axis"]]
    shape = tuple(n if s == 0 else s for s in attrs["shape"])
    return [jnp.zeros(shape, data.dtype)]


register("_rnn_begin_state", _rnn_begin_state, arguments=("data",),
         params={"shape": (ptuple, REQUIRED), "batch_axis": (pint, 0)},
         hint="rnn_begin_state")


register(
    "RNN", _rnn,
    arguments=lambda a: (["data", "parameters", "state", "state_cell"]
                         if a["mode"] == "lstm"
                         else ["data", "parameters", "state"]),
    outputs=lambda a: (["output"]
                       + (["state"] if a["state_outputs"] else [])
                       + (["state_cell"]
                          if a["state_outputs"] and a["mode"] == "lstm"
                          else [])),
    params={"state_size": (pint, REQUIRED), "num_layers": (pint, REQUIRED),
            "mode": (pstr, REQUIRED), "bidirectional": (pbool, False),
            "p": (pfloat, 0.0), "state_outputs": (pbool, False),
            "pkeep_": (pfloat, 1.0), "lstm_q_": (pbool, False)},
    needs_rng=True, hint="rnn")
