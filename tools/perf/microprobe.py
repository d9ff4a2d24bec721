#!/usr/bin/env python
"""Device-timed microprobes backing docs/how_to/perf.md's roofline and
PTB numbers.  Everything is measured from the TPU's own per-HLO
timestamps (a host clock around a dispatch cannot resolve microsecond
steps).

    python tools/perf/microprobe.py hbm     # streaming HBM ceiling
    python tools/perf/microprobe.py matmul  # MXU peak (8k^3 bf16)
    python tools/perf/microprobe.py ptb     # dependent-step decomposition
"""

import argparse
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _device_ps(fn, *args, category=None):
    """Device time of one traced invocation (sums `while` containers
    when present — scan children double-count — else all events)."""
    import jax

    from step_profile import load_device_events

    jax.block_until_ready(fn(*args))  # compile outside the trace
    td = tempfile.mkdtemp(prefix="microprobe_")
    jax.profiler.start_trace(td)
    jax.block_until_ready(fn(*args))
    jax.profiler.stop_trace()
    evs, _ = load_device_events(td)
    whiles = [e for e in evs
              if (e.get("args") or {}).get("hlo_category") == "while"]
    pick = whiles or evs
    if category:
        pick = [e for e in evs
                if (e.get("args") or {}).get("hlo_category") == category]
    return sum(int(e["args"].get("device_duration_ps", 0)) for e in pick)


def probe_hbm():
    """Streaming read+write ceiling: chained a = a*c + 1 over 256 MB."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    n = 256 * 1024 * 1024 // 4
    reps = 20
    x = jnp.asarray(np.random.rand(n).astype(np.float32))

    @jax.jit
    def stream(x):
        def body(a, _):
            return a * 0.999 + 1.0, None
        return jax.lax.scan(body, x, None, length=reps)[0]

    ps = _device_ps(stream, x)
    moved = reps * 2 * n * 4
    print("streaming HBM bandwidth: %.0f GB/s (%.2f ms for %.1f GB)"
          % (moved / (ps / 1e12) / 1e9, ps / 1e9, moved / 1e9))


def probe_matmul():
    """Sustained MXU rate: chained 8192^3 bf16 matmuls in one jit."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    k = 8192
    reps = 8
    # scale keeps the chained products finite without adding an
    # elementwise op to the timed loop
    a = jnp.asarray(np.random.rand(k, k).astype(np.float32) * 1e-4,
                    dtype=jnp.bfloat16)

    @jax.jit
    def chain(a):
        def body(x, _):
            return x @ a, None
        return jax.lax.scan(body, a, None, length=reps)[0]

    ps = _device_ps(chain, a)
    fl = reps * 2 * k ** 3
    print("sustained matmul: %.0f TFLOP/s (rated v5e bf16 peak 197)"
          % (fl / (ps / 1e12) / 1e12))


def probe_ptb(batch=32, hidden=200, steps=2000):
    """LSTM dependent-step decomposition (perf.md 'gate-arithmetic
    decomposition'): bare recurrence matmul, 4-gate-width matmul, full
    cell, full cell fwd+bwd — device us per dependent step."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    B, H, T = batch, hidden, steps
    rs = np.random.RandomState(0)
    h0 = jnp.asarray(rs.rand(B, H).astype(np.float32))
    c0 = jnp.asarray(rs.rand(B, H).astype(np.float32))
    W1 = jnp.asarray(rs.rand(H, H).astype(np.float32) * 0.01)
    W4 = jnp.asarray(rs.rand(H, 4 * H).astype(np.float32) * 0.01)
    b4 = jnp.asarray(rs.rand(4 * H).astype(np.float32) * 0.01)
    xp = jnp.asarray(rs.rand(T, B, 4 * H).astype(np.float32) * 0.01)

    def cell(carry, x):
        h, c = carry
        g = x + h @ W4 + b4
        i = jax.nn.sigmoid(g[:, :H])
        f = jax.nn.sigmoid(g[:, H:2 * H])
        gg = jnp.tanh(g[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(g[:, 3 * H:])
        c = f * c + i * gg
        return (o * jnp.tanh(c), c), None

    @jax.jit
    def bare(h):
        return jax.lax.scan(lambda h, _: (jnp.tanh(h @ W1), None),
                            h, None, length=T)[0]

    @jax.jit
    def wide(h):
        return jax.lax.scan(lambda h, _: (jnp.tanh((h @ W4)[:, :H]),
                                          None), h, None, length=T)[0]

    @jax.jit
    def lstm(carry):
        return jax.lax.scan(cell, carry, xp)[0]

    @jax.jit
    def lstm_grad(carry):
        def loss(carry):
            (h, c), _ = jax.lax.scan(cell, carry, xp)
            return h.sum() + c.sum()
        return jax.grad(loss)(carry)

    for name, fn, args in (
            ("bare tanh(h@W) H%d" % H, bare, (h0,)),
            ("wide  tanh((h@W4)[:H])", wide, (h0,)),
            ("lstm  full gates+state", lstm, ((h0, c0),)),
            ("lstm  fwd+bwd", lstm_grad, ((h0, c0),))):
        ps = _device_ps(fn, *args)
        print("%-26s %.3f us/step (device)" % (name, ps / 1e6 / T))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("probe", choices=("hbm", "matmul", "ptb"))
    args = ap.parse_args()
    {"hbm": probe_hbm, "matmul": probe_matmul,
     "ptb": probe_ptb}[args.probe]()


if __name__ == "__main__":
    main()
