#!/usr/bin/env python
"""On the chip, in one process: ``ops.attention.decode_attention`` at the
shapes of the three cells that call it (K-EXAONE's full layer,
Phi-4-mini-flash's shared layer, SmallThinker's rings and full layers, all
bfloat16), and the whole K-EXAONE decode step with it.  PERF.md section 6
(PR 28, PR 35) records what was read with it.

    chiprun -- python tools/perf/decode_attention_variants.py [kernel] [step]

``kernel``: the walk the kernel is (one grid step a slot, chunks of
``chunk`` rows, the edge in pieces of ``piece``) at the plan's own chunk
and several others, against the same sum in plain XLA over every row, over
ragged lengths as the cell holds them, every slot full and every slot
empty: the last two separate what a chunk that is read costs from what a
slot costs whatever it holds.
``step``: the step as the program has it against the step with every row
read, timed as ``benchmark/tools/moe_step_variants.py`` times them.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STEPS = 30
CALLS = 8
#: cell -> slots, K/V heads, group, head size, rows, ragged lengths from-to
SHAPES = {
    "phi-4-mini-flash": (128, 10, 4, 128, 4096, (64, 1600)),
    "k-exaone": (256, 8, 8, 128, 4096, (64, 1800)),
    "smallthinker-ring": (48, 4, 7, 128, 4096, (2500, 10000)),
    "smallthinker-full": (48, 4, 7, 128, 16384, (2500, 10000)),
}
#: (chunk, piece) of the walk that are tried beside the plan's own
WALKS = [(128, 128), (256, 128), (256, 256), (512, 128), (1024, 128),
         (2048, 128)]


def _say(**row):
    print("VARIANT " + json.dumps(row), flush=True)


def _time(fn, *args):
    import jax

    for _ in range(3):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.monotonic()
    for _ in range(STEPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.monotonic() - t0) / STEPS, out


def _in_a_row(fn):
    """``CALLS`` calls of ``fn(q, ck, cv, lengths)`` in one program, each
    fed the one before (as a step's eight layers are): a call of 0.2 ms
    alone in its program is timed by the host's dispatch, not the chip."""
    import jax
    import jax.numpy as jnp

    def run(q, ck, cv, lengths):
        def call(_, carry):
            out = fn(carry[0], ck, cv, lengths)
            return carry[0] + (out * 0).astype(q.dtype), out
        return jax.lax.fori_loop(
            0, CALLS, call, (q, jnp.zeros(q.shape, jnp.float32)))[1]
    return jax.jit(run)


def kernel_alone(cells):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention

    for cell in cells:
        s, kv, g, d, rows, (lo, hi) = SHAPES[cell]
        scale = d ** -0.5
        key = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(key[0], (s, kv, g, d), jnp.bfloat16)
        ck = jax.random.normal(key[1], (s, kv, rows, d), jnp.bfloat16)
        cv = jax.random.normal(key[2], (s, kv, rows, d), jnp.bfloat16)
        rs = np.random.RandomState(0)
        # a ring's horizon is ``min(pos, window - 1)``
        mixes = {"ragged-%d-%d" % (lo, hi):
                 np.minimum(rs.randint(lo, hi, (s,)), rows - 1),
                 "full": np.full((s,), rows - 1), "empty": np.zeros((s,))}
        row_bytes = 2 * kv * d * ck.dtype.itemsize
        planned = attention._decode_chunk(ck), attention._DECODE_PIECE
        _say(what="plan", cell=cell, chunk=planned[0], piece=planned[1])
        variants = [("xla", None)] \
            + [("walk", w) for w in dict.fromkeys([planned] + WALKS)
               if w[0] <= rows
               and 4 * w[0] * kv * d * ck.dtype.itemsize <= 12 << 20]
        for mix, lengths in mixes.items():
            held = int((np.asarray(lengths) + 1).sum())
            lengths = jnp.asarray(lengths, jnp.int32)
            want = None
            for name, size in variants:
                if name == "xla":
                    fn = _in_a_row(lambda *a: attention._decode_xla(*a, scale))
                    read = s * rows
                else:
                    fn = _in_a_row(lambda *a: attention._decode_pallas(
                        *a, scale, *size))
                    read = int((np.asarray(lengths) // size[1] + 1).sum()) \
                        * size[1]
                try:
                    ms, out = _time(fn, q, ck, cv, lengths)
                    ms /= CALLS
                except Exception as e:  # noqa: broad-except — a size the
                    # chip's compiler refuses is a reading too
                    _say(what="kernel", cell=cell, lengths=mix, variant=name,
                         size=size,
                         error="%s: %s" % (type(e).__name__, str(e)[:300]))
                    continue
                if want is None:
                    want = out
                _say(what="kernel", cell=cell, lengths=mix, variant=name,
                     size=size, planned=(name == "walk" and size == planned),
                     ms=ms, us_a_slot=1e3 * ms / s,
                     rows_read_share=read / (s * rows),
                     gb_read=read * row_bytes / 1e9,
                     held_roofline_pct=100 * held * row_bytes / 819e9
                     / (ms / 1e3),
                     widest_gap_to_xla=float(jnp.abs(out - want).max()),
                     mean_abs=float(jnp.abs(out).mean()))
        del q, ck, cv


def whole_step():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness
    from benchmark.families import exaone_moe_engine as family
    from benchmark.reference import exaone_moe_engine as ref
    from mxnet_tpu.models import exaone_moe as xm
    from mxnet_tpu.models import transformer_lm as tlm
    from mxnet_tpu.ops import attention

    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "k-exaone-236b-a23b.json"))
    cfg = family.model_config(xm, ref.sizes(config))
    device = jax.devices()[0]
    params = ref.init_weights(config, 7, device)
    s = int(config["engine"]["slots"])
    model = xm.ExaoneMoE(cfg)
    variants = {
        "program": {},
        "full=every-row": {
            "decode_attention": attention._decode_xla,
            "decode_attention_plan": lambda q, ck: (ck.shape[2], "off")},
    }
    rs = np.random.RandomState(0)
    lengths = jnp.asarray(rs.randint(64, 3000, (s,)), jnp.int32)
    last = jnp.asarray(rs.randint(0, cfg.vocab, (s,)), jnp.int32)
    active = jnp.ones((s,), bool)
    first = None
    for name, patch in variants.items():
        saved = {k: getattr(xm, k) for k in patch}
        for k, fn in patch.items():
            setattr(xm, k, fn)
        try:
            step = jax.jit(model.decode_step, donate_argnums=(1, 2))
            ck, cv = (tuple(jnp.zeros((s,) + tlm.slot_shape(c), c.dtype)
                            for c in model.cache_spec()) for _ in range(2))
            extra = jax.device_put(model.extra_state(), device)
            t0 = time.monotonic()
            compiled = step.lower(params, ck, cv, last, lengths, active,
                                  extra).compile()
            t_compile = time.monotonic() - t0
            for _ in range(3):
                logits, ck, cv, extra = compiled(params, ck, cv, last,
                                                 lengths, active, extra)
            jax.block_until_ready(logits)
            t0 = time.monotonic()
            for _ in range(STEPS):
                logits, ck, cv, extra = compiled(params, ck, cv, last,
                                                 lengths, active, extra)
            jax.block_until_ready(logits)
            ms = 1e3 * (time.monotonic() - t0) / STEPS
            counted = model.counters(jax.device_get(extra))
            if first is None:
                first = logits
            _say(what="step", variant=name, step_ms=ms, compile_s=t_compile,
                 temporaries_gb=compiled.memory_analysis()
                 .temp_size_in_bytes / 1e9,
                 rows_read_share=counted["gauges"][
                     "serving.attn.rows_read_share"],
                 logits_abs_mean=float(jnp.abs(logits).mean()),
                 widest_logit_gap_to_program=float(
                     jnp.abs(logits - first).max()))
            del ck, cv, logits, compiled
        finally:
            for k, fn in saved.items():
                setattr(xm, k, fn)


if __name__ == "__main__":
    todo = sys.argv[1:] or ["kernel", "step"]
    if "kernel" in todo:
        kernel_alone([c for c in SHAPES if c in todo] or list(SHAPES))
    if "step" in todo:
        whole_step()
