#!/usr/bin/env python
"""On the chip, in one process: ``ops.attention.decode_attention`` at the
shapes of the three cells that call it (K-EXAONE's full layer,
Phi-4-mini-flash's shared layer, SmallThinker's rings and full layers, all
bfloat16), and the whole K-EXAONE decode step with it.  PERF.md section 6
(PR 28, PR 35) records what was read with it.

    chiprun -- python tools/perf/decode_attention_variants.py [kernel] [step]

``kernel``: the grid the kernel had up to PR 34 (a grid step a block of
``max_len``, kept below for this comparison alone) against the walk it is
now (one grid step a slot, chunks of ``chunk`` rows, the edge in pieces of
``piece``) at several chunk sizes, over ragged lengths as the cell holds
them, every slot full and every slot empty: the last two separate what a
chunk that is read costs from what a slot costs whatever it holds.
``step``: the step as the program has it against the step with every row
read, timed as ``benchmark/tools/moe_step_variants.py`` times them.
"""

import functools
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


def _grid_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                 acc_scr, *, scale, block):
    """The kernel up to PR 34: grid ``(slots, blocks of rows)``; a step
    wholly above the slot's length does nothing."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from mxnet_tpu.ops.attention import NEG_INF

    j = pl.program_id(1)
    length = len_ref[pl.program_id(0)]
    last = length // block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def accumulate(edge):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if edge:
            at = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, block), 2)
            s = jnp.where(at <= length, s, NEG_INF)
            rows = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block, 1), 1)
            v = jnp.where(rows <= length, v, jnp.zeros_like(v))
        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_cur
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    pl.when(j < last)(lambda: accumulate(False))
    pl.when(j == last)(lambda: accumulate(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = acc_scr[...] / l_scr[...]


def _grid(q, cache_k, cache_v, lengths, scale, block):
    """The call up to PR 34: the steps above a slot's last block ask for
    the next slot's first block, which is fetched once, ahead of its
    turn."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, kv, g, d = q.shape

    def rows_of(i, j, lens):
        ahead = j > lens[i] // block
        return (jnp.minimum(i + ahead, s - 1), 0, jnp.where(ahead, 0, j), 0)

    def whole(i, j, lens):
        return (i, 0, 0, 0)

    return pl.pallas_call(
        functools.partial(_grid_kernel, scale=scale, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, cache_k.shape[2] // block),
            in_specs=[pl.BlockSpec((1, kv, g, d), whole),
                      pl.BlockSpec((1, kv, block, d), rows_of),
                      pl.BlockSpec((1, kv, block, d), rows_of)],
            out_specs=pl.BlockSpec((1, kv, g, d), whole),
            scratch_shapes=[pltpu.VMEM((kv, g, 1), jnp.float32),
                            pltpu.VMEM((kv, g, 1), jnp.float32),
                            pltpu.VMEM((kv, g, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((s, kv, g, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attention_grid",
    )(lengths, q, cache_k, cache_v)


def _grid_block(kv, d, rows):
    """The block the plan gave up to PR 34: 1 MiB of bfloat16 K."""
    block = 128
    while block * 2 * kv * d * 2 <= 1 << 20 and rows % (block * 2) == 0:
        block *= 2
    return block


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
        block = _grid_block(kv, d, rows)
        planned = attention._decode_chunk(ck), attention._DECODE_PIECE
        _say(what="plan", cell=cell, chunk=planned[0], piece=planned[1],
             parent_block=block)
        variants = [("xla", None), ("grid", block)] \
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
                elif name == "grid":
                    fn = _in_a_row(lambda *a: _grid(*a, scale, size))
                    read = int((np.asarray(lengths) // size + 1).sum()) * size
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
