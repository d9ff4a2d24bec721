#!/usr/bin/env python
"""On the chip, in one process: ``ops.attention.decode_attention`` at the
K-EXAONE cell's shapes (256 slots, 8 K/V heads of 128, group 8, 4096 rows,
bfloat16), and the whole decode step with it.  PERF.md section 6 (PR 28)
records what was read with it.

    chiprun -- python tools/perf/decode_attention_variants.py [kernel] [step]

``kernel``: the function alone, every row masked (``xla``) against the
Pallas kernel at several block sizes, over ragged lengths (64-3000, as
``benchmark/tools/moe_step_variants.py`` draws them), every slot full and
every slot empty: the last two separate what a block that is read costs
from what a grid step that is skipped costs.  ``step``: the step as the
program has it against the step with every row read, timed as that tool
times them.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STEPS = 30
SHAPE = (256, 8, 8, 128, 4096)  # slots, K/V heads, group, head size, rows


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


def kernel_alone():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention

    s, kv, g, d, rows = SHAPE
    scale = d ** -0.5
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (s, kv, g, d), jnp.bfloat16)
    ck = jax.random.normal(key[1], (s, kv, rows, d), jnp.bfloat16)
    cv = jax.random.normal(key[2], (s, kv, rows, d), jnp.bfloat16)
    rs = np.random.RandomState(0)
    mixes = {"ragged-64-3000": rs.randint(64, 3000, (s,)),
             "full": np.full((s,), rows - 1), "empty": np.zeros((s,))}
    row_bytes = 2 * kv * d * ck.dtype.itemsize
    planned, reason = attention.decode_attention_plan(q, ck)
    _say(what="plan", block=planned, reason=reason)
    for mix, lengths in mixes.items():
        lengths = jnp.asarray(lengths, jnp.int32)
        want = None
        for name in ("xla", 128, 256, 512, 1024, 2048):
            if name == "xla":
                fn = jax.jit(lambda *a: attention._decode_xla(*a, scale))
                read = s * rows
            else:
                fn = jax.jit(lambda *a, b=name: attention._decode_pallas(
                    *a, scale, b))
                read = int((np.asarray(lengths) // name + 1).sum()) * name
            try:
                ms, out = _time(fn, q, ck, cv, lengths)
            except Exception as e:  # noqa: broad-except — a block size the
                # chip's compiler refuses is a reading too
                _say(what="kernel", lengths=mix, variant=name,
                     error="%s: %s" % (type(e).__name__, str(e)[:300]))
                continue
            if want is None:
                want = out
            _say(what="kernel", lengths=mix, variant=name, ms=ms,
                 rows_read_share=read / (s * rows),
                 gb_read=read * row_bytes / 1e9,
                 gb_per_s=read * row_bytes / 1e6 / ms,
                 widest_gap_to_xla=float(jnp.abs(out - want).max()),
                 mean_abs=float(jnp.abs(out).mean()))


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
        kernel_alone()
    if "step" in todo:
        whole_step()
