"""On the chip: the two routed-expert products of ``models/exaone_moe.py``
timed against each other at the row counts of a decode step and of the
prefill buckets, at the shapes of both configurations that route (what
``expert_product``'s threshold is set from), and a ring read whole by a
masked einsum against the same ring through ``decode_attention``, at the
ring sizes of the three models that have one.  Prints one JSON line a
reading; device times by ``block_until_ready`` around repeated calls.

    chiprun -- python benchmark/tools/expert_product_variants.py
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: (name, embed, expert width, experts held, experts in all, picks a row,
#: activation, row counts)
SHAPES = [
    ("smallthinker", 2560, 768, 64, 64, 6, "relu",
     (48, 256, 512, 1024, 2048, 3072, 4096, 8192)),
    ("k-exaone", 6144, 2048, 16, 128, 8, "silu", (256, 512, 1024)),
]
#: (name, slots, kv heads, group, ring rows, head width)
RINGS = [
    ("k-exaone", 256, 8, 8, 128, 128),
    ("phi-4-mini-flash", 128, 10, 4, 512, 128),
    ("smallthinker", 48, 4, 7, 4096, 128),
]


def timed(fn, *args, repeats=10):
    import jax

    jax.block_until_ready(fn(*args))            # compile, warm
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


def experts():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import exaone_moe as xm

    for name, e, f, held, n, k, activation, rows in SHAPES:
        cfg = xm.ExaoneConfig(*([None] * 19))._replace(
            num_experts=n, top_k=k, first_expert=0, experts_held=held,
            activation=activation)
        key = jax.random.PRNGKey(0)
        moe = {"gate": jax.random.normal(key, (held, e, f), jnp.bfloat16),
               "up": jax.random.normal(key, (held, e, f), jnp.bfloat16),
               "down": jax.random.normal(key, (held, f, e), jnp.bfloat16)}
        act = xm._ACTIVATIONS[activation]
        # the weights are arguments: closed over, they would be constants
        # of the program, folded on the host
        every = jax.jit(lambda h, c, w, moe: xm._every_expert(
            act, h, xm._combine(cfg, c, w), moe))
        grouped = jax.jit(lambda h, c, w, moe: xm._grouped_experts(
            cfg, act, h, c, w, moe))
        for t in rows:
            h = jax.random.normal(key, (t, e), jnp.float32)
            scores = jax.random.normal(jax.random.PRNGKey(t), (t, n))
            w, chosen = jax.lax.top_k(jax.nn.softmax(scores), k)
            out = {"what": "experts", "shapes": name, "rows": t}
            for which, fn in (("every", every), ("grouped", grouped)):
                try:
                    out[which + "_ms"] = 1e3 * timed(fn, h, chosen, w, moe)
                except Exception as err:  # noqa: broad-except — a product that does not fit is a reading
                    out[which + "_ms"] = None
                    out[which + "_error"] = type(err).__name__
            print("VARIANT " + json.dumps(out), flush=True)


def rings():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import exaone_moe as xm
    from mxnet_tpu.ops import attention

    for name, s, kv, g, rows, d in RINGS:
        key = jax.random.PRNGKey(1)
        q = jax.random.normal(key, (s, kv, g, d), jnp.bfloat16)
        ck = jax.random.normal(key, (s, kv, rows, d), jnp.bfloat16)
        cv = jax.random.normal(key, (s, kv, rows, d), jnp.bfloat16)
        for fill in ("half", "full"):
            pos = jnp.full((s,), rows // 2 if fill == "half" else 3 * rows,
                           jnp.int32)

            def whole(q, ck, cv, pos):
                ring = jnp.arange(rows)
                holds = pos[:, None] - ((pos[:, None] - ring[None]) % rows)
                scores = jnp.einsum("skgd,skmd->skgm", q, ck,
                                    preferred_element_type=jnp.float32)
                return xm._softmax_ctx(
                    scores, (holds >= 0)[:, None, None, :], cv,
                    "skgm,skmd->skgd")

            def kernel(q, ck, cv, pos):
                return attention.decode_attention(
                    q, ck, cv, jnp.minimum(pos, rows - 1), 1.0)

            print("VARIANT " + json.dumps({
                "what": "ring", "shapes": name, "rows": rows, "fill": fill,
                "plan": attention.decode_attention_plan(q, ck)[0],
                "whole_ms": 1e3 * timed(jax.jit(whole), q, ck, cv, pos),
                "kernel_ms": 1e3 * timed(jax.jit(kernel), q, ck, cv, pos)}),
                flush=True)


if __name__ == "__main__":
    import jax

    print("device: %s" % jax.devices()[0].device_kind, flush=True)
    rings()
    experts()
