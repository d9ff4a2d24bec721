"""On the chip: the readings ``ops.attention.latent_attention``'s chunk and
``models/exaone_moe.py``'s ``expert_product`` rule were set from, at the
shapes of the DeepSeek-V2 cell.  A hand tool on no cell's path.

* the latent kernel alone over a donated-size cache (128 slots, 128 heads,
  latent rows of 512 and rotated rows in 128 lanes, 8192 rows, bfloat16),
  the slots' lengths spread as the cell's sessions are (2048-8191), at
  chunks of 256-2048 rows, beside the masked einsum over every row; each
  reading with the rows' bytes over the HBM rate and operations over the
  bf16 peak it stands against;
* the other way to carry the 64 rotated values, timed beside it: ONE array
  of 640 values a row (512 latent, 64 rotated, 64 of padding: the same
  1280 B a row in memory), one copy a chunk and one product of 640 for the
  scores, where the kernel that is taken makes two copies a chunk (1024 B
  and 256 B a row) and two products (512 and 128); built here from the
  kernels' shared walk (``ops.attention._walk_slot``), on no cell's path;
* the every-expert and the grouped product at 20 experts held of 160, 6
  picked, 5120 -> 1536, at a step's 128 rows and at the buckets' 2560-4096
  (``benchmark/tools/expert_product_variants.py``'s timing, its shapes
  replaced).

    chiprun -- python tools/perf/mla_variants.py [latent|experts]
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SLOTS, HEADS, RANK, ROPE, LANES, ROWS = 128, 128, 512, 64, 128, 8192


def latent():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.tools.expert_product_variants import timed
    from mxnet_tpu.ops import attention

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    bf = jnp.bfloat16
    q_lat = jax.random.normal(keys[0], (SLOTS, HEADS, RANK), bf) * 0.05
    q_rope = jnp.pad(
        jax.random.normal(keys[1], (SLOTS, HEADS, ROPE), bf) * 0.05,
        ((0, 0), (0, 0), (0, LANES - ROPE)))
    lat = jax.random.normal(keys[2], (SLOTS, 1, ROWS, RANK), bf)
    rope = jnp.pad(jax.random.normal(keys[3], (SLOTS, 1, ROWS, ROPE), bf),
                   ((0, 0), (0, 0), (0, 0), (0, LANES - ROPE)))
    lengths = jnp.asarray(np.linspace(2048, ROWS - 1, SLOTS), jnp.int32)
    held = int(lengths.sum()) + SLOTS
    floor = {"bytes_ms": 1e3 * held * 2 * (RANK + ROPE) / 819e9,
             "flops_ms": 1e3 * held * 2 * HEADS * (2 * RANK + ROPE) / 197e12,
             "rows": held}
    print("VARIANT " + json.dumps(dict(what="latent floor", **floor)),
          flush=True)
    want = np.asarray(attention._latent_xla(
        q_lat[:4], q_rope[:4], lat[:4], rope[:4], lengths[:4]))
    for chunk in (256, 512, 1024, 2048):
        fn = jax.jit(lambda a, b, c, d, n, chunk=chunk:
                     attention._latent_pallas(a, b, c, d, n, chunk, 128))
        out = {"what": "latent", "chunk": chunk}
        try:
            got = np.asarray(fn(q_lat, q_rope, lat, rope, lengths)[:4])
            out["max_abs_err_vs_einsum"] = float(np.abs(got - want).max())
            out["kernel_ms"] = 1e3 * timed(fn, q_lat, q_rope, lat, rope,
                                           lengths)
            out["share_of_floor_pct"] = 100 * max(
                floor["bytes_ms"], floor["flops_ms"]) / out["kernel_ms"]
        except Exception as err:  # noqa: broad-except — a chunk that does not fit is a reading
            out["error"] = "%s: %s" % (type(err).__name__, str(err)[:300])
        print("VARIANT " + json.dumps(out), flush=True)
    wide = jnp.concatenate([lat, rope], -1)           # (S, 1, rows, 640)
    q_wide = jnp.concatenate([q_lat, q_rope], -1)
    for chunk in (512, 1024):
        fn = jax.jit(lambda q, c, n, chunk=chunk:
                     one_array(q, c, n, RANK, chunk, 128))
        out = {"what": "latent, one array of 640", "chunk": chunk}
        try:
            got = np.asarray(fn(q_wide, wide, lengths)[:4])
            out["max_abs_err_vs_einsum"] = float(np.abs(got - want).max())
            out["kernel_ms"] = 1e3 * timed(fn, q_wide, wide, lengths)
            out["share_of_floor_pct"] = 100 * max(
                floor["bytes_ms"], floor["flops_ms"]) / out["kernel_ms"]
        except Exception as err:  # noqa: broad-except — a variant the compiler refuses is a reading
            out["error"] = "%s: %s" % (type(err).__name__, str(err)[:300])
        print("VARIANT " + json.dumps(out), flush=True)
    plain = jax.jit(attention._latent_xla)
    print("VARIANT " + json.dumps({
        "what": "latent", "chunk": "masked einsum over every row",
        "kernel_ms": 1e3 * timed(plain, q_lat, q_rope, lat, rope, lengths,
                                 repeats=3)}), flush=True)


def one_array(q, cache, lengths, rank, chunk, piece, interpret=False):
    """The variant layout: ``q (S, heads, w)`` against ``cache (S, 1, rows,
    w)`` whose row holds the latent values in its first ``rank`` lanes and
    the rotated ones after them; the context ``(S, heads, rank)``."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mxnet_tpu.ops import attention

    s, h, w = q.shape

    def kernel(len_ref, q_ref, hbm, o_ref, buf, sems, turns, m, l, acc):
        def accumulate(held, first, n):
            row = held[0][0]                                # (rows, w)
            sc = jax.lax.dot_general(q_ref[0], row, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            lat = row[:, :rank]
            if n is not None:
                rows = row.shape[0]
                at = first + jax.lax.broadcasted_iota(
                    jnp.int32, (1, rows), 1)
                sc = jnp.where(at <= n, sc, attention.NEG_INF)
                at = first + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, 1), 0)
                lat = jnp.where(at <= n, lat, jnp.zeros_like(lat))
            m_prev = m[...]
            m_cur = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(sc - m_cur)
            l[...] = l[...] * alpha + p.sum(axis=-1, keepdims=True)
            m[...] = m_cur
            acc[...] = acc[...] * alpha + jax.lax.dot_general(
                p.astype(lat.dtype), lat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        attention._walk_slot(len_ref, ((hbm, buf),), sems, turns,
                             (m, l, acc), chunk, piece, accumulate)
        o_ref[0] = acc[...] / l[...]

    def whole(i, lens):
        return (i, 0, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s,),
            in_specs=[pl.BlockSpec((1, h, w), whole),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, rank), whole),
            scratch_shapes=[pltpu.VMEM((2, 1, chunk, w), cache.dtype),
                            pltpu.SemaphoreType.DMA((1, 2)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((s, h, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="latent_attention_one_array", interpret=interpret,
    )(lengths, q, cache)


def experts():
    from benchmark.tools import expert_product_variants as tool

    tool.SHAPES = [("deepseek-v2 group", 5120, 1536, 20, 160, 6, "silu",
                    (128, 1024, 2560, 3072, 3584, 4096))]
    tool.experts()


if __name__ == "__main__":
    import jax

    print("device: %s" % jax.devices()[0].device_kind, flush=True)
    for part in sys.argv[1:] or ("latent", "experts"):
        {"latent": latent, "experts": experts}[part]()
