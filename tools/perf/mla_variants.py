"""On the chip: the readings ``ops.attention.latent_attention``'s chunk and
sub-block and ``models/exaone_moe.py``'s ``expert_product`` rule were set
from, at the shapes of the DeepSeek-V2 cell.  A hand tool on no cell's path.

* the latent kernel alone over a donated-size cache (128 slots, 128 heads,
  latent rows of 512 and rotated rows in 128 lanes, 8192 rows, bfloat16),
  the slots' lengths spread as the cell's sessions are (2048-8191): the
  grid of the rows a copy moves (512, 1024, 2048) by the rows multiplied at
  a time (256, 512, the whole chunk), the pair the code takes marked,
  beside the masked einsum over every row; each reading with the rows'
  bytes over the HBM rate and operations over the bf16 peak it stands
  against;
* the parts of a turn at the pair taken: the walk's copies with nothing
  multiplied, and the products with nothing copied (the walk's loop without
  its copies and waits): what a turn waits on;
* the other way to carry the 64 rotated values, timed beside it: ONE array
  of 640 values a row (512 latent, 64 rotated, 64 of padding: the same
  1280 B a row in memory), one copy a chunk and one product of 640 for the
  scores, where the kernel that is taken makes two copies a chunk (1024 B
  and 256 B a row) and two products (512 and 128); built here from the
  kernels' shared walk (``ops.attention._walk_slot``) with the softmax as
  PR 39 had it (a whole chunk a step, the edge piece by piece), the record
  of why the layout was not taken; on no cell's path;
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

    def reading(out, fn, *args, checked=True):
        try:
            if checked:
                got = np.asarray(fn(*args)[:4])
                out["max_abs_err_vs_einsum"] = float(np.abs(got - want).max())
            out["kernel_ms"] = 1e3 * timed(fn, *args)
            out["share_of_floor_pct"] = 100 * max(
                floor["bytes_ms"], floor["flops_ms"]) / out["kernel_ms"]
        except Exception as err:  # noqa: broad-except — a variant that does not fit or lower is a reading
            out["error"] = "%s: %s" % (type(err).__name__, str(err)[:300])
        print("VARIANT " + json.dumps(out), flush=True)

    taken = attention._latent_chunk(lat)
    taken = (taken, attention._latent_sub(HEADS, taken))
    for chunk in (512, 1024, 2048):
        for sub in [rows for rows in (256, 512) if rows < chunk] + [chunk]:
            fn = jax.jit(lambda a, b, c, d, n, chunk=chunk, sub=sub:
                         attention._latent_pallas(a, b, c, d, n, chunk, 128,
                                                  sub))
            reading({"what": "latent", "chunk": chunk,
                     "sub": "whole" if sub == chunk else sub,
                     "taken": (chunk, sub) == taken},
                    fn, q_lat, q_rope, lat, rope, lengths)
    for part in ("copies", "products"):
        fn = jax.jit(lambda a, b, c, d, n, part=part:
                     turn_part(a, b, c, d, n, taken[0], taken[1], part))
        reading({"what": "latent, a turn's %s alone" % part,
                 "chunk": taken[0], "sub": taken[1]},
                fn, q_lat, q_rope, lat, rope, lengths, checked=False)
    wide = jnp.concatenate([lat, rope], -1)           # (S, 1, rows, 640)
    q_wide = jnp.concatenate([q_lat, q_rope], -1)
    for chunk in (512, 1024):
        fn = jax.jit(lambda q, c, n, chunk=chunk:
                     one_array(q, c, n, RANK, chunk, 128))
        reading({"what": "latent, one array of 640", "chunk": chunk},
                fn, q_wide, wide, lengths)
    plain = jax.jit(attention._latent_xla)
    print("VARIANT " + json.dumps({
        "what": "latent", "chunk": "masked einsum over every row",
        "kernel_ms": 1e3 * timed(plain, q_lat, q_rope, lat, rope, lengths,
                                 repeats=3)}), flush=True)


def turn_part(q_lat, q_rope, cache_lat, cache_rope, lengths, chunk, sub,
              part, interpret=False):
    """The kernel's turn in halves.  ``copies``: ``_walk_slot`` with nothing
    multiplied (the output is the empty accumulator).  ``products``: the
    kernel as it is over a walk that copies and waits for nothing (it
    multiplies whatever the buffers hold: a time, not a result)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from mxnet_tpu.ops import attention

    s, h, c = q_lat.shape
    r = q_rope.shape[2]

    def walk_without_copies(len_ref, pairs, sems, turns, softmax, chunk,
                            piece, accumulate):
        i = pl.program_id(0)
        last = len_ref[i] // chunk

        @pl.when(i == 0)
        def _cold():
            turns[0] = 0

        turn0 = turns[0]
        m_scr, l_scr, acc_scr = softmax
        m_scr[...] = jnp.full_like(m_scr, attention.NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        def turn(t, carry):
            @pl.when(t < last)
            def _whole():
                accumulate([vmem[(turn0 + t) % 2] for _, vmem in pairs],
                           t * chunk, None)
            return carry

        jax.lax.fori_loop(0, last + 1, turn, 0)
        turns[0] = turn0 + last + 1

    def copies(len_ref, ql_ref, qr_ref, lat_hbm, rope_hbm, o_ref, lat_buf,
               rope_buf, sems, turns, m_scr, l_scr, acc_scr):
        attention._walk_slot(
            len_ref, ((lat_hbm, lat_buf), (rope_hbm, rope_buf)), sems, turns,
            (m_scr, l_scr, acc_scr), chunk, 128, lambda held, first, n: None)
        o_ref[0] = acc_scr[...]

    def products(*refs):
        attention._latent_kernel(*refs, chunk=chunk, piece=128, sub=sub,
                                 walk=walk_without_copies)

    def whole(i, lens):
        return (i, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        copies if part == "copies" else products,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s,),
            in_specs=[pl.BlockSpec((1, h, c), whole),
                      pl.BlockSpec((1, h, r), whole), hbm, hbm],
            out_specs=pl.BlockSpec((1, h, c), whole),
            scratch_shapes=[pltpu.VMEM((2, 1, chunk, c), cache_lat.dtype),
                            pltpu.VMEM((2, 1, chunk, r), cache_rope.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((h, 128), jnp.float32),
                            pltpu.VMEM((h, 128), jnp.float32),
                            pltpu.VMEM((h, c), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((s, h, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="latent_attention_" + part, interpret=interpret,
    )(lengths, q_lat, q_rope, cache_lat, cache_rope)


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
