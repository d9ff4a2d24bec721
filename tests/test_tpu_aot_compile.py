"""The main path's Pallas kernels, compiled at real widths for a TPU v5e
that is described and not attached (``jax.experimental.topologies``).

Interpret-mode tests run the kernel math; only the chip's compiler says
whether a kernel lowers — tile rules, scoped VMEM, layouts.  Nothing runs
here: a compile that passes is not a chip run.  Every case is skipped when
the topology cannot be described (no TPU compiler installed).

Each case compiles in a child process (``python tests/test_tpu_aot_compile.py
<case>``), not in the pytest worker: loading the TPU compiler installs its
own process-wide signal handlers (SIGTERM among them), which must not leak
into the workers that run the suite's signal tests.  The children inherit
the suite's environment — CPU platform, persistent compile cache off (an
entry written for a described device cannot be read back without the
chip).
"""

import contextlib
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.ops import attention, registry  # noqa: E402

#: exit code of a child that could not describe the chip
_SKIP = 77

#: case name -> zero-argument callable, run in the child
CASES = {}


@contextlib.contextmanager
def _tpu_trace():
    """What the executor does around a trace bound for the chip: the
    dispatch rules ask ``registry.on_tpu()``, and here JAX sees a CPU."""
    token = registry.trace_device.set("tpu")
    try:
        yield
    finally:
        registry.trace_device.reset(token)


def _one_chip():
    """The sharding that pins a shape onto one chip of the described
    ``v5e:2x2``; the child exits with ``_SKIP`` where it cannot be
    described."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: broad-except — any failure to describe
        # the chip (no libtpu, unknown topology name) skips the case
        print("cannot describe a v5e topology: %s" % e)
        sys.exit(_SKIP)
    return SingleDeviceSharding(topo.devices[0])


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (a ``jit``
    within the function: the kernels are jitted on their own)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in eqn.params.values():
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield from _equations(inner)


def _unbuilt_engine(model, **opts):
    """A ``DecodeEngine`` over ``model`` that builds its programs, allocates
    no state and runs nothing: what a case lowers for shapes of its own."""
    from mxnet_tpu.serving import DecodeEngine

    class Unbuilt(DecodeEngine):
        def _fresh_state(self):
            return None

        def _warm(self, state):
            return state

    return Unbuilt(model, {}, autostart=False, **opts)


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip from ``(shape, dtype)``
    pairs, and check that the kernel is in the compiled text."""
    sharding = _one_chip()
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    assert "tpu_custom_call" in \
        jax.jit(fn).lower(*args).compile().as_text()


# -- flash attention -----------------------------------------------------------
# the decode phase's prefill shapes (chip_smoke.py: 12 heads of 64, buckets
# 32/128/512) and the long-sequence shape at both head widths
def _flash_case(b, h, l, d):
    def run():
        probe = jax.ShapeDtypeStruct((b, h, l, d), jnp.bfloat16)
        # the dispatch rule must admit exactly what the compiler admits
        with _tpu_trace():
            assert attention._kernel_refusal(probe, probe, 256, 512) is None
        shape = ((b, h, l, d), jnp.bfloat16)
        _compile(lambda q, k, v: attention._flash_pallas(
            q, k, v, True, float(d) ** -0.5), shape, shape, shape)
    return run


for _shape in [(1, 12, 32, 64), (1, 12, 128, 64), (1, 12, 512, 64),
               (2, 8, 2048, 128), (2, 8, 2048, 64)]:
    CASES["flash_attention-%dx%dx%dx%d" % _shape] = _flash_case(*_shape)


def _flash_refusal():
    """A strict sub-block whose extent is not a multiple of 8 is the one
    thing Mosaic refuses at these shapes; ``_kernel_refusal`` names it
    ``tile`` instead of letting the lowering fail."""
    probe = jax.ShapeDtypeStruct((1, 12, 200, 64), jnp.bfloat16)
    with _tpu_trace():
        assert attention._kernel_refusal(probe, probe, 100, 512) == "tile"
        assert attention._kernel_refusal(probe, probe, 256, 512) is None
    shape = ((1, 12, 200, 64), jnp.bfloat16)
    try:
        _compile(lambda q, k, v: attention._flash_pallas(
            q, k, v, True, 0.125, block_q=100), shape, shape, shape)
    except Exception as e:  # noqa: broad-except — the compiler's refusal
        assert "divisible by 8" in str(e), e
    else:
        raise AssertionError("the compiler took a 100-row sub-block")


CASES["flash_attention-refuses-what-the-compiler-refuses"] = _flash_refusal


# -- the dense decode tier's two cache writes ------------------------------------
#: benchmark/configs/gpt2-large.json's widths (vocabulary, embed, heads,
#: layers, ffn, positions) with two of its 36 layers, its slots, and its
#: largest prefill bucket
_GPT2_LARGE, _SLOTS, _BUCKET = (50304, 1280, 20, 2, 5120, 1024), 12, 768


def _held_in_a_branch(text, cfg, slots):
    """Instructions of a compiled dense step that MAKE an array the size of
    a rung of ``transformer_lm.ladder`` or of the whole cache, rows second
    or rows last, outside every fused computation: a copy, a transpose or
    a conversion of a cache array, or a slice of one that no reading fusion
    took in.  (Parameters, tuple elements, bitcasts, the row writes and the
    compiler's own prefetches of a whole array are not that.)"""
    from mxnet_tpu.models import transformer_lm as tlm

    heads, hd = cfg.heads, cfg.embed // cfg.heads
    made, fused = [], False
    for line in text.splitlines():
        if not line.startswith(" "):
            fused = line.startswith("%fused_computation")
            continue
        m = re.match(r"\s+(?:ROOT )?%\S+ = (?:f32|bf16)\[([0-9,]+)\]"
                     r"\{[^}]*\} ([a-z-]+)\(", line)
        if fused or not m:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        for rows in tlm.ladder(cfg.max_len)[1]:
            if dims in ([slots, rows, heads, hd], [slots, heads, hd, rows]) \
                    and (rows < cfg.max_len or m.group(2) in (
                        "copy", "transpose", "convert", "fusion", "slice")):
                made.append(line.strip()[:200])
    return made


#: temporaries of the dense step at ``_GPT2_LARGE``'s two layers before the
#: ladder (PR 47's tree, this compiler), and the room the ladder's eight
#: branches a layer are given above it (they take 3,692,032)
_STEP_TEMPORARIES, _LADDER_ROOM = 2096640, 2 << 20


def _dense_engine_case(program):
    """The engine's own ``jit_step`` / ``jit_prefill``, state donated, at the
    ``gpt2-large`` cell's widths: the program may hold no temporaries the
    size of a cache array (beyond what it needs whatever the cache's
    layout) and no copy of one.  A write of the new rows that is not done in
    the layout the cache lives in on the chip shows up as both (the scatter
    the step had until PR 25: two transposes an array)."""
    def run():
        import re

        from mxnet_tpu.models import transformer_lm as tlm
        from mxnet_tpu.serving import DecodeEngine

        class Unwarmed(DecodeEngine):
            """Builds the programs and its state, and runs nothing."""

            def _warm(self, state):
                return state

        cfg = tlm.LMConfig(*_GPT2_LARGE, eos_id=_GPT2_LARGE[0])
        # the tree init_params builds, without drawing 170 M numbers
        v, e, f, n = cfg.vocab, cfg.embed, cfg.ffn, cfg.layers
        shapes = {"embed": (v, e), "pos": (cfg.max_len, e), "head": (e, v),
                  "ln_f": (e,),
                  "blocks": {"ln1": (n, e), "qkv_w": (n, e, 3 * e),
                             "out_w": (n, e, e), "ln2": (n, e),
                             "up_w": (n, e, f), "down_w": (n, f, e)}}
        engine = Unwarmed(
            cfg, jax.tree_util.tree_map(
                lambda shape: jnp.zeros(shape, jnp.float32), shapes,
                is_leaf=lambda a: isinstance(a, tuple)),
            slots=_SLOTS, prefill_buckets=(_BUCKET,), autostart=False)
        one_chip = _one_chip()

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        params, state = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype),
            (engine._params, engine._boot_state))
        if program == "step":
            lowered = engine._step_fn.lower(params, state,
                                            sds((_SLOTS,), jnp.bool_))
        else:
            lowered = engine._prefill_fns[_BUCKET].lower(
                params, state, sds((_BUCKET,), jnp.int32),
                sds((), jnp.int32), sds((), jnp.int32), sds((), jnp.int32),
                sds((), jnp.float32), sds((), jnp.uint32),
                sds((), jnp.bool_))
        compiled = lowered.compile()
        kv = state[0][0]
        cache_bytes = kv.size * kv.dtype.itemsize
        # the prefill's own largest temporary is not the cache's: the
        # logits of every prompt position, of which it keeps one row
        own = 4 * _BUCKET * cfg.vocab if program == "prefill" else 0
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp - own < cache_bytes, \
            "%d bytes of temporaries (%d of them the program's own), " \
            "one cache array is %d" % (temp, own, cache_bytes)
        text = compiled.as_text()
        copies = re.findall(
            r"= f32\[%d,%d,%d,%d\]\{[^}]*\} copy\(.*" % kv.shape, text)
        assert not copies, "%d copies of a cache array, the first: %s" \
            % (len(copies), copies[0][:200])
        if program == "step":
            # the ladder (PR 48): a conditional a layer, whose branches
            # read a prefix of the cache where it lies
            assert len(re.findall(r" conditional\(", text)) == cfg.layers
            made = _held_in_a_branch(text, cfg, _SLOTS)
            assert not made, "%d, the first: %s" % (len(made), made[0])
            assert temp < _STEP_TEMPORARIES + _LADDER_ROOM, temp
    return run


CASES["decode-dense-step-gpt2-large-12-slots-no-cache-copy"] = \
    _dense_engine_case("step")
CASES["decode-dense-prefill-768-gpt2-large-no-cache-copy"] = \
    _dense_engine_case("prefill")


def _paged_engine_case(program):
    """The engine's paged ``jit_step`` / ``jit_prefill`` at the same widths,
    16-row blocks, a pool the size of the dense cache (+ the scratch
    block): the paged programs compile for the chip and fit it.  Their
    temporaries are printed and bounded, not judged: a step gathers every
    slot's table into ``(slots, max_len, heads, head_dim)`` a layer and
    scatters its rows into a pool the compiler keeps in another layout, so
    it holds cache-sized copies the dense step no longer does.  Taking them
    out is ROADMAP S1(b); this is its baseline."""
    def run():
        import re

        from mxnet_tpu.models import transformer_lm as tlm

        cfg = tlm.LMConfig(*_GPT2_LARGE, eos_id=_GPT2_LARGE[0])
        engine = _unbuilt_engine(cfg, slots=_SLOTS,
                                 prefill_buckets=(_BUCKET,),
                                 kv_layout="paged", kv_block_size=16)
        nb, bs, mb = (engine._kv.num_blocks, engine._kv.block_size,
                      engine._kv.max_blocks)
        assert (nb, bs, mb) == (_SLOTS * 64 + 1, 16, 64)
        one_chip = _one_chip()

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        s = _SLOTS
        pool = sds((nb, bs, cfg.heads, cfg.embed // cfg.heads), jnp.float32)
        state = (tuple(pool for _ in range(cfg.layers)),
                 tuple(pool for _ in range(cfg.layers)),
                 sds((s,), jnp.int32), sds((s,), jnp.int32),
                 sds((s,), jnp.int32), sds((s,), jnp.bool_),
                 sds((s,), jnp.float32), sds((s,), jnp.uint32))
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype),
            jax.eval_shape(lambda: tlm.init_params(cfg)))
        i32, f32 = sds((), jnp.int32), sds((), jnp.float32)
        if program == "step":
            lowered = engine._step_fn.lower(
                params, state, sds((s,), jnp.bool_), sds((s, mb), jnp.int32))
        else:
            lowered = engine._prefill_fns[_BUCKET].lower(
                params, state, sds((_BUCKET,), jnp.int32), i32, i32, i32,
                sds((mb,), jnp.int32), i32, f32, sds((), jnp.uint32),
                sds((), jnp.bool_), i32, i32)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        pool_bytes = pool.size * pool.dtype.itemsize
        copies = re.findall(
            r"= f32\[%d,%d,%d,%d\]\{[^}]*\} copy\(" % pool.shape,
            compiled.as_text())
        print("paged %s: temporaries %d bytes, %.2f of one pool array "
              "(%d); %d copies of a pool-shaped array"
              % (program, ma.temp_size_in_bytes,
                 ma.temp_size_in_bytes / pool_bytes, pool_bytes,
                 len(copies)))
        # as of PR 29: two copies an array (into the scatter's layout and
        # back), 8 at two layers, and temporaries of 7.7 (step) and 7.5
        # (prefill) pool arrays.  More than two an array is a regression
        arrays = 2 * cfg.layers
        assert len(copies) <= 2 * arrays, len(copies)
        own = 4 * _BUCKET * cfg.vocab if program == "prefill" else 0
        assert ma.temp_size_in_bytes - own < 2 * arrays * pool_bytes
        assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.0e9
    return run


CASES["decode-paged-step-gpt2-large-12-slots"] = _paged_engine_case("step")
CASES["decode-paged-prefill-768-gpt2-large"] = _paged_engine_case("prefill")


def _gpt2_step_as_the_benchmark_lowers_it():
    """``benchmark/families/decode_engine.py`` ``scratch_bytes`` lowers
    ``engine._step_fn`` after every window for shapes it writes out by
    hand: ``(params, (K tuple, V tuple, six small arrays), keep)``, float32
    ``(slots, max_len, heads, head_dim)`` a layer.  The engine's model
    protocol must leave that signature as it is."""
    from mxnet_tpu.models import transformer_lm as tlm

    cfg = tlm.LMConfig(*_GPT2_LARGE, eos_id=_GPT2_LARGE[0])
    engine = _unbuilt_engine(cfg, slots=_SLOTS, prefill_buckets=(_BUCKET,))
    one_chip = _one_chip()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s = _SLOTS
    kv = sds((s, cfg.max_len, cfg.heads, cfg.embed // cfg.heads),
             jnp.float32)
    state = (tuple(kv for _ in range(cfg.layers)),
             tuple(kv for _ in range(cfg.layers)),
             sds((s,), jnp.int32), sds((s,), jnp.int32),
             sds((s,), jnp.int32), sds((s,), jnp.bool_),
             sds((s,), jnp.float32), sds((s,), jnp.uint32))
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: tlm.init_params(cfg)))
    compiled = engine._step_fn.lower(params, state,
                                     sds((s,), jnp.bool_)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < _STEP_TEMPORARIES + _LADDER_ROOM
    made = _held_in_a_branch(compiled.as_text(), cfg, s)
    assert not made, "%d, the first: %s" % (len(made), made[0])


CASES["decode-dense-step-gpt2-large-as-the-benchmark-lowers-it"] = \
    _gpt2_step_as_the_benchmark_lowers_it


class _NoDraw:
    """``numpy.random.RandomState`` for shapes alone: under ``eval_shape``
    the zeros are staged, so nothing of their size is ever made."""

    def __init__(self, seed):
        del seed

    def normal(self, loc, scale, shape):
        return jnp.zeros(shape)


def _exaone_step_case():
    """The engine's ``jit_step`` over ``models/exaone_moe.py`` at
    ``benchmark/configs/k-exaone-236b-a23b.json``'s widths and slots (256 x
    4096, bfloat16, 5 layers, 16 of 128 experts), the benchmark tool's own
    engine and shapes, traced as for the chip: it fits the chip, its
    temporaries stay under one full-layer cache array, and it holds no copy
    of a cache-sized array (with positions before K/V heads in the cache
    it held ten, one an array: PERF.md, PR 27).  The tool asks
    ``init_params`` for its shapes, which draws 3.7 G numbers on the host
    to tell them (160 s of this case's 171); here it is handed a generator
    that draws none."""
    from unittest import mock

    import numpy as np

    from benchmark import harness
    from benchmark.tools import aot_compile_moe as tool

    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "k-exaone-236b-a23b.json"))
    with mock.patch.object(np.random, "RandomState", _NoDraw):
        engine, params, state, keep, extra, _sds = tool.engine_programs(
            config, _one_chip())
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) > 3.7e9
    with _tpu_trace():
        compiled = engine._step_fn.lower(params, state, keep,
                                         extra).compile()
    full = max(a.size * a.dtype.itemsize for a in state[0])
    assert full == 256 * 8 * 4096 * 128 * 2
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < full, ma.temp_size_in_bytes
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.0e9
    copies = tool.cache_copies(compiled.as_text(), state)
    assert not copies, "%d copies of a cache array, the first: %s" \
        % (len(copies), copies[0][:200])


CASES["decode-step-k-exaone-256-slots-no-cache-copy"] = _exaone_step_case


# -- decode attention ----------------------------------------------------------
def _decode_attention_case():
    """``ops.attention.decode_attention`` alone at the K-EXAONE cell's
    full layer (256 slots, 8 K/V heads of 128 with 8 queries each, 4096
    rows, bfloat16): the plan admits it, read to a multiple of 128 rows a
    slot in chunks of 512 (1 MiB of K a copy), and what the plan admits
    the compiler takes."""
    q = jax.ShapeDtypeStruct((256, 8, 8, 128), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((256, 8, 4096, 128), jnp.bfloat16)
    with _tpu_trace():
        assert attention.decode_attention_plan(q, cache) == (128, None)
        assert attention._decode_chunk(cache) == 512
        _compile(lambda q, k, v, n: attention.decode_attention(
            q, k, v, n, 128 ** -0.5),
            (q.shape, q.dtype), (cache.shape, cache.dtype),
            (cache.shape, cache.dtype), ((256,), jnp.int32))


CASES["decode_attention-256x8x8x128-over-4096-rows"] = _decode_attention_case


def _decode_attention_vmem():
    """Where the plan says ``vmem`` the compiler does: two buffers of K
    and two of V are most of the 16 MiB a kernel may use once the least
    chunk, 128 rows, passes 2 MiB."""
    shape, cache = (4, 32, 4, 256), (4, 32, 1024, 256)
    with _tpu_trace():
        assert attention.decode_attention_plan(
            jax.ShapeDtypeStruct(shape, jnp.float32),
            jax.ShapeDtypeStruct(cache, jnp.float32)) == (1024, "vmem")
    try:
        _compile(lambda q, k, v, n: attention._decode_pallas(
            q, k, v, n, 0.0625, 128, 128),
            (shape, jnp.float32), (cache, jnp.float32),
            (cache, jnp.float32), ((4,), jnp.int32))
    except Exception as e:  # noqa: broad-except — the compiler's refusal
        assert "vmem" in str(e), e
    else:
        raise AssertionError("the compiler took 4 MiB chunks of K and V")


CASES["decode_attention-refuses-what-the-compiler-refuses"] = \
    _decode_attention_vmem


def _exaone_step_with_the_kernel():
    """The K-EXAONE ``jit_step`` as it is traced for the chip, the full
    layer's attention in the kernel: one ``tpu_custom_call``, no score
    array over all ``max_len`` rows, no copy of a cache-sized array (a
    ``pallas_call`` wants its operands in the default layout, which is the
    one the cache lives in), temporaries a fifteenth of what the masked
    read over every row held.  The parameters' shapes are the benchmark's
    own weights', which are drawn by the device and so, as shapes, by
    nobody (``init_params`` draws 3.7 G numbers on the host first)."""
    from benchmark import harness
    from benchmark.families import exaone_moe_engine as family
    from benchmark.reference import exaone_moe_engine as ref
    from benchmark.tools import aot_compile_moe as tool
    from mxnet_tpu.models import exaone_moe as xm

    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "k-exaone-236b-a23b.json"))
    model = xm.ExaoneMoE(family.model_config(xm, ref.sizes(config)),
                         jnp.dtype(config["precision"]["kv_cache"]))
    engine = _unbuilt_engine(
        model, slots=config["engine"]["slots"],
        prefill_buckets=config["engine"]["prefill_buckets"])
    one_chip = _one_chip()
    params, state, keep, extra = family.step_shapes(
        engine, jax.eval_shape(
            lambda: ref.init_weights(config, 0, jax.devices()[0])),
        lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip))
    with _tpu_trace():
        compiled = engine._step_fn.lower(params, state, keep,
                                         extra).compile()
    text = compiled.as_text()
    # the full layer's attention, and the row writes of K and V: the full
    # layer's and the four rings'
    assert text.count("tpu_custom_call") == 1 + 2 * 5
    assert "dynamic-update-slice" not in text
    assert "f32[256,8,8,4096]" not in text
    assert state[0][3].shape == (256, 8, 4096, 128)
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
    copies = tool.cache_copies(text, state)
    assert not copies, "%d copies of a cache array, the first: %s" \
        % (len(copies), copies[0][:200])


CASES["decode-step-k-exaone-256-slots-with-the-decode-kernel"] = \
    _exaone_step_with_the_kernel


def _decode_attention_pairs():
    """The call of ``models/sambay.py``'s full layer and of each of its
    seven cross layers at the Phi-4-mini-flash cell's sizes: 128 slots, 10
    K/V pairs of 128 lanes (two heads of 64 side by side) with 4 queries
    each, 4096 rows, bfloat16.  The plan admits it, in chunks of 256 rows,
    and with the cache donated nothing the size of it is copied."""
    import re

    q = jax.ShapeDtypeStruct((128, 10, 4, 128), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((128, 10, 4096, 128), jnp.bfloat16)
    with _tpu_trace():
        assert attention.decode_attention_plan(q, cache) == (128, None)
        assert attention._decode_chunk(cache) == 256
        text = _decode_attention_over_a_donated_cache(q, cache)
    # the two row writes and the attention, and no update-slice left
    assert text.count("tpu_custom_call") == 3
    assert "dynamic-update-slice" not in text
    assert not re.findall(r"= bf16\[128,10,4096,128\]\{[^}]*\} copy\(", text)


def _decode_attention_over_a_donated_cache(q, cache):
    """Compiled text of a step's use of the kernel: the new row written
    into the donated cache, then the attention over it."""
    one_chip = _one_chip()

    def step(q, ck, cv, k, v, n):
        ck = attention.write_slot_rows(ck, k, n)
        cv = attention.write_slot_rows(cv, v, n)
        return attention._decode_pallas(
            q, ck, cv, n, 0.125, attention._decode_chunk(cache),
            attention._DECODE_PIECE), ck, cv

    sds = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
           for shape, dtype in (
               (q.shape, q.dtype), (cache.shape, cache.dtype),
               (cache.shape, cache.dtype),
               (cache.shape[:2] + cache.shape[3:], cache.dtype),
               (cache.shape[:2] + cache.shape[3:], cache.dtype),
               (cache.shape[:1], jnp.int32))]
    return jax.jit(step, donate_argnums=(1, 2)).lower(*sds).compile() \
        .as_text()


CASES["decode_attention-128x10x4x128-pairs-over-4096-rows"] = \
    _decode_attention_pairs


def _decode_attention_group_of_seven(rows):
    """The call of ``models/smallthinker.py``'s step at the SmallThinker
    cell's sizes: 48 slots, 4 K/V heads of 128 with SEVEN queries each (a
    group that fills no whole sublane tile), bfloat16, over a ring of 4096
    rows and over a full layer of 16,384.  The plan admits both, in chunks
    of 1024 rows (1 MiB of K a copy), the compiler takes what the plan
    admits, and with the cache donated nothing the size of it is copied."""
    def run():
        import re

        q = jax.ShapeDtypeStruct((48, 4, 7, 128), jnp.bfloat16)
        cache = jax.ShapeDtypeStruct((48, 4, rows, 128), jnp.bfloat16)
        with _tpu_trace():
            assert attention.decode_attention_plan(q, cache) == (128, None)
            assert attention._decode_chunk(cache) == 1024
            text = _decode_attention_over_a_donated_cache(q, cache)
        assert text.count("tpu_custom_call") == 3
        assert "dynamic-update-slice" not in text
        assert not re.findall(
            r"= bf16\[48,4,%d,128\]\{[^}]*\} copy\(" % rows, text)
    return run


for _rows in (4096, 16384):
    CASES["decode_attention-48x4x7x128-over-%d-rows" % _rows] = \
        _decode_attention_group_of_seven(_rows)


def _flash_window_case(length, window):
    """The prompt's attention of ``models/smallthinker.py`` at its largest
    bucket and at the window's own length: 28 query heads over 4 K/V heads
    of 128 (read through the index map: no repeated copy of K or V is an
    operand), blocks of 512 rows, a window of 4096 or none, bfloat16.  No
    array of ``length x length`` scores is in the program."""
    def run():
        q = ((1, 28, length, 128), jnp.bfloat16)
        kv = ((1, 4, length, 128), jnp.bfloat16)
        with _tpu_trace():
            assert attention._kernel_refusal(
                jax.ShapeDtypeStruct(*q), jax.ShapeDtypeStruct(*kv),
                512, 512) is None
        sharding = _one_chip()
        args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                for s, d in (q, kv, kv)]
        text = jax.jit(lambda q, k, v: attention._flash_pallas(
            q, k, v, True, 128 ** -0.5, 512, 512, window=window)).lower(
                *args).compile().as_text()
        assert "tpu_custom_call" in text and "flash_attention" in text
        assert "[1,28,%d,128]" % length in text
        assert "%d,%d]" % (length, length) not in text
        assert "bf16[1,28,%d,128]{3,2,1,0} broadcast" % length not in text
    return run


for _length, _window in [(8192, 4096), (8192, None), (4096, 4096),
                         (3072, 4096)]:
    CASES["flash_attention-28-over-4-heads-%d-window-%s"
          % (_length, _window)] = _flash_window_case(_length, _window)


def _smallthinker_case(which):
    """The engine's programs over ``models/smallthinker.py`` at
    ``benchmark/configs/smallthinker-21ba3b-instruct.json``'s sizes (8
    layers of 64 experts, 151,936 rows of vocabulary, the configuration's
    slots x 16,384, bfloat16): each fits the chip beside what it is handed
    (under 15.5 GB in all).  The step reads rings and full layers through
    eight calls of ``decode_attention`` and writes sixteen arrays' rows
    with ``slot_write``; no ``(48, 4, 7, 4096)`` scores, no update-slice,
    no copy of a cache-sized array.  The largest prefill holds under 2 GB
    of temporaries: no ``(heads, P, P)`` scores and no ``(P, 64, 768)``
    product of every expert over every row (eight calls of
    ``flash_attention``, the experts through the kernel
    ``grouped_product`` and no ragged product)."""
    def run():
        from benchmark import harness
        from benchmark.tools import aot_compile_smallthinker as tool

        config = harness.load_json(os.path.join(
            ROOT, "benchmark", "configs",
            "smallthinker-21ba3b-instruct.json"))
        engine, params, state, keep, extra, sds = tool.engine_programs(
            config, _one_chip())
        s = config["engine"]["slots"]
        assert sorted({a.shape for a in state[0]}) == [
            (s, 4, 4096, 128), (s, 4, 16384, 128)]
        with _tpu_trace():
            if which == "step":
                compiled = engine._step_fn.lower(params, state, keep,
                                                 extra).compile()
            else:
                compiled = engine._prefill_fns[which].lower(
                    *tool.prefill_shapes(params, state, which,
                                         sds)).compile()
        ma = compiled.memory_analysis()
        assert ma.argument_size_in_bytes + ma.output_size_in_bytes \
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes < 15.5e9
        text = compiled.as_text()
        if which == "step":
            assert text.count("tpu_custom_call") == 8 + 2 * 8
            assert "dynamic-update-slice" not in text
            assert "f32[%d,4,7,4096]" % s not in text
            assert ma.temp_size_in_bytes < 64e6, ma.temp_size_in_bytes
        else:
            assert text.count("flash_attention") >= 8
            assert text.count("grouped_product") >= 8
            assert "ragged" not in text
            assert ma.temp_size_in_bytes < 2e9, ma.temp_size_in_bytes
            assert "%d,%d]" % (which, which) not in text
            assert "[%d,64,768]" % which not in text
        copies = tool.cache_copies(text, state)
        assert not copies, "%d copies of a cache array, the first: %s" \
            % (len(copies), copies[0][:200])
    return run


CASES["decode-step-smallthinker-no-cache-copy"] = _smallthinker_case("step")
CASES["decode-prefill-8192-smallthinker-under-2-GB-of-temporaries"] = \
    _smallthinker_case(8192)


# -- latent attention ----------------------------------------------------------
def _latent_attention_case():
    """``ops.attention.latent_attention`` over a donated cache at the
    DeepSeek-V2 cell's shapes (128 slots, 128 heads, latent rows of 512 and
    rotated rows padded to 128 lanes, 8192 rows, bfloat16): the plan admits
    it, chunks of 1024 rows (1 MiB of latent rows a copy) multiplied 512
    rows at a time (the scores of a sub-block one register file), the
    kernel's buffers and softmax under the 16 MiB a kernel may use, the new
    rows written by ``slot_write``, and no ``(128, 128, 8192)`` scores."""
    s, h, rows = 128, 128, 8192
    one_chip = _one_chip()
    lat = jax.ShapeDtypeStruct((s, 1, rows, 512), jnp.bfloat16)
    rope = jax.ShapeDtypeStruct((s, 1, rows, 128), jnp.bfloat16)
    ql = jax.ShapeDtypeStruct((s, h, 512), jnp.bfloat16)

    def step(ql, qr, cl, cr, new_l, new_r, n):
        cl = attention.write_slot_rows(cl, new_l, n)
        cr = attention.write_slot_rows(cr, new_r, n)
        return attention.latent_attention(ql, qr, cl, cr, n), cl, cr

    with _tpu_trace():
        assert attention.latent_attention_plan(ql, lat, rope) == (128, None)
        chunk = attention._latent_chunk(lat)
        assert (chunk, attention._latent_sub(h, chunk)) == (1024, 512)
        sds = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
               for shape, dtype in (
                   ((s, h, 512), jnp.bfloat16), ((s, h, 128), jnp.bfloat16),
                   (lat.shape, lat.dtype), (rope.shape, rope.dtype),
                   ((s, 1, 512), jnp.bfloat16), ((s, 1, 128), jnp.bfloat16),
                   ((s,), jnp.int32))]
        # the kernel as it is traced here: what it keeps in VMEM (two
        # buffers of latent and of rotated rows, the accumulator, the
        # running maximum and sum a lane wide) beside two blocks each of
        # its queries and its output, and that it asks for no limit of its
        # own, so that the compile below is held to the chip's 16 MiB
        (kernel,) = [
            eqn for eqn in _equations(jax.make_jaxpr(step)(*sds).jaxpr)
            if eqn.primitive.name == "pallas_call"
            and eqn.params["name"] == "latent_attention"]
        scratch = kernel.params["jaxpr"].invars[
            -kernel.params["grid_mapping"].num_scratch_operands:]
        held = sum(math.prod(v.aval.shape) * v.aval.dtype.itemsize
                   for v in scratch if str(v.aval.memory_space) == "vmem")
        assert held == 2 * chunk * (512 + 128) * 2 \
            + h * (512 + 2 * 128) * 4 == 3014656
        assert held + 2 * h * ((512 + 128) * 2 + 512 * 4) < 16 << 20
        assert kernel.params["compiler_params"]["mosaic_tpu"] \
            .vmem_limit_bytes is None
        text = jax.jit(step, donate_argnums=(2, 3)).lower(*sds).compile() \
            .as_text()
    assert text.count("tpu_custom_call") == 3
    assert "f32[%d,%d,%d]" % (s, h, rows) not in text
    assert "dynamic-update-slice" not in text
    copies = [line for line in text.splitlines()
              if " copy(" in line and ",%d," % rows in line.split("copy(")[0]]
    assert not copies, copies[0][:300]


CASES["latent_attention-128x128x512-over-8192-rows"] = _latent_attention_case


def _latent_attention_lanes():
    """Rotated rows of 64 cached as they are: the plan says ``lanes`` and
    the call takes the masked einsum."""
    ql = jax.ShapeDtypeStruct((128, 128, 512), jnp.bfloat16)
    lat = jax.ShapeDtypeStruct((128, 1, 8192, 512), jnp.bfloat16)
    rope = jax.ShapeDtypeStruct((128, 1, 8192, 64), jnp.bfloat16)
    with _tpu_trace():
        assert attention.latent_attention_plan(ql, lat, rope) \
            == (8192, "lanes")


CASES["latent_attention-refuses-rows-narrower-than-the-lanes"] = \
    _latent_attention_lanes


def _deepseek_v2_case(which):
    """The engine's programs over ``models/deepseek_v2.py`` at
    ``benchmark/configs/deepseek-v2.json``'s sizes (5 layers, 20 experts of
    160 held, 12,800 rows of vocabulary, the configuration's slots x 8192,
    bfloat16): each fits the chip beside what it is handed (under 15.0 GB
    in all).  The step reads the latent rows through five calls of
    ``latent_attention`` and writes ten arrays' rows with ``slot_write``;
    no expanded K or V of held rows (``(slots, 128, 8192, ...)``), no
    ``(slots, 128, 8192)`` scores, no update-slice, no copy of a cache-sized
    array, under 64 MB of temporaries.  The largest prefill holds under
    1.6 GB of temporaries: no ``(heads, P, P)`` scores and no ``(P, 20,
    1536)`` product of every expert over every row (five calls of
    ``flash_attention``, the four expert layers through the kernel
    ``grouped_product`` and no ragged product)."""
    def run():
        from benchmark import harness
        from benchmark.tools import aot_compile_deepseek_v2 as tool

        config = harness.load_json(os.path.join(
            ROOT, "benchmark", "configs", "deepseek-v2.json"))
        engine, params, state, keep, extra, sds = tool.engine_programs(
            config, _one_chip())
        s = config["engine"]["slots"]
        assert sorted({a.shape for a in state[0] + state[1]}) == [
            (s, 1, 8192, 128), (s, 1, 8192, 512)]
        with _tpu_trace():
            if which == "step":
                compiled = engine._step_fn.lower(params, state, keep,
                                                 extra).compile()
            else:
                compiled = engine._prefill_fns[which].lower(
                    *tool.prefill_shapes(params, state, which,
                                         sds)).compile()
        ma = compiled.memory_analysis()
        assert ma.argument_size_in_bytes + ma.output_size_in_bytes \
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes < 15.0e9
        text = compiled.as_text()
        if which == "step":
            assert text.count("tpu_custom_call") == 5 + 2 * 5
            assert text.count("latent_attention") >= 5
            assert "dynamic-update-slice" not in text
            for held in ("[%d,128,8192" % s, "[%d,8192,128," % s):
                assert held not in text, held
            assert ma.temp_size_in_bytes < 64e6, ma.temp_size_in_bytes
        else:
            assert text.count("flash_attention") >= 5
            assert text.count("grouped_product") >= 4
            assert "ragged" not in text
            assert ma.temp_size_in_bytes < 1.6e9, ma.temp_size_in_bytes
            assert "%d,%d]" % (which, which) not in text
            assert "[%d,20,1536]" % which not in text
        copies = tool.cache_copies(text, state)
        assert not copies, "%d copies of a cache array, the first: %s" \
            % (len(copies), copies[0][:200])
    return run


CASES["decode-step-deepseek-v2-no-cache-copy-no-expanded-heads"] = \
    _deepseek_v2_case("step")
CASES["decode-prefill-4096-deepseek-v2-under-1.6-GB-of-temporaries"] = \
    _deepseek_v2_case(4096)


def _sdar_case(which):
    """The engine's programs over ``models/sdar.py`` at
    ``benchmark/configs/sdar-30b-a3b-chat.json``'s sizes (6 layers of 128
    experts, 151,936 rows of vocabulary, the configuration's slots x 4096,
    bfloat16): each fits the chip beside what it is handed (under 15.5 GB
    in all, and under the chip's 16 GB with the temporaries of the second
    step the loop keeps dispatched).  The step, a pass over ``(slots, 8)``
    rows (a slot's pending block and its open one), reads every layer
    through ONE ``decode_attention`` at ``group`` 64, a horizon a half, and
    writes twelve arrays' runs of four rows with ``slot_write`` twice each;
    no ``(slots, 4, 64, 4096)`` scores, no update-slice, no copy of a
    cache-sized array; its 768 rows go through the kernel
    ``grouped_product`` in every layer, so it holds no ``(768, 128, 768)``
    product of every expert over every row, and the head's logits are the
    open blocks' ``(384, vocab)`` alone.
    A prefill calls ``flash_attention`` under the blocked mask for every
    layer but the last, whose output nothing reads, holds no ``(heads, P,
    P)`` scores, and from 512 rows takes the grouped product by the
    kernel; no program holds a ragged product."""
    def run():
        from benchmark import harness
        from benchmark.tools import aot_compile_sdar as tool

        config = harness.load_json(os.path.join(
            ROOT, "benchmark", "configs", "sdar-30b-a3b-chat.json"))
        engine, params, state, keep, extra, sds = tool.engine_programs(
            config, _one_chip())
        s = config["engine"]["slots"]
        assert {a.shape for a in state[0]} == {(s, 4, 4096, 128)}
        assert state[2].shape == (s, 4) and len(state) == 13
        with _tpu_trace():
            if which == "step":
                compiled = engine._step_fn.lower(params, state, keep,
                                                 extra).compile()
            else:
                compiled = engine._prefill_fns[which].lower(
                    *tool.prefill_shapes(params, state, which,
                                         sds)).compile()
        ma = compiled.memory_analysis()
        assert ma.argument_size_in_bytes + ma.output_size_in_bytes \
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes < 15.5e9
        text = compiled.as_text()
        assert "ragged" not in text
        if which == "step":
            # a layer: one decode_attention, K and V each written twice,
            # one grouped product
            assert text.count("tpu_custom_call") == 6 + 4 * 6 + 6
            for kernel, calls in (("decode_attention", 6),
                                  ("slot_write", 24)):
                assert len(re.findall(r"(?m)^\s*%%%s\S* = .* custom-call\("
                                      % kernel, text)) == calls, kernel
            assert text.count("grouped_product") >= 6
            for rows in (4 * s, 8 * s):
                assert "[%d,128,768]" % rows not in text
            assert "dynamic-update-slice" not in text
            assert "f32[%d,4,64,4096]" % s not in text
            # the logits of 4 rows a slot and what the tail makes of them
            assert ma.temp_size_in_bytes < 0.6e9, ma.temp_size_in_bytes
            assert "[%d,151936]" % (4 * s) in text
            assert "[%d,151936]" % (8 * s) not in text
            # the loop keeps two steps dispatched: the second one's
            # temporaries beside the first one's whole
            assert ma.argument_size_in_bytes + ma.output_size_in_bytes \
                - ma.alias_size_in_bytes + 2 * ma.temp_size_in_bytes < 16e9
        else:
            assert text.count("flash_attention") >= 5
            # five layers' experts (the sixth layer's run in no prefill)
            assert ("grouped_product" in text) == (which >= 512)
            assert ma.temp_size_in_bytes < 0.5e9, ma.temp_size_in_bytes
            # (at 128 the queries themselves are ``[32, 128, 128]``)
            assert which == 128 or "32,%d,%d]" % (which, which) not in text
        copies = tool.cache_copies(text, state)
        assert not copies, "%d copies of a cache array, the first: %s" \
            % (len(copies), copies[0][:200])
    return run


CASES["decode-step-sdar-a-pass-no-cache-copy"] = _sdar_case("step")
for _bucket in (128, 512, 1024):
    CASES["decode-prefill-%d-sdar-blocked-mask" % _bucket] = \
        _sdar_case(_bucket)


def _block_kernels_case():
    """The three kernels at the shapes a block model gives them, each
    alone: ``write_slot_rows`` with a run of four rows a slot over a donated
    cache (the compiler takes the kernel, nothing cache-sized is copied),
    ``decode_attention`` at ``group`` 32, and ``flash_attention`` under the
    mask that is causal by blocks of four (a block length that is no power
    of two is refused by name and takes the plain path)."""
    def run():
        import re

        shape = (96, 4, 4096, 128)
        one_chip = _one_chip()

        def sds(sh, dt):
            return jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)

        cache = sds(shape, jnp.bfloat16)
        rows = sds((96, 4, 4, 128), jnp.bfloat16)
        at = sds((96,), jnp.int32)
        with _tpu_trace():
            assert attention.write_slot_rows_plan(cache, rows) == (96, None)
            assert attention.write_slot_rows_plan(
                sds(shape, jnp.float32),
                sds((96, 4, 16, 128), jnp.float32)) == (0, "run")
            written = jax.jit(attention.write_slot_rows,
                              donate_argnums=(0,)).lower(
                                  cache, rows, at).compile()
            q = sds((96, 4, 32, 128), jnp.bfloat16)
            assert attention.decode_attention_plan(q, cache) == (128, None)
            attended = jax.jit(
                lambda q, k, v, n: attention.decode_attention(
                    q, k, v, n, 0.088)).lower(q, cache, cache, at).compile()
            heads = sds((1, 32, 1024, 128), jnp.bfloat16)
            kv = sds((1, 4, 1024, 128), jnp.bfloat16)
            assert attention._kernel_refusal(heads, kv, 512, 512, 4) is None
            assert attention._kernel_refusal(heads, kv, 512, 512, 6) \
                == "block"
            flashed = jax.jit(lambda q, k, v: attention.flash_attention(
                q, k, v, causal=True, block_q=512, block_k=512,
                block=4)).lower(heads, kv, kv).compile()
        text = written.as_text()
        assert text.count("tpu_custom_call") == 1 and "slot_write" in text
        assert "dynamic-update-slice" not in text
        assert not re.findall(
            r"= bf16\[%d,%d,%d,%d\]\{[^}]*\} copy\(" % shape, text)
        assert written.memory_analysis().temp_size_in_bytes < 1e6
        assert "decode_attention" in attended.as_text()
        assert "f32[96,4,32,4096]" not in attended.as_text()
        assert "flash_attention" in flashed.as_text()
    return run


CASES["block-kernels-run-of-4-group-of-32-blocked-mask"] = \
    _block_kernels_case()


def _decode_attention_lanes():
    """Heads of 64 cached on their own, ``(128, 20, 4096, 64)``: the plan
    says ``lanes`` and the call takes the plain path.  The compiler keeps
    such a cache rows-minor, and the kernel's copies move whole tiles of
    128 lanes: made to walk it, the compiler refuses the slice."""
    q = jax.ShapeDtypeStruct((128, 20, 2, 64), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((128, 20, 4096, 64), jnp.bfloat16)
    with _tpu_trace():
        assert attention.decode_attention_plan(q, cache) == (4096, "lanes")
        try:
            _decode_attention_over_a_donated_cache(q, cache)
        except Exception as e:  # noqa: broad-except — the compiler's refusal
            assert "aligned to tiling (128), but is 64" in str(e), e
        else:
            raise AssertionError("the compiler took rows of 64 lanes")


CASES["decode_attention-refuses-rows-narrower-than-the-lanes"] = \
    _decode_attention_lanes


def _slot_write_case(shape, group):
    """``write_slot_rows`` alone over a donated cache at the shapes the
    decode cells write (the Phi-4-mini-flash rings and shared layer,
    K-EXAONE's full layer and rings: the plan holds every slot's tile at
    once) and at more slots than the kernel's VMEM holds tiles for (two
    buffers of ``group`` slots, the last group shorter).  The compiler
    takes the kernel, nothing the size of the cache is copied and nothing
    is held beside it (the parent's whole step took 0.13 GB of temporaries
    in the Phi-4-mini-flash cell; the tiles are the kernel's own VMEM)."""
    def run():
        import re

        s, n, _, d = shape
        one_chip = _one_chip()
        cache, rows, at = (
            jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in ((shape, jnp.bfloat16), ((s, n, d), jnp.bfloat16),
                           ((s,), jnp.int32)))
        with _tpu_trace():
            assert attention.write_slot_rows_plan(cache, rows) \
                == (group, None)
            compiled = jax.jit(attention.write_slot_rows,
                               donate_argnums=(0,)).lower(
                                   cache, rows, at).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and "slot_write" in text
        assert "dynamic-update-slice" not in text
        assert not re.findall(
            r"= bf16\[%d,%d,%d,%d\]\{[^}]*\} copy\(" % shape, text)
        ma = compiled.memory_analysis()
        assert ma.alias_size_in_bytes >= cache.size * 2
        assert ma.temp_size_in_bytes < 1e6, ma.temp_size_in_bytes
    return run


for _shape, _group in [((128, 10, 512, 128), 128), ((128, 10, 4096, 128), 128),
                       ((256, 8, 4096, 128), 256), ((256, 8, 128, 128), 256),
                       ((512, 8, 4096, 128), 192)]:
    CASES["slot_write-%dx%dx%dx%d" % _shape] = _slot_write_case(_shape,
                                                                _group)


def _ssm_scan_case():
    """``ops.ssm.ssm_scan`` alone at the Phi-4-mini-flash prefill's
    largest bucket (1024 positions, ``d_inner`` 5120, ``d_state`` 16,
    float32): what the plan admits (512 lanes of state a grid step, 128
    positions a chunk) the compiler takes, and nothing the size of the
    ``(positions, d_state, d_inner)`` products is held."""
    from mxnet_tpu.ops import ssm

    f32 = jnp.float32
    shapes = [((1024, 5120), f32), ((1024, 5120), f32), ((1024, 16), f32),
              ((1024, 16), f32), ((16, 5120), f32), ((16, 5120), f32)]
    with _tpu_trace():
        assert ssm.ssm_scan_plan(
            jax.ShapeDtypeStruct(*shapes[0]),
            jax.ShapeDtypeStruct(*shapes[4])) == ((512, 128), None)
        _compile(ssm.ssm_scan, *shapes)


CASES["ssm_scan-1024-positions-5120x16"] = _ssm_scan_case


def _sambay_case(which):
    """The engine's programs over ``models/sambay.py`` at
    ``benchmark/configs/phi-4-mini-flash-reasoning.json``'s sizes (all 32
    layers, 200,064 rows of vocabulary, 128 slots x 4096, bfloat16): each
    fits the chip beside the 13.5 GB it is handed, the step's attention
    over the shared layer is eight calls of ``decode_attention`` and a
    prefill's recurrences nine of ``ssm_scan``, and nothing the size of a
    ring, the full layer or a recurrent state is copied."""
    def run():
        from benchmark import harness
        from benchmark.tools import aot_compile_sambay as tool

        config = harness.load_json(os.path.join(
            ROOT, "benchmark", "configs",
            "phi-4-mini-flash-reasoning.json"))
        engine, params, state, keep, extra, sds = tool.engine_programs(
            config, _one_chip())
        assert config["engine"]["slots"] == 128
        big = [a for side in state[:2] for a in side
               if a.size * a.dtype.itemsize > 30e6]
        assert sorted({a.shape for a in big}) == [
            (128, 10, 512, 128), (128, 10, 4096, 128), (128, 16, 5120)]
        with _tpu_trace():
            if which == "step":
                compiled = engine._step_fn.lower(params, state, keep,
                                                 extra).compile()
            else:
                compiled = engine._prefill_fns[which].lower(
                    *tool.prefill_shapes(params, state, which,
                                         sds)).compile()
        ma = compiled.memory_analysis()
        assert 13.4e9 < ma.argument_size_in_bytes < 13.6e9
        assert ma.temp_size_in_bytes < 0.6e9, ma.temp_size_in_bytes
        assert ma.argument_size_in_bytes + ma.output_size_in_bytes \
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes < 15.5e9
        text = compiled.as_text()
        # the step: the eight readers of the shared layer and the row
        # writes of K and V, eight rings' and the shared layer's; a
        # prefill: the nine state-space layers' scans
        assert text.count("tpu_custom_call") == (8 + 2 * 9 if which == "step"
                                                 else 9)
        if which == "step":
            assert "dynamic-update-slice" not in text
            # no more beside its arguments than the parent's step took
            assert ma.temp_size_in_bytes < 0.14e9, ma.temp_size_in_bytes
        copies = tool.cache_copies(text, (big, ()))
        assert not copies, "%d copies of slot state, the first: %s" \
            % (len(copies), copies[0][:200])
    return run


CASES["decode-step-phi-4-mini-flash-128-slots"] = _sambay_case("step")
CASES["decode-prefill-1024-phi-4-mini-flash-128-slots"] = _sambay_case(1024)


def _ssd_scan_case(positions):
    """``ops.ssm.ssd_scan`` alone at the granite-4.0-h-micro prefill's
    shapes (64 heads of 64, a state of 128 values a channel, float32; 1024
    positions in chunks of 256, and the first bucket's one chunk of 128):
    what the plan admits (eight heads' state a grid step) the compiler
    takes: slices of one lane out of a block of eight, the ``(Q, Q)``
    decays of a head, the float32 product with the carried state."""
    def run():
        from mxnet_tpu.ops import ssm

        f32 = jnp.float32
        t = positions
        shapes = [((t, 4096), f32), ((t, 64), f32), ((64,), f32),
                  ((t, 128), f32), ((t, 128), f32), ((64,), f32),
                  ((128, 4096), f32)]
        with _tpu_trace():
            assert ssm.ssd_scan_plan(*(jax.ShapeDtypeStruct(*shapes[i])
                                       for i in (0, 1, 3))) \
                == ((8, min(t, 256)), None)
            _compile(ssm.ssd_scan, *shapes)
    return run


for _positions in (128, 1024):
    CASES["ssd_scan-%d-positions-64x64x128" % _positions] = \
        _ssd_scan_case(_positions)


def _granite_case(which):
    """The engine's programs over ``models/granite_hybrid.py`` at
    ``benchmark/configs/granite-4.0-h-micro.json``'s sizes (all 40 layers,
    100,352 rows of vocabulary, 64 slots x 4096, bfloat16 weights and K/V,
    float32 states): each fits the chip beside the 13.4 GB it is handed.
    The step's four attention layers are a ``decode_attention`` and two
    ``slot_write`` each over heads cached in pairs (no ``lanes`` refusal),
    its 36 state updates plain fusions that copy no state; a prefill's
    recurrences are 36 calls of ``ssd_scan``."""
    def run():
        from benchmark import harness
        from benchmark.tools import aot_compile_granite_hybrid as tool

        config = harness.load_json(os.path.join(
            ROOT, "benchmark", "configs", tool.CONFIG + ".json"))
        engine, params, state, keep, extra, sds = tool.engine_programs(
            config, _one_chip())
        assert config["engine"]["slots"] == 64
        big = [a for side in state[:2] for a in side
               if a.size * a.dtype.itemsize > 30e6]
        assert sorted({a.shape for a in big}) == [
            (64, 4, 4096, 128), (64, 128, 4096)]
        assert len(big) == 36 + 2 * 4
        with _tpu_trace():
            if which == "step":
                compiled = engine._step_fn.lower(params, state, keep,
                                                 extra).compile()
            else:
                compiled = engine._prefill_fns[which].lower(
                    *tool.prefill_shapes(params, state, which,
                                         sds)).compile()
        ma = compiled.memory_analysis()
        assert 13.3e9 < ma.argument_size_in_bytes < 13.5e9
        assert ma.argument_size_in_bytes + ma.output_size_in_bytes \
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes < 15.5e9
        text = compiled.as_text()
        if which == "step":
            assert text.count("tpu_custom_call") == 3 * 4
            for kernel, calls in (("decode_attention", 4),
                                  ("slot_write", 8)):
                assert len(re.findall(r"(?m)^\s*%%%s\S* = .* custom-call\("
                                      % kernel, text)) == calls, kernel
            assert "dynamic-update-slice" not in text
            assert ma.temp_size_in_bytes < 0.2e9, ma.temp_size_in_bytes
        else:
            assert text.count("tpu_custom_call") == 36
            assert text.count("ssd_scan") >= 36
        copies = tool.cache_copies(text, (big, ()))
        assert not copies, "%d copies of slot state, the first: %s" \
            % (len(copies), copies[0][:200])
    return run


CASES["decode-step-granite-4.0-h-micro-64-slots"] = _granite_case("step")
CASES["decode-prefill-128-granite-4.0-h-micro-64-slots"] = _granite_case(128)
CASES["decode-prefill-1024-granite-4.0-h-micro-64-slots"] = \
    _granite_case(1024)


# -- the tests -----------------------------------------------------------------
#: the engines' cases have a file a model family (``test_tpu_aot_<family>.py``:
#: under ``--dist loadfile`` only a file can go to another worker); the
#: kernels' own cases are this file's
FAMILIES = ("gpt2-large", "k-exaone", "phi-4-mini-flash", "smallthinker",
            "deepseek-v2", "sdar", "granite-4.0-h-micro")


def cases_of(family=None):
    """Names of the engine cases of ``family``; of the kernels' own cases
    without one."""
    if family is None:
        return sorted(c for c in CASES
                      if not any(f in c for f in FAMILIES))
    return sorted(c for c in CASES if family in c)


def compile_in_a_child(case):
    env = dict(os.environ, TPU_LOG_DIR="disabled",
               # nothing attaches a chip, so several children may load the
               # TPU compiler at once; libtpu's one-process lockfile would
               # otherwise let one in and make the others skip
               ALLOW_MULTIPLE_LIBTPU_LOAD="1")
    # five times what the slowest case took in the driver's take-up run of
    # PR 38 (the Phi-4-mini-flash prefill, 38 s beside five other workers)
    proc = subprocess.run([sys.executable, os.path.abspath(__file__), case],
                          env=env, capture_output=True, text=True,
                          timeout=200)
    if proc.returncode == _SKIP:
        pytest.skip(proc.stdout.strip()[-300:])
    assert proc.returncode == 0, proc.stderr[-3000:]


@pytest.mark.parametrize("case", cases_of())
def test_kernel_compiles_for_a_described_v5e(case):
    compile_in_a_child(case)


if __name__ == "__main__":
    assert not jax.config.jax_enable_compilation_cache, \
        "run with JAX_ENABLE_COMPILATION_CACHE=false (tests/conftest.py)"
    CASES[sys.argv[1]]()
