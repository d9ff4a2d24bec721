"""On the chip, in one process: the K-EXAONE decode step
(``models/exaone_moe.py`` ``decode_step`` at ``configs/k-exaone-236b-a23b``'s
widths, state donated, seeded weights) timed alone with each candidate
cache write and routed-expert product in the program's place.  What PERF.md
section 6 (PR 27) records was read with it.

    chiprun -- python benchmark/tools/moe_step_variants.py [variant ...]
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STEPS = 30


def _scatter(cache, rows, at):
    import jax.numpy as jnp

    return cache.at[jnp.arange(cache.shape[0]), :, at].set(
        rows.astype(cache.dtype))


def _ragged(xm, cfg):
    """The routed product as XLA's grouped matmul over the picks sorted by
    held expert (``lax.ragged_dot``); picks of absent experts sort last and
    fall outside every group."""
    import jax
    import jax.numpy as jnp

    def sparse_mlp(cfg, h, moe, shared=True):
        chosen, w = xm.route(cfg, h, moe)
        t, k, x = h.shape[0], cfg.top_k, cfg.experts_held
        local = chosen - cfg.first_expert
        held = (local >= 0) & (local < x)
        flat = jnp.where(held, local, x).reshape(-1)
        order = jnp.argsort(flat)
        rows = (jnp.arange(t * k) // k)[order]
        sizes = jnp.bincount(flat, length=x + 1)[:x].astype(jnp.int32)
        dt = moe["gate"].dtype
        xs = h.astype(dt)[rows]
        g = jax.lax.ragged_dot(xs, moe["gate"], sizes,
                               preferred_element_type=jnp.float32)
        u = jax.lax.ragged_dot(xs, moe["up"], sizes,
                               preferred_element_type=jnp.float32)
        scale = (w.reshape(-1) * held.reshape(-1))[order]
        a = (jax.nn.silu(g) * u * scale[:, None]).astype(dt)
        y = jax.lax.ragged_dot(a, moe["down"], sizes,
                               preferred_element_type=jnp.float32)
        y = jnp.zeros((t, h.shape[1]), jnp.float32).at[rows].add(
            y * (scale != 0)[:, None])
        if shared:
            y = y + xm._swiglu(h, moe["shared"])
        return y, chosen

    return sparse_mlp


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness
    from benchmark.families import exaone_moe_engine as family
    from benchmark.reference import exaone_moe_engine as ref
    from mxnet_tpu.models import exaone_moe as xm
    from mxnet_tpu.models import transformer_lm as tlm

    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "k-exaone-236b-a23b.json"))
    cfg = family.model_config(xm, ref.sizes(config))
    device = jax.devices()[0]
    params = ref.init_weights(config, 7, device)
    s = int(config["engine"]["slots"])
    model = xm.ExaoneMoE(cfg)
    select = xm.write_ring
    slices = xm.write_full
    variants = {
        "program": {},
        "ring=slices": {"write_ring": slices},
        "ring=scatter": {"write_ring": _scatter},
        "full=scatter": {"write_full": _scatter},
        "full=select": {"write_full": select},
        "experts=ragged_dot": {"sparse_mlp": _ragged(xm, cfg)},
    }
    names = sys.argv[1:] or list(variants)
    rs = np.random.RandomState(0)
    lengths = jnp.asarray(rs.randint(64, 3000, (s,)), jnp.int32)
    for name in names:
        saved = {k: getattr(xm, k) for k in variants[name]}
        for k, fn in variants[name].items():
            setattr(xm, k, fn)
        try:
            step = jax.jit(model.decode_step, donate_argnums=(1, 2))
            kv = [tuple(jnp.zeros((s,) + tlm.slot_shape(c), c.dtype)
                        for c in model.cache_spec()) for _ in range(2)]
            extra = jax.device_put(model.extra_state(), device)
            last = jnp.asarray(rs.randint(0, cfg.vocab, (s,)), jnp.int32)
            active = jnp.ones((s,), bool)
            t0 = time.monotonic()
            lowered = step.lower(params, kv[0], kv[1], last, lengths, active,
                                 extra)
            compiled = lowered.compile()
            t_compile = time.monotonic() - t0
            ck, cv = kv
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
            print("VARIANT " + json.dumps({
                "variant": name, "step_ms": ms, "compile_s": t_compile,
                "temporaries_gb": compiled.memory_analysis()
                .temp_size_in_bytes / 1e9,
                "logits_abs_mean": float(jnp.abs(logits).mean()),
                "first_logits": [float(v) for v in logits[0, :3]]}),
                flush=True)
            del ck, cv, kv, logits, compiled
        except Exception as e:  # noqa: broad-except — a variant the chip's
            # compiler refuses is a reading too
            print("VARIANT " + json.dumps({
                "variant": name, "error": "%s: %s" % (
                    type(e).__name__, str(e)[:300])}), flush=True)
        finally:
            for k, fn in saved.items():
                setattr(xm, k, fn)


if __name__ == "__main__":
    main()
