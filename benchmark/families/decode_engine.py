"""Adapter of the decode tier: ``serving.lm_pool(..., n_replicas=1)`` ->
``ReplicaPool`` -> ``DecodeEngine`` over ``models/transformer_lm.py``, the
objects ``/generate`` calls, driven in-process through ``pool.generate``
with ``on_token`` timestamps (the HTTP front end is not in this path).

From the program it takes the system under test and its counters only; the
weights are the reference's (``reference/decode_engine.py``), made on the
device from the seed, and the reference judges what the window served."""

import time

import jax
import numpy as np

from benchmark.reference import decode_engine as ref


class System:
    """The server protocol the ``open_loop`` and ``closed_loop`` generators
    drive: ``slots``, ``submit``, ``wait``, ``cancel``, ``error_of``,
    ``pending``, ``refused``."""

    def __init__(self, config, traffic, seed, devices):
        del traffic
        from mxnet_tpu import serving
        from mxnet_tpu.models import transformer_lm as tlm
        from mxnet_tpu.serving.batcher import InvalidRequest, Overloaded

        self.refused = (Overloaded, InvalidRequest)
        self.config = config
        self.seed = seed
        self.device = devices[0]
        vocab, embed, heads, layers, ffn, max_len = ref.sizes(config)
        # an end-of-sequence id no token can equal: every session runs its
        # full length, so the work is what the traffic file says
        self.cfg = tlm.LMConfig(vocab, embed, heads, layers, ffn, max_len,
                                eos_id=vocab)
        self.params = ref.init_weights(config, seed, self.device)
        engine = config["engine"]
        self.slots = int(engine["slots"])
        self.pool = serving.lm_pool(
            self.cfg, self.params, n_replicas=1, devices=[self.device],
            name="bench-lm",
            engine_opts={"slots": self.slots,
                         "prefill_buckets": tuple(engine["prefill_buckets"]),
                         "kv_layout": engine["kv_layout"]})
        self.engine = self.pool.replicas[0].engine
        # one short session through the whole path, so the first counted
        # request does not pay the host's first-call costs
        self.wait(self.submit(np.zeros((4,), np.int32), 2, None), 600)

    def submit(self, prompt, max_new, on_token):
        return self.pool.generate(prompt, max_new_tokens=max_new,
                                  temperature=0.0, on_token=on_token,
                                  seed=0)

    def wait(self, handle, timeout):
        """None when the session finished, else the error (a timeout is
        one)."""
        try:
            handle.result(timeout)
            return None
        except Exception as e:  # noqa: broad-except — every failure of a
            # session is the request's outcome, counted by the generator
            return e

    def error_of(self, handle):
        if not handle.done():
            return None
        return self.wait(handle, 0.0)

    def cancel(self, handle):
        handle.cancel()

    def pending(self):
        return self.engine.pending_rows()

    def counters(self):
        """Program counters the per-layer readers use."""
        return {"decode_steps": self.engine.steps,
                "tokens_out": self.engine.tokens_out}

    def scratch_bytes(self):
        """Temporaries of the decode-step program, by ``memory_analysis()``
        of the engine's step lowered again for its own shapes (a cache
        hit): the device allocator's own peak leaves a program's scratch
        out."""
        import jax.numpy as jnp

        cfg, s = self.cfg, self.slots

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        kv = sds((s, cfg.max_len, cfg.heads, cfg.embed // cfg.heads),
                 jnp.float32)
        state = (tuple(kv for _ in range(cfg.layers)),
                 tuple(kv for _ in range(cfg.layers)),
                 sds((s,), jnp.int32), sds((s,), jnp.int32),
                 sds((s,), jnp.int32), sds((s,), jnp.bool_),
                 sds((s,), jnp.float32), sds((s,), jnp.uint32))
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), self.params)
        compiled = self.engine._step_fn.lower(
            params, state, sds((s,), jnp.bool_)).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    def close(self):
        """Stop the server and free its device state (the cache and the
        engine's view of the weights), so the reference has the chip."""
        self.pool.close(drain=False)
        self.pool = self.engine = None

    # -- correct ----------------------------------------------------------
    def check(self, window, with_control=False):
        """Once the window has closed and the server is gone: EVERY request
        the window finished, so every slot and the longest request are
        among them and the mean is over some thousands of served tokens.
        The reference runs once over each prompt with its served tokens,
        and the numbers compared are the widest and the mean gap by which
        a served token's logit lies below the reference's best.  The mean
        is the steady one, and the one the lower precision fails; the
        widest is held against a token altered where it is produced.

        ``with_control`` also reads, at the same positions, the gap of the
        token the lower-precision forward puts first: bfloat16, the
        control, and fp8 weights beside it as a reading."""
        limits = self.config["limits"]
        finished = [r for r in window["requests"] if r.finished()]
        if not finished:
            return [{"name": "served_token_gap", "value": None,
                     "limit": limits["served_token_gap"], "ok": False,
                     "why": "no request finished in the window"}], None
        max_len = self.cfg.max_len
        heads = self.cfg.heads
        steps = ("bfloat16", "fp8") if with_control else ()
        # per reading: widest gap, sum of gaps, tokens not the best
        read = {who: [0.0, 0.0, 0] for who in ("served",) + steps}
        tokens_read = 0
        with jax.default_device(self.device):
            for r in finished:
                n_prompt, n = len(r.prompt), len(r.prompt) + len(r.tokens)
                seq = np.zeros((max_len,), np.int32)
                seq[:n_prompt] = r.prompt
                seq[n_prompt:n] = r.tokens
                seq = jax.numpy.asarray(seq)
                logits = ref.reference_logits(heads, self.params, seq)
                # the token at position p was chosen from the logits at
                # p - 1; padding lies after every position read
                chosen = {"served": jax.numpy.roll(seq, -1)}
                for step in steps:
                    chosen[step] = ref.lower_precision_argmax(
                        heads, step, self.params, seq)
                for who, toks in chosen.items():
                    gaps = np.asarray(ref.gaps_below_best(logits, toks))
                    gaps = gaps[n_prompt - 1:n - 1]
                    acc = read[who]
                    acc[0] = max(acc[0], float(gaps.max()))
                    acc[1] += float(gaps.sum())
                    acc[2] += int((gaps > 0).sum())
                tokens_read += n - n_prompt

        def entries(who, **more):
            widest, total, off = read[who]
            mean = total / tokens_read
            return [dict(name="served_token_gap", value=widest,
                         limit=limits["served_token_gap"],
                         ok=widest <= limits["served_token_gap"], **more),
                    dict(name="served_token_mean_gap", value=mean,
                         limit=limits["served_token_mean_gap"],
                         ok=mean <= limits["served_token_mean_gap"],
                         not_the_best=off, **more)]

        compared = entries("served", tokens=tokens_read,
                           requests=len(finished))
        control = [e for step in steps
                   for e in entries(step, control=step)] or None
        return compared, control
