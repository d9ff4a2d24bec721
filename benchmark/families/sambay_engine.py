"""Adapter of the decode tier over the SambaY-shaped model:
``serving.lm_pool(model, ...)`` -> ``ReplicaPool`` -> ``DecodeEngine`` over
``models/sambay.py`` through the engine's model protocol, driven in-process
as ``families/decode_engine.py`` drives the first model (the HTTP front end
is not in this path).

From the program it takes the system under test and its counters only; the
weights are the reference's (``reference/sambay_engine.py``), made on the
device from the seed in bfloat16, and the reference judges what the window
served."""

import random

import jax
import numpy as np

from benchmark.families import decode_engine as first
from benchmark.reference import sambay_engine as ref

#: the reference reads rows of this many tokens, sequences laid end to end
#: in them, so that the check compiles one shape and not one a length
ROW = 4096


def model_config(sb, z):
    """``ref.sizes(config)`` as the program's ``SambaYConfig``, with an
    end-of-sequence id no token can equal: every session runs its full
    length, so the work is what the traffic file says."""
    return sb.SambaYConfig(eos_id=z["vocab"], **{
        k: z[k] for k in sb.SambaYConfig._fields if k in z})


def model_of(config):
    import jax.numpy as jnp

    from mxnet_tpu.models import sambay as sb

    return sb.SambaY(model_config(sb, ref.sizes(config)),
                     jnp.dtype(config["precision"]["kv_cache"]))


def step_shapes(engine, params, sds):
    """``(params, state, keep, extra)`` of the engine's decode step as
    shapes made by ``sds(shape, dtype)``: what ``engine._step_fn`` lowers
    for.  The state holds every entry's first array, every entry's second,
    and the six arrays of ``(slots,)``."""
    import jax.numpy as jnp

    from mxnet_tpu.models import transformer_lm as tlm

    s = engine.slots
    held = [tuple(sds((s,) + shape, dtype) for shape, dtype in (
        tlm.slot_arrays(c)[i] for c in engine.model.cache_spec()))
        for i in range(2)]
    state = (held[0], held[1], sds((s,), jnp.int32), sds((s,), jnp.int32),
             sds((s,), jnp.int32), sds((s,), jnp.bool_),
             sds((s,), jnp.float32), sds((s,), jnp.uint32))
    params, extra = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        (params, engine.model.extra_state()))
    return params, state, sds((s,), jnp.bool_), extra


class System(first.System):
    """The server protocol the ``closed_loop`` generator drives, as the
    first model's adapter has it (``submit``, ``wait``, ``cancel``,
    ``error_of``, ``pending``, ``refused``, ``close``); its own are the
    model, the counters, the step's shapes and the check."""

    def __init__(self, config, traffic, seed, devices):
        del traffic
        from mxnet_tpu import serving
        from mxnet_tpu.serving.batcher import InvalidRequest, Overloaded

        self.refused = (Overloaded, InvalidRequest)
        self.config = config
        self.seed = seed
        self.device = devices[0]
        self.sizes = ref.sizes(config)
        self.params = ref.init_weights(config, seed, self.device)
        engine = config["engine"]
        self.slots = int(engine["slots"])
        model = model_of(config)
        self.cfg = model.cfg
        self.pool = serving.lm_pool(
            model, self.params, n_replicas=1, devices=[self.device],
            name="bench-sambay",
            engine_opts={"slots": self.slots,
                         "prefill_buckets": tuple(engine["prefill_buckets"]),
                         "kv_layout": engine["kv_layout"],
                         "max_queue": int(engine["max_queue"])})
        self.engine = self.pool.replicas[0].engine
        # one short session through the whole path, so the first counted
        # request does not pay the host's first-call costs
        self.wait(self.submit(np.zeros((4,), np.int32), 2, None), 600)

    def counters(self):
        """Program counters the per-layer readers use: the engine's steps
        and tokens, and the model's row counters (one small device read;
        the harness asks at both ends of the traced seconds and once after
        the window)."""
        model = self.engine.model_counters()
        return {"decode_steps": self.engine.steps,
                "tokens_out": self.engine.tokens_out,
                "rows_full": model["rows_full"],
                "rows_ring": model["rows_ring"],
                "rows": model["rows"], "ssm_steps": model["steps"]}

    def scratch_bytes(self):
        """Temporaries of the decode-step program, by ``memory_analysis()``
        of the engine's step lowered again for its own shapes (a cache
        hit)."""
        compiled = self.engine._step_fn.lower(*step_shapes(
            self.engine, self.params, jax.ShapeDtypeStruct)).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    # -- correct ----------------------------------------------------------
    def check(self, window, with_control=False):
        """Once the window has closed and the server is gone: a seeded
        sample of ``check_sessions`` of the requests the window finished,
        the longest among them (fewer finished is not correct).  The
        float32 ``highest`` reference runs once over each prompt with its
        served tokens, so prefill and decoding through every kind of slot
        state are judged against the full forward pass; compared are the
        widest and the mean gap by which a served token's logit lies below
        the reference's best.

        ``with_control`` also reads, at the same positions, the gap of the
        token that a plain forward pass puts first in the stated precision
        (a reading) and in each control's: the weights through fp8, and the
        recurrent state kept in bfloat16."""
        limits = self.config["limits"]
        want = int(limits["check_sessions"])
        finished = [r for r in window["requests"] if r.finished()]
        if len(finished) < want:
            return [{"name": "served_token_gap", "value": None,
                     "limit": limits["served_token_gap"], "ok": False,
                     "why": "%d requests finished in the window, the "
                            "check reads %d" % (len(finished), want)}], None
        finished.sort(key=lambda r: (len(r.prompt) + len(r.tokens),
                                     r.index))
        rng = random.Random("%d/check" % self.seed)
        sample = [finished[-1]] + rng.sample(finished[:-1], want - 1)
        z = self.sizes
        steps = ((ref.STATED,) + ref.CONTROLS) if with_control else ()
        names = ("served",) + tuple(p.name for p in steps)
        # per reading: widest gap, sum of gaps, tokens not the best
        read = {who: [0.0, 0.0, 0] for who in names}
        tokens_read = 0
        lengths = [len(r.prompt) + len(r.tokens) for r in sample]
        width = max(ROW, z["max_len"])
        with jax.default_device(self.device):
            for row in ref.pack(lengths, width):
                seq, seg, pos = (np.zeros((width,), np.int32)
                                 for _ in range(3))
                seg[:] = -1             # padding is no sequence's
                # padding starts a sequence a token: its state is reset
                for i, start in row:
                    r, n = sample[i], lengths[i]
                    seq[start:start + n] = np.concatenate(
                        [r.prompt, r.tokens])
                    seg[start:start + n] = i
                    pos[start:start + n] = np.arange(n)
                seq, seg, pos = (jax.numpy.asarray(a)
                                 for a in (seq, seg, pos))
                # the token at position p was chosen from the logits at
                # p - 1, which lie one place before it in the row too
                chosen = [jax.numpy.roll(seq, -1)] + [
                    ref.best_tokens(self.params, ref.forward_hidden(
                        z, self.params, seq, seg, pos, pr), pr)
                    for pr in steps]
                gaps = np.asarray(ref.gaps_below_best(
                    self.params,
                    ref.forward_hidden(z, self.params, seq, seg, pos),
                    jax.numpy.stack(chosen)))
                for who, mine_all in zip(names, gaps):
                    acc = read[who]
                    for i, start in row:
                        mine = mine_all[start + len(sample[i].prompt) - 1:
                                        start + lengths[i] - 1]
                        acc[0] = max(acc[0], float(mine.max()))
                        acc[1] += float(mine.sum())
                        acc[2] += int((mine > 0).sum())
                tokens_read += sum(len(sample[i].tokens) for i, _ in row)

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
                           requests=len(sample),
                           longest=len(sample[0].prompt)
                           + len(sample[0].tokens))
        control = [e for pr in steps
                   for e in entries(pr.name, control=pr.name)] or None
        return compared, control
