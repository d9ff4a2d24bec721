"""Adapter of the decode tier over the Granite-4.0-H-shaped model:
``serving.lm_pool(model, ...)`` -> ``ReplicaPool`` -> ``DecodeEngine`` over
``models/granite_hybrid.py`` through the engine's model protocol, driven
in-process as ``families/decode_engine.py`` drives the first model (the
HTTP front end is not in this path).

From the program it takes the system under test and its counters only; the
weights are the reference's (``reference/granite_hybrid_engine.py``), made
on the device from the seed in bfloat16, and the reference judges what the
window served."""

import random

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import decode_engine as first
from benchmark.families.sdar_engine import step_shapes  # noqa: F401 — the tools ask the family for it; the engine says its own state's layout
from benchmark.reference import granite_hybrid_engine as ref

#: tokens a row of the reference's call holds, or ``max_len`` where that is
#: more: one compiled length for every check
ROW = 4096


def model_of(config):
    """The program's model object at ``ref.sizes(config)``, with an
    end-of-sequence id no token can equal: every session runs its full
    length, so the work is what the traffic file says."""
    from mxnet_tpu.models import granite_hybrid as gh

    z = ref.sizes(config)
    return gh.GraniteHybrid(
        gh.GraniteHybridConfig(eos_id=z["vocab"], **{
            k: z[k] for k in gh.GraniteHybridConfig._fields if k in z}),
        jnp.dtype(config["precision"]["kv_cache"]))


class System(first.System):
    """The server protocol the ``closed_loop`` generator drives, as the
    first model's adapter has it (``submit``, ``wait``, ``cancel``,
    ``error_of``, ``pending``, ``refused``, ``close``); its own are the
    model, the counters, the step's shapes and the check."""

    def __init__(self, config, traffic, seed, devices):
        del traffic
        # first, so that a program without this model fails at once
        model = model_of(config)
        from mxnet_tpu import serving
        from mxnet_tpu.serving.batcher import InvalidRequest, Overloaded

        self.refused = (Overloaded, InvalidRequest)
        self.config = config
        self.seed = seed
        self.device = devices[0]
        self.sizes = ref.sizes(config)
        self.cfg = model.cfg
        self.params = ref.init_weights(config, seed, self.device)
        engine = config["engine"]
        self.slots = int(engine["slots"])
        self.pool = serving.lm_pool(
            model, self.params, n_replicas=1, devices=[self.device],
            name="bench-granite",
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
                "rows": model["rows"], "rows_full": model["rows_full"],
                "ssd_steps": model["steps"]}

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
        float32 ``highest`` reference reads each prompt with its served
        tokens once, whole sequences end to end in rows of ``max_len``, so
        prefill and decoding through both kinds of slot state are judged
        against the full forward pass; compared are the widest and the mean
        gap by which a served token's logit lies below the reference's
        best.

        ``with_control`` also reads, at the same positions, the gaps of the
        token a plain forward pass puts first in the stated precision and
        in each of ``ref.CONTROLS``.  Written out here as the Phi-4
        adapter has it (sample, rows and entries), over this family's
        reference: that adapter's function names its own (ROADMAP D15)."""
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
        lengths = [len(r.prompt) + len(r.tokens) for r in sample]
        width = max(ROW, z["max_len"])
        with jax.default_device(self.device):
            for row in ref.pack(lengths, width):
                seq, seg, pos = (np.zeros((width,), np.int32)
                                 for _ in range(3))
                seg[:] = -1             # padding is no sequence's
                for i, start in row:
                    n = lengths[i]
                    seq[start:start + n] = np.concatenate(
                        [sample[i].prompt, sample[i].tokens])
                    seg[start:start + n] = i
                    pos[start:start + n] = np.arange(n)
                seq, seg, pos = (jnp.asarray(a) for a in (seq, seg, pos))
                # the token at position p was chosen from the logits at
                # p - 1, which lie one place before it in the row too
                chosen = [jnp.roll(seq, -1)] + [
                    ref.best_tokens(self.params, ref.forward_hidden(
                        z, self.params, seq, seg, pos, pr), pr)
                    for pr in steps]
                gaps = np.asarray(ref.gaps_below_best(
                    self.params,
                    ref.forward_hidden(z, self.params, seq, seg, pos),
                    jnp.stack(chosen)))
                for who, mine_all in zip(names, gaps):
                    acc = read[who]
                    for i, start in row:
                        mine = mine_all[start + len(sample[i].prompt) - 1:
                                        start + lengths[i] - 1]
                        acc[0] = max(acc[0], float(mine.max()))
                        acc[1] += float(mine.sum())
                        acc[2] += int((mine > 0).sum())
        tokens_read = sum(len(r.tokens) for r in sample)

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
