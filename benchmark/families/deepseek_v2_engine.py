"""Adapter of the decode tier over the DeepSeek-V2-shaped model:
``serving.lm_pool(model, ...)`` -> ``ReplicaPool`` -> ``DecodeEngine`` over
``models/deepseek_v2.py`` through the engine's model protocol, driven
in-process as ``families/decode_engine.py`` drives the first model (the
HTTP front end is not in this path).

From the program it takes the system under test and its counters only; the
weights are the reference's (``reference/deepseek_v2_engine.py``), made on
the device from the seed in bfloat16, and the reference judges what the
window served."""

import random

import jax
import numpy as np

from benchmark.families import decode_engine as first
from benchmark.families.sambay_engine import step_shapes  # noqa: F401 — the tools ask the family for it
from benchmark.reference import deepseek_v2_engine as ref


def model_of(config):
    """The program's model object at ``ref.sizes(config)``, with an
    end-of-sequence id no token can equal: every session runs its full
    length, so the work is what the traffic file says."""
    import jax.numpy as jnp

    from mxnet_tpu.models import deepseek_v2 as dm

    z = ref.sizes(config)
    return dm.DeepSeekV2(
        dm.DeepSeekV2Config(eos_id=z["vocab"], **{
            k: z[k] for k in dm.DeepSeekV2Config._fields if k in z}),
        jnp.dtype(config["precision"]["kv_cache"]))


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
        # first, so that a program without this model fails at once
        model = model_of(config)
        self.cfg = model.cfg
        self.params = ref.init_weights(config, seed, self.device)
        engine = config["engine"]
        self.slots = int(engine["slots"])
        self.pool = serving.lm_pool(
            model, self.params, n_replicas=1, devices=[self.device],
            name="bench-deepseek-v2",
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
        and tokens, and the model's routing and row counters (one small
        device read; the harness asks at both ends of the traced seconds
        and once after the window)."""
        model = self.engine.model_counters()
        return {"decode_steps": self.engine.steps,
                "tokens_out": self.engine.tokens_out,
                "moe_picks": np.asarray(model["moe_picks"], np.int64),
                "moe_picks_total": model["moe_picks_total"],
                "moe_rows": model["rows"], "moe_steps": model["steps"],
                "rows_latent": model["rows_latent"],
                "rows_reached": model["rows_reached"]}

    def scratch_bytes(self):
        """Temporaries of the decode-step program, by ``memory_analysis()``
        of the engine's step lowered again for its own shapes (a cache
        hit)."""
        compiled = self.engine._step_fn.lower(*step_shapes(
            self.engine, self.params, jax.ShapeDtypeStruct)).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    # -- correct ----------------------------------------------------------
    def sample(self, window):
        """The ``check_sessions`` sessions the check reads, of those that
        produced tokens in the window, finished or cut at its close: the
        one that holds the most rows, one that was admitted inside the
        window if any was, and a seeded sample of the rest."""
        want = int(self.config["limits"]["check_sessions"])
        served = [r for r in window["requests"]
                  if r.error is None and r.tokens
                  and r.token_times[-1] >= window["t0"]]
        served.sort(key=lambda r: (len(r.prompt) + len(r.tokens), r.index))
        if len(served) < want:
            return served, want
        took = [served.pop()]
        inside = [r for r in served if r.sent >= window["t0"]]
        if inside:
            took.append(inside[-1])
            served.remove(inside[-1])
        rng = random.Random("%d/check" % self.seed)
        return took + rng.sample(served, want - len(took)), want

    def check(self, window, with_control=False):
        """Once the window has closed and the server is gone: the sessions
        of :meth:`sample` (fewer served is not correct).  The float32
        ``highest`` reference runs once over each prompt with the tokens it
        was served, so prefill through the expanded heads and the grouped
        experts, and decoding through the absorbed form over the latent
        rows, are judged against the full forward pass in the expanded
        form; compared are the widest and the mean gap by which a served
        token's logit lies below the reference's best (``widest_at``: the
        position of the widest's token in its session of ``widest_of``
        rows).

        ``with_control`` also reads, at the same positions, the gap of the
        token that a plain forward pass puts first in the stated precision
        (a reading) and in each control: the weights through fp8, and the
        latent cache kept in fp8."""
        limits = self.config["limits"]
        sample, want = self.sample(window)
        if len(sample) < want:
            return [{"name": "served_token_gap", "value": None,
                     "limit": limits["served_token_gap"], "ok": False,
                     "why": "%d sessions produced tokens in the window, "
                            "the check reads %d" % (len(sample), want)}], \
                None
        z = self.sizes
        steps = ((ref.STATED,) + ref.CONTROLS) if with_control else ()
        names = ("served",) + tuple(v.name for v in steps)
        # per reading: widest gap, sum of gaps, tokens not the best
        read = {who: [0.0, 0.0, 0] for who in names}
        where = (0, 0)      # of the served reading's widest: position, rows
        tokens_read = 0
        lengths = [len(r.prompt) + len(r.tokens) for r in sample]
        width = z["max_len"]
        with jax.default_device(self.device):
            for row in ref.pack(lengths, width):
                seq, seg, pos = (np.zeros((width,), np.int32)
                                 for _ in range(3))
                seg[:] = -1             # padding is no sequence's
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
                    ref.best_tokens(z, self.params, ref.forward_hidden(
                        z, self.params, seq, seg, pos, v), v)
                    for v in steps]
                gaps = np.asarray(ref.gaps_below_best(
                    z, self.params,
                    ref.forward_hidden(z, self.params, seq, seg, pos),
                    jax.numpy.stack(chosen)))
                for who, mine_all in zip(names, gaps):
                    acc = read[who]
                    for i, start in row:
                        mine = mine_all[start + len(sample[i].prompt) - 1:
                                        start + lengths[i] - 1]
                        if who == "served" and float(mine.max()) > acc[0]:
                            where = (len(sample[i].prompt)
                                     + int(mine.argmax()), lengths[i])
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

        compared = entries(
            "served", tokens=tokens_read, requests=len(sample),
            longest=lengths[0], shortest=min(lengths),
            widest_at=where[0], widest_of=where[1],
            admitted_inside=sum(1 for r in sample
                                if r.sent >= window["t0"]),
            finished=sum(1 for r in sample if r.finished()))
        control = [e for v in steps
                   for e in entries(v.name, control=v.name)] or None
        return compared, control
