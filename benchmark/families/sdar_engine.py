"""Adapter of the decode tier over the SDAR-shaped model:
``serving.lm_pool(model, ...)`` -> ``ReplicaPool`` -> ``DecodeEngine`` over
``models/sdar.py`` through the engine's model protocol, driven in-process as
``families/decode_engine.py`` drives the first model (the HTTP front end is
not in this path).  The model generates by diffusion over blocks: a step is
a pass and delivers none or a block's tokens a slot.

From the program it takes the system under test, its counters and, with a
session's tokens, the pass at which each was fixed; the weights are the
reference's (``reference/sdar_engine.py``), made on the device from the
seed in bfloat16, and the reference judges what the window served."""

import jax
import numpy as np

from benchmark.families import decode_engine as first
from benchmark.families import deepseek_v2_engine
from benchmark.reference import sdar_engine as ref


def model_of(config):
    """The program's model object at ``ref.sizes(config)``, with an
    end-of-sequence id no token can equal: every session runs its full
    length, so the work is what the traffic file says."""
    import jax.numpy as jnp

    from mxnet_tpu.models import sdar

    z = ref.sizes(config)
    return sdar.SDAR(
        sdar.SDARConfig(eos_id=z["vocab"], **{
            k: z[k] for k in sdar.SDARConfig._fields if k in z}),
        jnp.dtype(config["precision"]["kv_cache"]))


def step_shapes(engine, params, sds):
    """``(params, state, keep, extra)`` of the engine's decode step as
    shapes made by ``sds(shape, dtype)``: what ``engine._step_fn`` lowers
    for.  The state's layout is the engine's to say
    (``DecodeEngine.state_shapes``)."""
    import jax.numpy as jnp

    params, extra = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        (params, engine.model.extra_state()))
    return (params, engine.state_shapes(sds),
            sds((engine.slots,), jnp.bool_), extra)


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
            name="bench-sdar",
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
        and tokens, and the model's routing, pass and row counters (one
        small device read; the harness asks at both ends of the traced
        seconds and once after the window)."""
        model = self.engine.model_counters()
        out = {"decode_steps": self.engine.steps,
               "tokens_out": self.engine.tokens_out,
               "moe_picks": np.asarray(model["moe_picks"], np.int64),
               "moe_picks_total": model["moe_picks_total"],
               "moe_rows": model["rows"], "moe_steps": model["steps"]}
        for name in ("passes", "commits", "tokens_committed", "rows_read",
                     "fixed_by_threshold", "fixed_by_quota"):
            out["sdar_" + name] = model[name]
        return out

    def scratch_bytes(self):
        """Temporaries of the decode-step program, by ``memory_analysis()``
        of the engine's step lowered again for its own shapes (a cache
        hit)."""
        compiled = self.engine._step_fn.lower(*step_shapes(
            self.engine, self.params, jax.ShapeDtypeStruct)).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    # -- correct ----------------------------------------------------------
    #: the ``check_sessions`` sessions the check reads: the one that holds
    #: the most rows, one admitted inside the window, a seeded sample of
    #: the rest of those that produced tokens in it
    sample = deepseek_v2_engine.System.sample

    def check(self, window, with_control=False):
        """Once the window has closed and the server is gone: the sessions
        of :meth:`sample` (fewer served is not correct).  EVERY served
        token of a whole block is judged at the pass in which it was fixed,
        with its block as it stood at that pass (``ref.replay``: the final
        transcript once for the K and V every later block reads, then each
        pass over all blocks' states before it).  Compared are the widest
        and the mean gap by which a served token's logit lies below the
        reference's best at its position and pass (``served_token_gap``,
        ``served_token_mean_gap``), and for the CHOICE OF POSITION the mean
        gap by which the fixed position's log-confidence (best logit less
        the log of the softmax's sum) lies below the best among the block's
        positions not yet fixed at that pass (``fixed_position_mean_gap``;
        0 where the reference's confidence passes the threshold, which
        fixes a position whatever its rank).  That entry also carries the
        WIDEST position gap as a reading (``widest``): with seeded weights
        a block's confidences lie so close that no fault read on the chip
        moved it three times past a sound run (the configuration's
        ``limits_why``), so no limit judges it.  A session's last block is
        left out where the session ends inside it: the positions beyond
        its end were never delivered, so the block's states cannot be
        replayed.

        ``with_control`` also reads, at the same positions and passes, the
        gaps of the token and of the position that a plain replay puts
        first in the stated precision (a reading) and in each control: the
        weights through fp8, and the K/V cache kept in fp8."""
        limits = self.config["limits"]
        names = ("served_token_gap", "served_token_mean_gap",
                 "fixed_position_mean_gap")
        sample, want = self.sample(window)
        if len(sample) < want:
            return [{"name": names[0], "value": None,
                     "limit": limits[names[0]], "ok": False,
                     "why": "%d sessions produced tokens in the window, "
                            "the check reads %d" % (len(sample), want)}], \
                None
        z = self.sizes
        b, passes = z["block"], z["denoise_steps"]
        steps = ((ref.STATED,) + ref.CONTROLS) if with_control else ()
        who_all = ("served",) + tuple(v.name for v in steps)
        # per reading: [widest, sum, count not 0] of the token's gap and of
        # the position's
        read = {who: [[0.0, 0.0, 0], [0.0, 0.0, 0]] for who in who_all}
        judged = skipped = 0
        where = (0, 0, 0)   # of the served widest: position, rows, pass
        # whole blocks alone: the transcript up to its last whole block
        lengths = [(len(r.prompt) + len(r.tokens)) // b * b for r in sample]
        width = z["max_len"]
        with jax.default_device(self.device):
            for row in ref.pack(lengths, width):
                seq = np.zeros((width,), np.int32)
                seg = np.full((width,), -1, np.int32)   # padding: nobody's
                pos = np.zeros((width,), np.int32)
                at = np.full((width,), -1, np.int32)
                for i, start in row:
                    r, n = sample[i], lengths[i]
                    made = max(n - len(r.prompt), 0)
                    seq[start:start + n] = np.concatenate(
                        [r.prompt, r.tokens])[:n]
                    seg[start:start + n] = i
                    pos[start:start + n] = np.arange(n)
                    at[start + n - made:start + n] = \
                        r.handle.fixed_at[:made]
                    judged += made
                    skipped += len(r.tokens) - made
                seq_d, seg_d, pos_d, at_d = (jax.numpy.asarray(a)
                                             for a in (seq, seg, pos, at))
                # the reference's own passes, then each reading's
                streams = ref.replay(z, self.params, seq_d, at_d, seg_d,
                                     pos_d)
                mine = [np.asarray(a) for a in zip(*[
                    ref.read_rows(z, self.params, x, seq_d)
                    for x in streams])]                 # each (passes, T)
                conf = mine[0] - mine[2]
                open_at = at[None, :] >= np.arange(passes)[:, None]
                blocks = conf.reshape(passes, -1, b)
                # the best log-confidence among a block's open positions
                top = np.where(open_at.reshape(blocks.shape), blocks,
                               -np.inf).max(-1, keepdims=True)
                fixed_here = at[None, :] == np.arange(passes)[:, None]
                for who in who_all:
                    if who == "served":
                        token_gap = mine[0] - mine[3]
                        place = fixed_here
                    else:
                        v = ref.VARIANTS[who]
                        theirs = [np.asarray(a) for a in zip(*[
                            ref.read_rows(z, self.params, x, seq_d, v)
                            for x in ref.replay(z, self.params, seq_d, at_d,
                                                seg_d, pos_d, v)])]
                        # the reference's logit of the token they put first
                        took = np.stack([np.asarray(ref.read_rows(
                            z, self.params, x, jax.numpy.asarray(c))[3])
                            for x, c in zip(streams, theirs[1])])
                        token_gap = mine[0] - took
                        # the open position of each block they would fix,
                        # in the passes that fixed one
                        pick = np.where(open_at, theirs[0] - theirs[2],
                                        -np.inf).reshape(
                                            blocks.shape).argmax(-1)
                        place = ((np.arange(b) == pick[..., None])
                                 & fixed_here.reshape(blocks.shape).any(
                                     -1, keepdims=True)).reshape(
                                         open_at.shape) & open_at
                    place_gap = np.where(
                        conf > np.log(z["threshold"]), 0.0,
                        (top - blocks).reshape(conf.shape))
                    for acc, gaps, hit in (
                            (read[who][0], token_gap, fixed_here),
                            (read[who][1], place_gap, place)):
                        if not hit.any():
                            continue
                        if who == "served" and acc is read[who][0] \
                                and float(gaps[hit].max()) > acc[0]:
                            t, p = np.unravel_index(
                                np.where(hit, gaps, -1.0).argmax(),
                                gaps.shape)
                            i = int(seg[p])
                            where = (int(pos[p]), lengths[i], int(t))
                        acc[0] = max(acc[0], float(gaps[hit].max()))
                        acc[1] += float(gaps[hit].sum())
                        acc[2] += int((gaps[hit] > 0).sum())

        def entries(who, **more):
            token, place = read[who]

            def mean(name, acc, **reading):
                value = acc[1] / max(judged, 1)
                return dict(name=name, value=value, limit=limits[name],
                            ok=value <= limits[name], not_the_best=acc[2],
                            **reading, **more)

            return [dict(name=names[0], value=token[0],
                         limit=limits[names[0]],
                         ok=token[0] <= limits[names[0]], **more),
                    mean(names[1], token),
                    mean(names[2], place, widest=place[0])]

        compared = entries(
            "served", tokens=judged, left_out=skipped, requests=len(sample),
            longest=lengths[0] if lengths else 0, shortest=min(lengths),
            widest_at=where[0], widest_of=where[1], widest_pass=where[2],
            admitted_inside=sum(1 for r in sample
                                if r.sent >= window["t0"]),
            finished=sum(1 for r in sample if r.finished()))
        if not judged:
            compared[0].update(ok=False, why="no whole block was served")
        control = [e for v in steps
                   for e in entries(v.name, control=v.name)] or None
        return compared, control
