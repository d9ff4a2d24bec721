"""A plain serial decode loop over a ``DecodeEngine``'s own programs, and
the comparison of the engine's loop with it.

:func:`serial_loop` is the reference the engine's pipelined loop
(``serving/decode.py`` ``_serve_loop``) has to equal token for token: admit
queued requests into free slots in order (one ``jit_prefill`` each, its
first token read at once), dispatch ONE ``jit_step``, read its packed
buffer, fan it out, again — every read made with nothing queued behind it.
``tests/test_decode.py`` holds the engine to it on the CPU, dense and paged.

Run as a script it makes the same comparison on the chip at a benchmark
configuration of the K-EXAONE family (the weights are the benchmark
reference's, drawn from ``--seed``):

    chiprun -- python tests/serial_loop.py --sessions 64

Phase 1 queues ``--sessions`` requests on a stopped engine and starts it, so
the engine and the serial loop launch the same programs in the same order
on the same rows: the streams must be equal bit for bit.  Phase 2 sends
more requests than the engine has slots, so that slots are reused a step
later than the serial loop reuses them.  Prints one JSON line; exit code 1
when a stream differs.
"""

import argparse
import json
import os
import sys
import time
from collections import deque

import numpy as np


def serial_loop(eng, requests):
    """``requests`` (``(prompt, max_new_tokens, temperature, seed)`` each)
    through a stopped engine's ``jit_prefill`` and ``jit_step`` on a fresh
    state, serially.  Returns each request's tokens."""
    kv, cfg = eng._kv, eng.cfg
    state = eng._fresh_state()
    holders, lens = [None] * eng.slots, [0] * eng.slots
    todo = deque(range(len(requests)))
    tokens = [[] for _ in requests]

    def retire(slot):
        holders[slot] = None
        if kv is not None:
            kv.release(slot)

    while todo or any(h is not None for h in holders):
        for slot in range(eng.slots):
            if holders[slot] is not None or not todo:
                continue
            r = todo.popleft()
            prompt, new, temperature, seed = requests[r]
            full = np.array(prompt, np.int32)
            n = int(full.size)
            tail = (np.int32(min(n + new - 1, cfg.max_len)),
                    np.float32(temperature), np.uint32(seed), np.bool_(True))
            start = 0
            if kv is not None:
                plan = kv.admit(slot, full)
                start = plan.start
            bucket = next(b for b in eng.prefill_buckets if n - start <= b)
            padded = np.zeros((bucket,), np.int32)
            padded[:n - start] = full[start:]
            if kv is not None:
                state, out = eng._prefill_fns[bucket](
                    eng._params, state, padded, np.int32(start), np.int32(n),
                    np.int32(slot), np.ascontiguousarray(kv.tables[slot]),
                    *tail, np.int32(plan.cow_src), np.int32(plan.cow_dst))
                kv.offer(slot, full)
            else:
                state, out = eng._prefill_fns[bucket](
                    eng._params, state, padded, np.int32(n), np.int32(slot),
                    *tail)
            out = np.asarray(out)
            tokens[r].append(int(out[0]))
            holders[slot], lens[slot] = r, n
            if out[1]:
                retire(slot)
        keep = np.ones((eng.slots,), bool)
        if kv is not None:
            for slot, r in enumerate(holders):
                if r is not None:
                    kv.append(slot, min(lens[slot], cfg.max_len - 1))
            state, packed = eng._step_fn(eng._params, state, keep,
                                         np.ascontiguousarray(kv.tables))
        else:
            state, packed = eng._dispatch_step(state, keep)
        packed = np.asarray(packed)
        for slot, r in enumerate(holders):
            if r is None:
                continue
            if packed[0, slot] >= 0:
                tokens[r].append(int(packed[0, slot]))
                lens[slot] += 1
            if packed[1, slot]:
                retire(slot)
    return tokens


def _requests(rng, count, eng, prompt_max, new_max):
    """Prompts of lognormal length (median 96) and outputs of uniform
    length, cut to what the engine admits; every other one sampled at
    temperature 0.8."""
    cfg = eng.cfg
    new_max = min(new_max, cfg.max_len // 2)
    prompt_max = min(prompt_max, eng.prefill_buckets[-1],
                     cfg.max_len - new_max - 1)
    out = []
    for i in range(count):
        n = int(np.clip(rng.lognormal(np.log(96.0), 0.8), 1, prompt_max))
        out.append((rng.randint(0, cfg.vocab, size=n).astype(np.int32),
                    int(rng.randint(1, new_max + 1)), 0.8 * (i % 2),
                    int(rng.randint(0, 2 ** 31))))
    return out


def _through_engine(eng, requests, held):
    """The same requests through the engine's own loop; ``held``: queued
    before it starts, so that its first turn admits them all."""
    streams = [[] for _ in requests]
    t0 = time.monotonic()
    if not held:
        eng.start()
    sessions = [eng.submit(p, max_new_tokens=new, temperature=t, seed=s,
                           on_token=streams[i].append)
                for i, (p, new, t, s) in enumerate(requests)]
    eng.start()     # a no-op on an engine that runs
    tokens = [sess.result(600) for sess in sessions]
    seconds = time.monotonic() - t0
    eng.stop()
    assert streams == tokens, "a stream is not its transcript"
    return tokens, seconds


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config",
                    default="benchmark/configs/k-exaone-236b-a23b.json")
    ap.add_argument("--sessions", type=int, default=64)
    ap.add_argument("--seed", type=int, default=2147487533)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.getcwd())

    import jax
    import jax.numpy as jnp

    from benchmark.families import exaone_moe_engine as family
    from benchmark.reference import exaone_moe_engine as ref
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import exaone_moe as xm
    from mxnet_tpu.serving import DecodeEngine

    with open(args.config) as f:
        config = json.load(f)
    device = jax.devices()[0]
    cfg = family.model_config(xm, ref.sizes(config))
    opts = config["engine"]
    telemetry.enable()
    eng = DecodeEngine(
        xm.ExaoneMoE(cfg, jnp.dtype(config["precision"]["kv_cache"])),
        ref.init_weights(config, args.seed, device), device=device,
        slots=int(opts["slots"]), name="serial-check", autostart=False,
        prefill_buckets=tuple(opts["prefill_buckets"]),
        kv_layout=opts["kv_layout"],
        max_queue=args.sessions + 2 * int(opts["slots"]))
    rng = np.random.RandomState(args.seed % (2 ** 31))
    phases = {
        "same_order": _requests(rng, args.sessions, eng, 1000, 96),
        "slots_reused": _requests(rng, eng.slots + args.sessions, eng,
                                  500, 48),
    }
    result = {"device": device.device_kind, "slots": eng.slots,
              "config": args.config, "seed": args.seed}
    ok = True
    for name, requests in phases.items():
        got, seconds = _through_engine(eng, requests,
                                       held=name == "same_order")
        t0 = time.monotonic()
        want = serial_loop(eng, requests)
        differ = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
        ok = ok and not differ
        result[name] = {
            "sessions": len(requests), "tokens": sum(map(len, want)),
            "streams_that_differ": len(differ), "first": differ[:5],
            "engine_s": round(seconds, 3),
            "serial_s": round(time.monotonic() - t0, 3)}
    result["overlap_share"] = telemetry.snapshot()["gauges"].get(
        "serving.decode.overlap_share")
    result["steps"] = eng.steps
    eng.close(drain=False)
    result["ok"] = ok
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
