"""Closed-loop traffic: a fixed number of clients, each sending its next
request when its last one completed, so the server is always offered
exactly as much as it finishes.  Callers that wait for a reply make such
traffic; with more clients than slots every slot stays full and the
queueing and the generator's own timing drop out.

The plan is a pure function of the traffic file and ``--seed``: the fixed
quantile set of sizes, shuffled by the seed and dealt to the clients in
turn; a client that runs out starts its hand again.  A ramp, not counted,
fills the slots before the window opens."""

import queue
import time

from benchmark import loadgen
from benchmark.harness import annotate


def plan(params, seed, seconds, config):
    del seconds                 # the clients run until the window closes
    n = int(params["request_set"])
    rng = loadgen.seeded(seed, "closed")
    return loadgen.make_requests(
        loadgen.request_sizes(params, n, rng),
        int(config["vocab_size"]), seed)


def drive(system, requests, params, seconds, tracer):
    clients = int(params["clients_per_slot"]) * system.slots
    ramp = float(params["ramp_seconds"])
    sample_every = float(params["sample_seconds"])
    hands = [requests[c::clients] for c in range(clients)]
    turn = [0] * clients
    done = queue.Queue()
    sent, live = [], {}
    start = time.monotonic()
    t0 = start + ramp
    t_close = t0 + seconds

    def send(c):
        proto = hands[c][turn[c] % len(hands[c])]
        turn[c] += 1
        r = loadgen.Request(len(sent), proto.prompt, proto.max_new)

        def on_token(tok, r=r, c=c):
            r.on_token(tok)
            if len(r.tokens) == r.max_new:
                done.put(c)

        r.sent = time.monotonic()
        sent.append(r)
        try:
            with annotate("submit"):
                r.handle = system.submit(r.prompt, r.max_new, on_token)
            live[c] = r
        except system.refused as e:
            r.error = e
            done.put(c)             # the client tries its next request

    for c in range(clients):
        send(c)
    pending_samples = []
    next_sample = t0
    while True:
        now = time.monotonic()
        if now >= t_close:
            break
        tracer.poll(now - t0)
        if now >= next_sample:
            pending_samples.append(system.pending())
            next_sample += sample_every
            for c, r in list(live.items()):     # a session that was shed
                err = system.error_of(r.handle)
                if err is not None and r.error is None:
                    r.error = err
                    done.put(c)
        try:
            c = done.get(timeout=max(0.0, min(next_sample, t_close)
                                     - time.monotonic()))
        except queue.Empty:
            continue
        live.pop(c, None)
        send(c)
    t_end = time.monotonic()
    tracer.close()
    for r in live.values():
        r.cancelled = True
        system.cancel(r.handle)
    # what the window saw: sent in it, or still producing tokens in it
    counted = [r for r in sent if r.sent >= t0
               or (r.token_times and r.token_times[-1] >= t0)]
    return {"t0": t0, "t_end": t_end, "requests": counted,
            "attempted": len(counted),
            "failed": sum(1 for r in counted if r.error is not None),
            "pending_samples": pending_samples,
            "queue_at_close": pending_samples[-1] if pending_samples else 0}
