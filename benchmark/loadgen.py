"""What the serving generators share: the fixed set of request sizes that
every seed deals in another order, and the record kept of each request.

Every seed gets the SAME set (the quantiles of the traffic file's
distributions) and only shuffles it, so two seeds offer the same work and
differ in order alone; sizes drawn afresh per seed would make the seed
change the work and the runs spread for that reason."""

import math
import random
import statistics
import time

import numpy as np


def lognormal_quantiles(n, median, sigma, lo, hi):
    """``n`` whole numbers: the (i + 0.5)/n quantiles of a lognormal with
    this median and sigma, clipped to [lo, hi]."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        v = median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(hi, max(lo, round(v)))))
    return out


class Request:
    """One request and what became of it.  ``token_times`` is appended to
    from the server's thread by :meth:`on_token`; everything else is
    written by the generator's one thread."""

    __slots__ = ("index", "sent", "prompt", "max_new", "token_times",
                 "tokens", "handle", "error", "cancelled")

    def __init__(self, index, prompt, max_new):
        self.index = index
        self.sent = None
        self.prompt = prompt
        self.max_new = max_new
        self.token_times = []
        self.tokens = []
        self.handle = None
        self.error = None
        self.cancelled = False

    def on_token(self, tok):
        self.tokens.append(int(tok))
        self.token_times.append(time.monotonic())

    def finished(self):
        return self.error is None and len(self.tokens) == self.max_new


def request_sizes(params, n, rng):
    """``n`` (prompt length, output length) pairs: both quantile sets,
    shuffled independently by ``rng``."""
    p, o = params["prompt_tokens"], params["output_tokens"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                  p["max"])
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                  o["max"])
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    return list(zip(prompts, outputs))


def make_requests(sizes, token_below, seed):
    """Requests of these sizes with prompts of random tokens below
    ``token_below``, all drawn from ``seed``."""
    rs = np.random.default_rng([seed % (2 ** 32), seed // (2 ** 32)])
    return [Request(i, rs.integers(0, token_below, size=p, dtype=np.int32),
                    o)
            for i, (p, o) in enumerate(sizes)]


def seeded(seed, stream):
    return random.Random("%d/%s" % (seed, stream))
