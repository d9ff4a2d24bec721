"""Output tokens delivered per second of the window, every token whose
``on_token`` fell inside it, over the window's whole length."""


def compute(run):
    w = run["window"]
    t0, t_end = w["t0"], w["t_end"]
    n = sum(1 for r in w["requests"] for t in r.token_times
            if t0 <= t <= t_end)
    print("decode_tokens: %d in %.3f s" % (n, t_end - t0), flush=True)
    return n / (t_end - t0)
