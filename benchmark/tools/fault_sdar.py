"""On the chip: one run of the SDAR cell with a fault planted under the
timed path that moves FEW tokens, or few positions, FAR: the reading the
limit on the widest token gap (``limits.served_token_gap``) stands below,
and the reading that showed the widest position gap to hold no limit (the
configuration's ``limits_why``).  The faults of
``tests/benchmark/test_bench_sdar.py`` move every token a little and are the
mean gaps' to catch, and were read at the tiny size alone.

``crossed``: a slot whose block starts at a row that ``--every`` divides is
handed, in the pass that finds the block all open, the logits of the slot
before it: one token of that block is another session's best.  The
reference, which judges a served token at the pass that fixed it with its
block as it stood, sees that token alone lie far below its best; what
follows it is judged as served.

``out_of_turn``: in every block that starts at such a row the open rows
but the LEAST confident are flattened about their best logit, so every
token stays its row's best and the sampler, which ranks by the softmax's
best probability, fixes the least confident open position first: a
position fixed as far out of turn as the block's confidences allow.
``--every 4`` is every block.

    chiprun -- python3 benchmark/tools/fault_sdar.py crossed --every 2048 \\
        --seed <n> [--seconds 30]

Prints what ``benchmark/run.py`` prints: the ``compared`` lines and, last,
the result line (``correct`` false is the point)."""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "sdar-30b-a3b-chat.reason-saturate"


def plant(fault, every):
    """Replace ``SDAR.decode_step`` by one with ``fault`` in the blocks
    that start at a row ``every`` divides."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models import sdar

    plain = sdar.SDAR.decode_step

    def crossed(logits, hit, still_open):
        first_pass = still_open.all(-1)
        return jnp.where((hit & first_pass)[:, None, None],
                         jnp.roll(logits, 1, axis=0), logits)

    def out_of_turn(logits, hit, still_open):
        best = logits.max(-1, keepdims=True)
        conf = best[..., 0] - jax.nn.logsumexp(logits, axis=-1)
        least = jnp.where(still_open, conf, jnp.inf)
        least = least == least.min(-1, keepdims=True)
        flatten = hit[:, None] & still_open & ~least
        return jnp.where(flatten[..., None],
                         best + 0.25 * (logits - best), logits)

    change = {"crossed": crossed, "out_of_turn": out_of_turn}[fault]

    def faulty(self, params, cache_k, cache_v, block, lengths, active,
               extra):
        logits, *rest = plain(self, params, cache_k, cache_v, block,
                              lengths, active, extra)
        # a position not yet fixed reads the mask token
        return (change(logits, lengths % every == 0,
                       block == self.cfg.mask_id), *rest)

    sdar.SDAR.decode_step = faulty


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("fault", choices=("crossed", "out_of_turn"))
    parser.add_argument("--every", type=int, default=2048)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    opts = parser.parse_args()
    plant(opts.fault, opts.every)
    from benchmark import run

    sys.argv = [sys.argv[0], "--workload", CELL, "--seed", str(opts.seed),
                "--seconds", str(opts.seconds), "--trace", "0"]
    run.main()


if __name__ == "__main__":
    main()
