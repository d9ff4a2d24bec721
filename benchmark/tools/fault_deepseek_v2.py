"""On the chip: one run of the DeepSeek-V2 cell with a fault planted under
the timed path that moves FEW tokens FAR, the reading the limit on the
widest gap (``limits.served_token_gap``) stands below.  The faults of
``tests/benchmark/test_bench_deepseek_v2.py`` move every token a little and
are the mean gap's to catch; this one leaves the mean inside its limit.

``crossed``: at a step where a slot's position is a multiple of ``--every``
the slot is handed the logits of the slot before it, so one token in
``--every`` of each session is another session's best.  The reference,
which reads every served token under the tokens served before it, sees that
token alone lie far below its best; the tokens after it follow from it and
are judged as served.

    chiprun -- python3 benchmark/tools/fault_deepseek_v2.py --every 1024 \\
        --seed <n> [--seconds 30]

Prints what ``benchmark/run.py`` prints: the ``compared`` lines and, last,
the result line (``correct`` false is the point)."""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CELL = "deepseek-v2.doc-saturate"


def plant_crossed(every):
    import jax.numpy as jnp

    from mxnet_tpu.models import deepseek_v2 as dm

    plain = dm.DeepSeekV2.decode_step

    def crossed(self, params, cache_lat, cache_rope, last_tok, lengths,
                active, extra):
        logits, *rest = plain(self, params, cache_lat, cache_rope, last_tok,
                              lengths, active, extra)
        hit = (lengths % every == 0)[:, None]
        return (jnp.where(hit, jnp.roll(logits, 1, axis=0), logits), *rest)

    dm.DeepSeekV2.decode_step = crossed


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--every", type=int, default=1024)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    opts = parser.parse_args()
    plant_crossed(opts.every)
    from benchmark import run

    sys.argv = [sys.argv[0], "--workload", CELL, "--seed", str(opts.seed),
                "--seconds", str(opts.seconds), "--trace", "0"]
    run.main()


if __name__ == "__main__":
    main()
