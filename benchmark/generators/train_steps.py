"""Training traffic: one batch made from ``--seed`` on the device and
replayed, step after step, until the window closes (what
``train_imagenet.py --benchmark 1`` offers: compute only, no input
pipeline).  The traffic file fixes the global batch, the kvstore and how
many first steps the reference follows; there is nothing to schedule, so
the plan is the file itself."""


def plan(params, seed, seconds, config):
    del seed, seconds, config
    return dict(params)


def drive(system, plan_, params, seconds, tracer):
    del plan_, params
    return system.train(seconds, tracer)
