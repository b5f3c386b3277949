"""Engine host: mean time per request in the engine's decision
(``decide``, ``plan_for``, ``account`` and the bandwidth read), less the
classifier it calls, in ms, on the harness clock."""


def read(ctx):
    own = [(r["sched"][1] - r["sched"][0])
           - (r["classify"][1] - r["classify"][0])
           for r in ctx.requests if "sched" in r and "classify" in r]
    return sum(own) / len(own) * 1e3 if own else None
