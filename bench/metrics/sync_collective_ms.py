"""Device time of collective operations per averaging round, in ms, on the
slowest device: all-reduce, all-gather, reduce-scatter, collective-permute
and all-to-all events in the traced window over the rounds in it."""


def read(ctx):
    if ctx.rounds == 0 or ctx.chips == 1:
        return None
    slowest = max(d["collective_s"] for d in ctx.trace["devices"])
    return None if slowest == 0 else 1e3 * slowest / ctx.rounds
