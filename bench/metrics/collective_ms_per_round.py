"""Device time of the cross-chip collective operations (all-reduce,
all-gather, collective-permute, reduce-scatter, all-to-all, matched by
instruction name in ``bench/trace.py``) per round, averaged over the
chips: the Eq. (4) exchange of a client-sharded fleet."""


def read(ctx):
    s = sum(ctx["trace"]["collectives"].values())
    return 1e3 * s / ctx["rounds"] if s > 0 else None
