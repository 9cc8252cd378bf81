"""Device time of local training per round: operations under the
``feddd_local_train`` scope (the scanned round) or in the caller's jitted
training program (per-round dispatch), averaged over the chips."""


def read(ctx):
    s = ctx["trace"]["phases"].get("local_train", 0.0)
    return 1e3 * s / ctx["rounds"] if s > 0 else None
