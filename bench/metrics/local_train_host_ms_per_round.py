"""Host time of the executors' local-training phase (the ``local_train``
span of ``repro.obs``) per round: the dispatch of the fused trainer, or
the per-client Python loop of a ragged fleet."""


def read(ctx):
    s = ctx["trace"]["host"].get("local_train", 0.0)
    return 1e3 * s / ctx["rounds"] if s > 0 else None
