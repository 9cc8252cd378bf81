"""Host time of the driver's dropout-rate allocation (the ``allocate``
span of ``repro.obs``: the Eq. (9)-(11) LP) per round."""


def read(ctx):
    s = ctx["trace"]["host"].get("allocate", 0.0)
    return 1e3 * s / ctx["rounds"] if s > 0 else None
