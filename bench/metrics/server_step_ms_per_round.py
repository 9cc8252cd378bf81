"""Device time of the FedDD server step per round: operations under the
round engine's other ``feddd_*`` scopes (select, encode_masks,
encode_wire, aggregate, client_update, allocate, clock), averaged over
the chips."""

SERVER = ("select", "encode_masks", "encode_wire", "aggregate",
          "client_update", "allocate", "clock")


def seconds(ctx):
    return sum(ctx["trace"]["phases"].get(p, 0.0) for p in SERVER)


def read(ctx):
    s = seconds(ctx)
    return 1e3 * s / ctx["rounds"] if s > 0 else None
