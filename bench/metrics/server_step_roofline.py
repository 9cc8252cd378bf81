"""The server step's share of its HBM roofline: the least time the
chip's HBM bandwidth allows for the bytes the step must move (read each
client's parameters before and after training, write its next ones, read
and write the global; ``bench/counts.py``), over the device time of the
server-step operations, per round.  The fleet's bytes are shared out over
the cell's chips, as the server-step time is averaged over them."""

from bench import counts, families
from bench.metrics import server_step_ms_per_round as server


def read(ctx):
    s = server.seconds(ctx)
    if s <= 0:
        return None
    setup = ctx["setup"]
    least = counts.server_bytes_per_round(
        families.load(setup["family"]), setup["client_models"],
        setup["global_model"]) / ctx["chips"] \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * ctx["rounds"] / s
