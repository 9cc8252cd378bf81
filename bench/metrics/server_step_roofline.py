"""The server step's share of its HBM roofline: the least time the
chip's HBM bandwidth allows for the bytes the step must move (read each
client's parameters before and after training, write its next ones, read
and write the global; ``bench/counts.py``), over the device time of the
server-step operations, per round."""

from bench import counts
from bench.metrics import server_step_ms_per_round as server


def read(ctx):
    s = server.seconds(ctx)
    if s <= 0:
        return None
    least = counts.server_bytes_per_round(
        ctx["setup"]["client_layers"], ctx["setup"]["global_layers"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * ctx["rounds"] / s
