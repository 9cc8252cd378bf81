"""Whole round's share of the chips' bf16 peak: the forward and backward
FLOPs local training needs (and one evaluation pass where the mix
evaluates every round), counted from shapes by ``bench/counts.py``, times
the rounds of the traced window, over the window and chips x peak."""

from bench import counts


def read(ctx):
    s, red = ctx["setup"], ctx["trace"]
    flops = counts.round_flops(
        s["client_layers"], s["xs"].shape[1], s["epochs"],
        s["global_layers"] if s["eval"] else None, len(s["yte"]))
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops * ctx["rounds"] / red["window_s"] / peak
