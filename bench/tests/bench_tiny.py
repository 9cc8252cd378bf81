"""Cells of BENCHMARK.json cut to a size a CPU test run holds: every
width divided by 8, four clients (a ragged fleet: one client of each of
its two widest and two narrowest models), an eval set of 64 samples.
The traffic, limits and everything else are the cell's own."""

import copy

from bench import run

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def tiny_cell(name: str) -> dict:
    cell = copy.deepcopy(run.load_cell(name))
    conf = cell["config_data"]

    def cut(spec):
        return {"conv": [max(1, w // 8) for w in spec["conv"]],
                "fc": [max(1, w // 8) for w in spec["fc"]]}

    conf["global"] = cut(conf["global"])
    homogeneous = len(conf["fleet"]) == 1
    fleet = conf["fleet"] if homogeneous else conf["fleet"][:2] \
        + conf["fleet"][-2:]
    conf["fleet"] = [dict(cut(g), clients=4 if homogeneous else 1)
                     for g in fleet]
    traffic = cell["traffic_data"]
    traffic["eval_samples"] = min(traffic["eval_samples"], 64)
    return cell
