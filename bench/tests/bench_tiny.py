"""Cells of BENCHMARK.json cut to a size a CPU test run holds: the
configuration cut by its family's ``cpu_cut`` (four clients in a
homogeneous fleet, one a device where it is sharded over four), an eval
set of 64 samples.  The traffic, limits and everything else are the
cell's own."""

import copy

from bench import families, run

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def tiny_cell(name: str) -> dict:
    cell = copy.deepcopy(run.load_cell(name))
    conf = cell["config_data"]
    cell["config_data"] = families.load(conf["family"]).cpu_cut(
        conf, clients=4)
    traffic = cell["traffic_data"]
    traffic["eval_samples"] = min(traffic["eval_samples"], 64)
    return cell
