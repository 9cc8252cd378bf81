"""A configuration of a new kind of client model is new files only: a
family that only the tests define (``family_deep_mlp.py``, parameters
nested four levels deep, with its own plain reference) runs through
``run.run_cell`` with no edit to the harness; the program is correct and
the control (the reference in bfloat16) is not.  Also: the leaf paths of
the VGG family are the comparison's keys of before, and a configuration
or a mix that says nothing the harness can place is an error."""

import json
import sys
from pathlib import Path

import pytest

import jax

import family_deep_mlp
import reference_deep_mlp
from bench import compare, control, run
from bench.families import vgg
from bench_tiny import CPU, tiny_cell

BENCH = Path(__file__).resolve().parents[1]
SEED = 2 ** 31 + 777


def deep_mlp_cell() -> dict:
    conf = {"name": "deep_mlp_4", "family": "deep_mlp",
            "reference": "deep_mlp_feddd", "input": 16, "hidden": [32, 24],
            "classes": 4, "clients": 4, "samples_per_client": 64,
            "data_seed": 0, "batch_size": 32, "local_epochs": 1, "lr": 0.05,
            "a_server": 0.6, "d_max": 0.8, "delta": 1.0, "h": 5}
    return {"name": "deep_mlp_4.scanned", "config": "deep_mlp_4",
            "traffic": "scanned", "chips": 1, "config_data": conf,
            "traffic_data": json.loads(
                (BENCH / "traffic" / "scanned.json").read_text()),
            # float32 on the CPU agrees to ~1e-6; bfloat16 misses by ~0.08
            "limits": {"first_update_gap": 1e-3, "rates_gap": 5e-3,
                       "budget_gap": 1e-5, "global_change_gap": 0.05,
                       "clients_change_gap": 0.05},
            "per_layer": [], "per_layer_units": {},
            "end_to_end": {"rounds_per_s": "rounds/s", "setup_s": "s"}}


@pytest.fixture
def deep_mlp(monkeypatch):
    """The family and its reference where the harness looks them up by
    name; fresh compiled programs around the test."""
    monkeypatch.setitem(sys.modules, "bench.families.deep_mlp",
                        family_deep_mlp)
    monkeypatch.setitem(sys.modules, "bench.references.deep_mlp_feddd",
                        reference_deep_mlp)
    jax.clear_caches()
    yield deep_mlp_cell()
    jax.clear_caches()


def test_new_family_runs_correct_through_the_harness(deep_mlp):
    out = run.run_cell(deep_mlp, SEED, 0.5, False, CPU)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["window"]["compiles"] == 0
    assert set(out["metrics"]) == {"rounds_per_s", "setup_s"}


def test_new_family_control_is_not_correct(deep_mlp):
    numbers, _ = control.readings(deep_mlp, SEED, "control")
    checks = compare.judge(numbers, deep_mlp["limits"])
    assert not all(c["ok"] for c in checks), checks


def test_new_family_leaf_paths_nest_four_levels(deep_mlp):
    setup = run.build_setup(deep_mlp, SEED)
    g, _ = family_deep_mlp.make_weights(SEED, setup["global_model"])
    assert set(compare.leaves(g)) == {
        "body.block0.dense.w", "body.block0.dense.b",
        "body.block1.dense.w", "body.block1.dense.b",
        "head.out.w", "head.out.b"}


def test_vgg_leaf_paths_are_the_comparison_keys_of_before():
    layers = vgg.vgg_layers([8, 16, 32, 64, 64], [12, 12])
    g, _ = vgg.make_weights(3, layers)
    assert set(compare.leaves(g)) == {f"{nm}.{k}" for nm in g
                                      for k in g[nm]}
    assert {"conv0.w", "conv4.b", "fc5.b", "fc7.w"} <= set(
        compare.leaves(g))


def test_configuration_without_family_is_an_error():
    cell = tiny_cell("vgg_full_128.scanned")
    del cell["config_data"]["family"]
    with pytest.raises(ValueError, match="names no family"):
        run.build_setup(cell, SEED)


@pytest.mark.parametrize("mix,placed", [
    ({}, {}),
    ({"mesh": "clients", "collective": "dense"},
     {"mesh": 4, "mesh_collective": "dense"})])
def test_placement_follows_the_mix(mix, placed):
    cell = {"traffic": "t", "chips": 4, "traffic_data": mix}
    assert run.placement(cell) == placed


def test_unknown_mesh_is_an_error():
    cell = {"traffic": "t", "chips": 4,
            "traffic_data": {"mesh": "model", "collective": "dense"}}
    with pytest.raises(ValueError, match="the one mesh is 'clients'"):
        run.placement(cell)
