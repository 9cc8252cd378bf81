"""Per-layer readers on hand-made contexts: the collective time per round,
and the server step's roofline share with the fleet's bytes shared out
over the cell's chips."""

import pytest

from bench import counts
from bench.families import vgg
from bench.metrics import collective_ms_per_round, server_step_roofline

VGG = vgg.vgg_layers([64, 128, 256, 512, 512], [100, 100])


def test_collective_ms_per_round():
    ctx = {"trace": {"collectives": {"all-reduce": 0.3, "all-gather": 0.1}},
           "rounds": 200}
    assert collective_ms_per_round.read(ctx) == pytest.approx(2.0)
    assert collective_ms_per_round.read(
        {"trace": {"collectives": {}}, "rounds": 200}) is None


@pytest.mark.parametrize("chips", [1, 4])
def test_server_step_roofline_shares_bytes_over_chips(chips):
    setup = {"family": "vgg", "client_models": [VGG] * 256,
             "global_model": VGG}
    least = counts.server_bytes_per_round(vgg, [VGG] * 256, VGG) / 819e9
    # the server step's device time per chip, averaged over the chips
    ctx = {"trace": {"phases": {"aggregate": 10 * least / chips}},
           "rounds": 10, "chips": chips, "setup": setup,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert server_step_roofline.read(ctx) == pytest.approx(100.0)
