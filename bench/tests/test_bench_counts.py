"""bench/counts.py against hand counts of the paper's full VGG client."""

from bench import counts, fleet

VGG = fleet.vgg_layers([64, 128, 256, 512, 512], [100, 100])


def test_vgg_forward_macs_by_hand():
    # conv0: 32x32 out, 3x3x3 -> 64; then 16, 8, 4, 2 pixels a side
    macs = (32 * 32 * 9 * 3 * 64 + 16 * 16 * 9 * 64 * 128
            + 8 * 8 * 9 * 128 * 256 + 4 * 4 * 9 * 256 * 512
            + 2 * 2 * 9 * 512 * 512 + 512 * 100 + 100 * 100 + 100 * 10)
    assert macs == 67_891_960
    assert sum(counts.layer_forward_flops(VGG)) == 2 * macs


def test_train_flops_leave_out_the_image_gradient():
    f = counts.layer_forward_flops(VGG)
    assert counts.train_flops_per_sample(VGG) == 3 * sum(f) - f[0]
    # one round of 128 clients x 64 samples: ~3.3 TFLOP
    per_round = counts.round_flops([VGG] * 128, 64, 1)
    assert abs(per_round - 3.308e12) / 3.308e12 < 1e-3


def test_eval_adds_one_forward_pass_per_sample():
    base = counts.round_flops([VGG] * 2, 64, 1)
    with_eval = counts.round_flops([VGG] * 2, 64, 1, VGG, 2048)
    assert with_eval - base == 2048 * sum(counts.layer_forward_flops(VGG))


def test_server_bytes_by_hand():
    params = (3 * 3 * 3 * 64 + 64 + 3 * 3 * 64 * 128 + 128
              + 3 * 3 * 128 * 256 + 256 + 3 * 3 * 256 * 512 + 512
              + 3 * 3 * 512 * 512 + 512 + 512 * 100 + 100
              + 100 * 100 + 100 + 100 * 10 + 10)
    assert fleet.param_bytes(VGG) == 4 * params
    assert counts.server_bytes_per_round([VGG] * 128, VGG) \
        == 3 * 128 * 4 * params + 2 * 4 * params
