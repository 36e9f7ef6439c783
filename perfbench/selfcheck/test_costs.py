"""The FLOP and byte functions against hand-worked counts."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.harness import common, costs  # noqa: E402

RESNET = common.load_json(os.path.join(ROOT,
                                       "perfbench/configs/resnet50.json"))
OPT = common.load_json(os.path.join(ROOT, "perfbench/configs/opt_1p3b.json"))


def test_resnet50_forward_is_4_09_gmacs():
    # He et al. give 3.8 GFLOPs (multiply-adds) for ResNet-50 with
    # stride on the 1x1; with the stride on the 3x3 (this graph, the
    # "v1.5" placement) the count is 4.09 GMACs = 8.18 GFLOPs
    f = costs.resnet_forward_flops_per_image(RESNET)
    assert abs(f / 2 / 1e9 - 4.09) < 0.03, f
    assert costs.resnet_train_flops_per_image(RESNET) == 3 * f


def test_resnet50_hand_worked_nodes():
    n = costs.resnet_nodes(RESNET, 1)
    # stem: 112 x 112 x 64 outputs, 7 x 7 x 3 taps
    assert n["conv0"]["forward_flops"] == 2 * 112 * 112 * 64 * 147
    assert len(n["conv0"]["kernels"]) == 2          # no data gradient
    # stage1_unit1_conv2: 56 x 56 x 64 outputs, 3 x 3 x 64 taps
    assert n["stage1_unit1_conv2"]["forward_flops"] \
        == 2 * 56 * 56 * 64 * 9 * 64
    # stage2_unit1_conv2 carries the stride: 28 x 28 x 128 outputs
    assert n["stage2_unit1_conv2"]["forward_flops"] \
        == 2 * 28 * 28 * 128 * 9 * 128
    # 1 + (3 + 4 + 6 + 3) * 3 + 4 shortcuts + fc = 54 nodes
    assert len(n) == 54
    # bytes of stage4_unit3_conv3 forward at bf16: x 7*7*512, w 2048*512,
    # y 7*7*2048, two bytes each
    fwd = n["stage4_unit3_conv3"]["kernels"][0]
    assert fwd[2] == 2 * (7 * 7 * 512 + 2048 * 512 + 7 * 7 * 2048)


def test_roofline_bound_side():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    sec, by = costs.roofline_seconds(
        [("a", 197e12, 1.0), ("b", 1.0, 819e9)], peaks)
    assert abs(sec - 2.0) < 1e-9 and by == {"compute": 1, "memory": 1}


def test_opt_1p3b_parameters():
    # 24 x (4 x 2048^2 + 2 x 2048 x 8192) + 50272 x 2048
    assert costs.decoder_matmul_params(OPT) \
        == 24 * (4 * 2048 * 2048 + 2 * 2048 * 8192) + 50272 * 2048
    total = costs.decoder_total_params(OPT)
    assert 1.30e9 < total < 1.33e9, total
    assert costs.decoder_flops_per_token(OPT) \
        == 2.0 * costs.decoder_matmul_params(OPT)


def test_peak_table_refuses_an_unknown_device():
    assert common.peaks_for("TPU v5 lite", ROOT)["bf16_flops_per_s"] \
        == 197e12
    try:
        common.peaks_for("TPU v9 imaginary", ROOT)
    except RuntimeError as e:
        assert "no row" in str(e)
    else:
        raise AssertionError("an unknown device must be an error")
