"""Operation and byte counts from shapes, checked by hand: the ResNet
family's (``families/resnet_cifar.py``) and those every family shares
(``flops.py``)."""
import pytest

from chip import flops as F
from chip import spec as S

R32 = S.config("resnet32_cifar10")
R56 = S.config("resnet56_cifar100")
RC = S.family(R32)


@pytest.mark.parametrize("cfg", [R32, R56], ids=["r32", "r56"])
def test_client_part_matches_table_iv(cfg):
    # the paper's Table IV: 475.136K per data point for the client part
    assert RC.table_iv_client(cfg["model"]) == 475_136


def test_one_residual_stage_by_hand():
    # ResNet-32, stage 0: five blocks of two 3x3 16->16 convs at 32x32
    stage0 = sum(RC.conv_macs(hw, k, ci, co)
                 for s, hw, k, ci, co in RC.stage_convs(R32["model"])
                 if s == 0)
    assert stage0 == 10 * 32 * 32 * 9 * 16 * 16 == 23_592_960
    # stage 1 opens with a strided 3x3 16->32 conv and a 1x1 projection
    s1 = [c for c in RC.stage_convs(R32["model"]) if c[0] == 1]
    assert s1[:3] == [(1, 16, 3, 16, 32), (1, 16, 3, 32, 32),
                      (1, 16, 1, 16, 32)]
    assert len(s1) == 11


def test_forward_macs_by_hand():
    conv = lambda hw, k, ci, co: hw * hw * k * k * ci * co
    stage = lambda hw, ci, co, n: (conv(hw, 3, ci, co) + conv(hw, 3, co, co)
                                   + (conv(hw, 1, ci, co) if ci != co else 0)
                                   + (n - 1) * 2 * conv(hw, 3, co, co))
    for cfg, n, classes in ((R32, 5, 10), (R56, 9, 100)):
        want = (conv(32, 3, 3, 16) + stage(32, 16, 16, n)
                + stage(16, 16, 32, n) + stage(8, 32, 64, n) + 64 * classes)
        assert RC.forward_macs(cfg["model"]) == want
    assert RC.forward_macs(R32["model"]) == 69_124_736
    assert RC.train_flops_per_sample(R32["model"]) == 6 * 69_124_736


def test_bn_act_cost_by_hand():
    model = {"depth": 8, "width": 4, "input_hw": 4, "input_channels": 3,
             "num_classes": 2}
    fleet = {"num_clients": 2, "per_client_batch": 3, "steps_per_round": 2}
    # client bn1+relu 4x4x4; stage 0: bn1+relu, bn2 at 4x4x4; stage 1:
    # bn1+relu, bn2, bn_proj at 2x2x8; stage 2: the same at 1x1x16
    layers = [(64, 4, 3), (64, 4, 3), (64, 4, 2),
              (32, 8, 3), (32, 8, 2), (32, 8, 2),
              (16, 16, 3), (16, 16, 2), (16, 16, 2)]
    rows = 6
    ops = sum(rows * e * k for e, _, k in layers) * 2
    nbytes = sum(2 * rows * e * 2 + 2 * c * 4 for e, c, _ in layers) * 2
    assert RC.bn_act_cost(model, fleet, "bfloat16") == (ops, nbytes)


def test_permute_cost_by_hand():
    # 1,600 rows over 4 chips; a bf16 row is 32*32*16*2 bytes; 4 kernels
    # a step each read and write the chip's 400 rows; 4 steps
    got = F.permute_cost(RC.smashed_elems(R56["model"]), R56["fleet"], 4,
                         "bfloat16")
    assert got == (0, 4 * 4 * 2 * 400 * 32 * 32 * 16 * 2)


def test_least_time_picks_the_binding_bound():
    p = F.peaks("TPU v5 lite")
    t, bound = F.least_time_s(0, 819e9, p)
    assert (t, bound) == (1.0, "memory")
    t, bound = F.least_time_s(197e12 * 2, 819e9, p)
    assert (t, bound) == (2.0, "compute")


def test_peaks_are_keyed_by_device_kind():
    p = F.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        F.peaks("cpu")
    with pytest.raises(KeyError):
        F.peaks("_source")
