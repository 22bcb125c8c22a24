import json

from bench import shapes
from bench.tests.conftest import ROOT

MODEL = json.loads((ROOT / "bench/configs/resnet18-cifar.json").read_text()
                   )["model"]


def test_param_count_is_the_configurations():
    assert shapes.resnet18_params(MODEL) == 11_173_962 \
        == MODEL["param_count"]


def test_forward_macs_by_hand():
    # 32x32 stem, then per stage: (out map, k, c_in, c_out) of each conv.
    macs = 32 * 32 * 9 * 3 * 64                    # stem
    macs += 4 * 32 * 32 * 9 * 64 * 64              # stage 0: 4 convs
    for hw, c_in, c_out in ((16, 64, 128), (8, 128, 256), (4, 256, 512)):
        macs += hw * hw * 9 * c_in * c_out         # block 0 conv1 (stride 2)
        macs += 3 * hw * hw * 9 * c_out * c_out    # block 0 conv2, block 1
        macs += hw * hw * c_in * c_out             # 1x1 projection
    macs += 512 * 10                               # head
    assert shapes.resnet18_forward_macs(MODEL) == macs
    assert abs(macs / 1e9 - 0.555) < 0.001         # ~0.56 GMAC per image


def test_train_flops_leave_out_the_image_gradient():
    fwd = shapes.resnet18_forward_macs(MODEL)
    stem = 32 * 32 * 9 * 3 * 64
    assert shapes.resnet18_train_flops(MODEL) == 2 * (3 * fwd - stem)


def test_merge_bytes():
    assert shapes.fedavg_merge_bytes(8, 1000) == (8 + 2) * 1000 * 4
