"""Operations and bytes of the timed work, counted from shapes.

These are the yardstick's counts: the work the algorithm needs, whatever
the compiler does with it (XLA's ``cost_analysis`` would count fusion
artefacts and recomputation instead).
"""
from __future__ import annotations


def resnet18_convs(model: dict) -> list[tuple[int, int, int, int, int]]:
    """Every convolution and the head of a CIFAR-stem ResNet-18 as
    ``(out_h, out_w, k, c_in, c_out)`` (the head is a 1x1 "conv" on a
    1x1 map)."""
    h, w, c = model["image_shape"]
    widths = model["stage_widths"]
    k0 = model["stem_kernel"]
    convs = [(h, w, k0, c, widths[0])]
    c_in = widths[0]
    for si, c_out in enumerate(widths):
        for bi in range(model["blocks_per_stage"]):
            stride = 2 if (si > 0 and bi == 0) else 1
            h, w = -(-h // stride), -(-w // stride)
            convs.append((h, w, 3, c_in, c_out))
            convs.append((h, w, 3, c_out, c_out))
            if stride != 1 or c_in != c_out:
                convs.append((h, w, 1, c_in, c_out))
            c_in = c_out
    convs.append((1, 1, 1, c_in, model["n_classes"]))
    return convs


def resnet18_forward_macs(model: dict) -> int:
    """Multiply-accumulates of one image's forward pass (convolutions and
    the head; norms and activations are elementwise and not counted)."""
    return sum(h * w * k * k * ci * co
               for h, w, k, ci, co in resnet18_convs(model))


def resnet18_train_flops(model: dict) -> int:
    """FLOPs of one image's forward and backward pass: the forward, the
    gradient of every weight (as many MACs again) and the gradient of
    every input except the image's (the stem's)."""
    convs = resnet18_convs(model)
    macs = [h * w * k * k * ci * co for h, w, k, ci, co in convs]
    return 2 * (2 * sum(macs) + sum(macs[1:]))


def resnet18_params(model: dict) -> int:
    """Weights of the network: convolutions, the norms' scale and bias,
    the head's bias."""
    convs = resnet18_convs(model)
    weights = sum(k * k * ci * co for _, _, k, ci, co in convs)
    norms = sum(2 * co for _, _, _, _, co in convs[:-1])
    return weights + norms + model["n_classes"]


def fedavg_merge_bytes(n_clients: int, n_params: int, dtype_bytes: int = 4
                       ) -> int:
    """HBM bytes of one scenario's masked FedAvg merge: every client's
    parameters read, the global parameters read (the fallback when no
    client delivered) and the merged parameters written."""
    return (n_clients * n_params + 2 * n_params) * dtype_bytes
