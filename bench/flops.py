"""Operations a run must do, from shapes alone.

Only work that any implementation of the configuration has to do is
counted, so that no change to the program can raise the count:

  crossbars  per die-image and per IRC (layer, group): the positive and
             negative conductance-plane currents, 2 planes x 2*P*R*N
             (P output positions, R mapped rows, N = group columns)
  digital    the stem's 3x3/2 conv and the head's 1x1 conv
  not        activated-count dots, IR drop, the SA epilogue, PRNG work

QAT adds the ideal grouped convs of the train path and the backward pass
of every differentiable layer (two products per forward product, the
stem's input gradient excepted).
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple


def _layers(net: Dict) -> Iterator[Tuple[int, int, int]]:
    """(positions per image, mapped rows, groups) of each IRC layer."""
    H, W = net["img_hw"][0] // 2, net["img_hw"][1] // 2
    g = net["group"]
    lead = net["bias_rows"] if net["scheme"] == "ternary" else 0
    for ch, nb in zip(net["stage_channels"], net["blocks_per_stage"]):
        for _ in range(nb):
            yield H * W, lead + 9 * g, ch // g
        H, W = -(-H // 2), -(-W // 2)


def _rows(net: Dict, macro: Dict, rows: int) -> int:
    """In-memory BN adds `bn_rows` leading rows to the binary design."""
    if net["scheme"] == "binary" and net["use_bn"]:
        return rows + macro["bn_rows"]
    return rows


def stem_flops(net: Dict) -> float:
    H, W = net["img_hw"][0] // 2, net["img_hw"][1] // 2
    return 2.0 * H * W * 27 * net["stage_channels"][0]


def head_flops(net: Dict) -> float:
    stride = 2 ** (len(net["stage_channels"]) + 1)
    cells = (net["img_hw"][0] // stride) * (net["img_hw"][1] // stride)
    ho = net["n_anchors"] * (5 + net["n_classes"])
    return 2.0 * cells * net["stage_channels"][-1] * ho


def crossbar_flops(conf: Dict) -> float:
    """Plane currents of one die on one image."""
    net, g = conf["network"], conf["network"]["group"]
    return sum(2 * 2.0 * P * _rows(net, conf["macro"], R) * g * n_groups
               for P, R, n_groups in _layers(net))


def die_flops(conf: Dict) -> float:
    """One die's inference on one image: crossbars, stem and head."""
    net = conf["network"]
    return crossbar_flops(conf) + stem_flops(net) + head_flops(net)


def qat_step_flops(conf: Dict, train_chips: int, batch: int) -> float:
    """One ensemble-QAT step on `batch` images and `train_chips` dies.

    The first IRC layer's input is shared by the dies, so its ideal conv
    runs once per image; every later layer's runs once per die-image."""
    net, g = conf["network"], conf["network"]["group"]
    die_images = train_chips * batch
    total = 2 * stem_flops(net) * batch                 # forward, dW
    total += 3 * head_flops(net) * die_images           # forward, dW, dx
    for i, (P, _, n_groups) in enumerate(_layers(net)):
        conv = 2.0 * P * 9 * g * g * n_groups
        total += 3 * conv * (batch if i == 0 else die_images)
    return total + crossbar_flops(conf) * die_images
