"""Model FLOPs of one FedEntropy round of the paper's CNN, from shapes.

A multiply-add is two FLOPs; the backward pass costs twice the forward.
Only real (unpadded) images count: E epochs of forward and backward per
image, and the soft-label forward over the client's images after
training. Pooling, activations and the optimizer are not counted.
"""
from __future__ import annotations


def forward_macs(cfg: dict) -> int:
    """Multiply-adds of one image's forward pass (651,720 for the
    paper's CNN on 32x32x3)."""
    k, ch = cfg["kernel_size"], cfg["channels"]
    c1, c2 = cfg["conv1_channels"], cfg["conv2_channels"]
    o1 = cfg["image_hw"] - k + 1
    o2 = o1 // 2 - k + 1
    flat = (o2 // 2) ** 2 * c2
    f1, f2 = cfg["fc_widths"]
    return (o1 * o1 * c1 * k * k * ch + o2 * o2 * c2 * k * k * c1
            + flat * f1 + f1 * f2 + f2 * cfg["num_classes"])


def round_flops(cfg: dict, samples_per_round: float) -> float:
    """FLOPs of one round whose cohort holds ``samples_per_round`` real
    images."""
    fwd = 2 * forward_macs(cfg)
    return samples_per_round * (cfg["local_epochs"] * 3 * fwd + fwd)
