"""Operations and bytes the algorithm needs, counted from shapes.

Each model's file (`bench/models/<model>.py`) counts its forward FLOPs per
row of its data. Convolutions and dense layers count every multiply-add
as two operations, padding taps of SAME convolutions included (a dense
convolution is what the chip computes, and what the paper's Table I
counts). Normalization, activations, pooling and the loss are left out:
they are a few operations per activation against the hundreds per
activation of a convolution. A training step counts three forward passes
per row (forward, and backward for activations and for weights).
"""
from __future__ import annotations


def conv(h, w, k, c_in, c_out, stride=1):
    """FLOPs of a SAME k x k convolution over an h x w x c_in input, and
    its output's height and width."""
    ho, wo = -(-h // stride), -(-w // stride)
    return 2 * ho * wo * k * k * c_in * c_out, ho, wo


def forward_flops(cfg: dict) -> int:
    """Forward FLOPs for one row (an image, a sequence) of the
    configuration's model."""
    from bench import models
    return models.of(cfg).forward_flops(cfg)


def train_flops(cfg: dict, samples: int) -> int:
    """FLOPs of `samples` training rows: forward and backward."""
    return 3 * forward_flops(cfg) * samples
