"""Operations and bytes the algorithm needs, counted from shapes.

Forward FLOPs per sample count every multiply-add of the convolutions and
dense layers as two operations, padding taps of SAME convolutions included
(a dense convolution is what the chip computes, and what the paper's
Table I counts). Normalization, activations, pooling and the loss are left
out: they are a few operations per activation against the hundreds per
activation of a convolution. A training step counts three forward passes
per sample (forward, and backward for activations and for weights).
"""
from __future__ import annotations


def _conv(h, w, k, c_in, c_out, stride=1):
    ho, wo = -(-h // stride), -(-w // stride)
    return 2 * ho * wo * k * k * c_in * c_out, ho, wo


def forward_flops(cfg: dict) -> int:
    """Forward FLOPs for one sample of the configuration's model."""
    m = cfg["model"]
    h, w, c = cfg["data"]["image"]
    nc = cfg["data"]["classes"]
    if m["name"] == "resnet":
        n = (m["depth"] - 2) // 6
        width = m["width"]
        total, h, w = _conv(h, w, 3, c, width)
        c = width
        for c_out in (width, 2 * width, 4 * width):
            for _ in range(n):
                stride = 2 if c != c_out else 1
                f1, ho, wo = _conv(h, w, 3, c, c_out, stride)
                f2, _, _ = _conv(ho, wo, 3, c_out, c_out)
                total += f1 + f2
                if c != c_out:
                    total += _conv(h, w, 1, c, c_out, stride)[0]
                h, w, c = ho, wo, c_out
        return total + 2 * c * nc
    if m["name"] == "lenet":
        c1, c2, f1, f2 = m["widths"]
        k = m["kernel"]
        a, _, _ = _conv(h, w, k, c, c1)
        b, _, _ = _conv(h // 2, w // 2, k, c1, c2)
        flat = (h // 4) * (w // 4) * c2
        return a + b + 2 * (flat * f1 + f1 * f2 + f2 * nc)
    raise ValueError(f"no FLOP count for model {m['name']!r}")


def train_flops(cfg: dict, samples: int) -> int:
    """FLOPs of `samples` training samples: forward and backward."""
    return 3 * forward_flops(cfg) * samples

