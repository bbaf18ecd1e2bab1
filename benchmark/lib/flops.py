"""Operations a layer needs, from its shapes. A multiply-add is two
operations; a tap that falls on padding is not counted, since no
implementation has to multiply by it."""

from __future__ import annotations

import numpy as np


def same_out(size: int, stride: int) -> int:
    return -(-size // stride)


def valid_taps(size: int, kernel: int, stride: int) -> int:
    """Taps of a SAME-padded 1-D convolution that land inside the input,
    summed over the output positions (XLA's SAME: the smaller half of the
    padding goes in front)."""
    out = same_out(size, stride)
    pad = max((out - 1) * stride + kernel - size, 0)
    start = np.arange(out) * stride - pad // 2
    lo = np.maximum(start, 0)
    hi = np.minimum(start + kernel, size)
    return int(np.sum(hi - lo))


def conv_macs(h: int, w: int, kernel: int, stride: int, cin: int,
              cout: int) -> int:
    """Multiply-adds of one image through a SAME ``kernel`` x ``kernel``
    convolution over an ``h`` x ``w`` input."""
    return valid_taps(h, kernel, stride) * valid_taps(w, kernel, stride) \
        * cin * cout


def train_flops(forward_macs: int, first_layer_macs: int) -> int:
    """Forward, and a backward of twice the forward less the first layer's
    input gradient, which nothing needs."""
    return 2 * (3 * forward_macs - first_layer_macs)
