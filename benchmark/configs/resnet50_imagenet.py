"""Plain reference of ResNet-50 (He et al., arXiv:1512.03385, Table 1,
50-layer): 7x7/2 stem with batch norm and ReLU, 3x3/2 max-pool, four
stages of [3, 4, 6, 3] bottleneck blocks (1x1, 3x3, 1x1 at widths 64 to
512, output x4), global average pool, fc to the classes. The stride of a
stage's first block sits on its 3x3 convolution, and its shortcut is a
strided 1x1 projection with batch norm. Batch norm normalises with the
batch's own mean and biased variance, taken in float32 over the whole
batch, and keeps running statistics with the stated momentum.

Parameters come as the program names them: ``stem`` (``conv``, ``bn``),
``stage1..4`` (lists of blocks: ``conv1 bn1 conv2 bn2 conv3 bn3`` and,
on a stage's first block, ``proj proj_bn``), ``fc`` (``kernel``,
``bias``); a batch norm is ``scale`` and ``offset``, its state ``mean``
and ``var``. An input of 64 px or less takes the 3x3/1 stem without the
pool (what the program does at CIFAR sizes; the cells run 224 px)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib import flops as F
from benchmark.lib.reference import max_pool_3x3_s2

BLOCKS = (3, 4, 6, 3)
WIDTHS = (64, 128, 256, 512)


def make_forward(spec: dict):
    momentum, eps = spec["bn_momentum"], spec["bn_eps"]

    def bn(nm, x, p, s):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.maximum(
            jnp.mean(jnp.square(xf), axis=(0, 1, 2)) - jnp.square(mean), 0.0)
        y = (x - mean) * (jax.lax.rsqrt(var + eps) * p["scale"]) \
            + p["offset"]
        new = {"mean": momentum * s["mean"] + (1.0 - momentum) * mean,
               "var": momentum * s["var"] + (1.0 - momentum) * var}
        return nm.store(y), new

    def block(nm, stride, x, p, s):
        ns = {}
        h, ns["bn1"] = bn(nm, nm.conv(x, p["conv1"]), p["bn1"], s["bn1"])
        h = jax.nn.relu(h)
        h, ns["bn2"] = bn(nm, nm.conv(h, p["conv2"], stride), p["bn2"],
                          s["bn2"])
        h = jax.nn.relu(h)
        h, ns["bn3"] = bn(nm, nm.conv(h, p["conv3"]), p["bn3"], s["bn3"])
        if "proj" in p:
            x, ns["proj_bn"] = bn(nm, nm.conv(x, p["proj"], stride),
                                  p["proj_bn"], s["proj_bn"])
        return nm.store(jax.nn.relu(x + h)), ns

    def forward(nm, params, model_state, x):
        new_state = {}
        big = params["stem"]["conv"].shape[0] == 7
        x = nm.conv(x, params["stem"]["conv"], 2 if big else 1)
        x, stem_bn = bn(nm, x, params["stem"]["bn"],
                        model_state["stem"]["bn"])
        new_state["stem"] = {"bn": stem_bn}
        x = jax.nn.relu(x)
        if big:
            x = max_pool_3x3_s2(x)
        for si in range(1, 5):
            name = f"stage{si}"
            states = []
            for bi, bp in enumerate(params[name]):
                stride = 2 if (bi == 0 and si > 1) else 1
                # recompute a block's inside in the backward pass: at
                # float32 the saved activations of a whole batch would not
                # fit beside the weights otherwise
                x, bs = jax.checkpoint(
                    lambda xx, pp, ss, _s=stride: block(nm, _s, xx, pp, ss)
                )(x, bp, model_state[name][bi])
                states.append(bs)
            new_state[name] = states
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        logits = nm.dense(x, params["fc"]["kernel"]) + params["fc"]["bias"]
        return logits, new_state

    return forward


def init_model_state(params):
    """Running mean 0 and variance 1 for every batch norm."""
    def walk(node):
        if isinstance(node, dict):
            if set(node) == {"scale", "offset"}:
                return {"mean": jnp.zeros_like(node["scale"], jnp.float32),
                        "var": jnp.ones_like(node["scale"], jnp.float32)}
            kids = {k: walk(v) for k, v in node.items()}
            return {k: v for k, v in kids.items() if v} or None
        if isinstance(node, (list, tuple)):
            return [walk(v) for v in node]
        return None
    return walk(params)


def _layers(spec: dict):
    """(macs, parameters) of each layer for one image, stem first."""
    s, c = spec["crop_size"], spec["num_channels"]
    out = []
    if s > 64:
        out.append((F.conv_macs(s, s, 7, 2, c, 64), 7 * 7 * c * 64 + 2 * 64))
        s = F.same_out(F.same_out(s, 2), 2)
    else:
        out.append((F.conv_macs(s, s, 3, 1, c, 64), 3 * 3 * c * 64 + 2 * 64))
    cin = 64
    for si, (n, width) in enumerate(zip(BLOCKS, WIDTHS)):
        cout = 4 * width
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            so = F.same_out(s, stride)
            out.append((F.conv_macs(s, s, 1, 1, cin, width),
                        cin * width + 2 * width))
            out.append((F.conv_macs(s, s, 3, stride, width, width),
                        9 * width * width + 2 * width))
            out.append((F.conv_macs(so, so, 1, 1, width, cout),
                        width * cout + 2 * cout))
            if bi == 0:
                out.append((F.conv_macs(s, s, 1, stride, cin, cout),
                            cin * cout + 2 * cout))
            s, cin = so, cout
    k = spec["num_classes"]
    out.append((cin * k, cin * k + k))
    return out


def param_count(spec: dict) -> int:
    return sum(n for _, n in _layers(spec))


def train_flops_per_image(spec: dict) -> int:
    layers = _layers(spec)
    return F.train_flops(sum(m for m, _ in layers), layers[0][0])
