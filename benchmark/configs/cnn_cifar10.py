"""Plain reference of the paper's CNN (cifar10cnn.py:94-147 of the
modelled system): conv 5x5x64 + ReLU, max-pool 3x3/2, conv 5x5x64 + ReLU,
max-pool 3x3/2, fc 384 + ReLU, fc 192 + ReLU, fc to the classes. All
convolutions and pools are SAME-padded; no ReLU on the logits (the
``fixed`` fidelity the cells run). Parameters come as the program names
them: ``conv1 conv2 full1 full2 full3``, each ``kernel`` and ``bias``."""

from __future__ import annotations

import jax

from benchmark.lib import flops as F
from benchmark.lib.reference import max_pool_3x3_s2


def make_forward(spec: dict):
    del spec

    def forward(nm, params, model_state, x):
        for name in ("conv1", "conv2"):
            p = params[name]
            x = nm.store(jax.nn.relu(nm.conv(x, p["kernel"]) + p["bias"]))
            x = max_pool_3x3_s2(x)
        x = x.reshape(x.shape[0], -1)
        for name in ("full1", "full2"):
            p = params[name]
            x = nm.store(jax.nn.relu(nm.dense(x, p["kernel"]) + p["bias"]))
        p = params["full3"]
        return nm.dense(x, p["kernel"]) + p["bias"], model_state

    return forward


def init_model_state(params):
    del params
    return {}


def _layers(spec: dict):
    """(kind, macs, parameters) of each layer for one image."""
    s, c = spec["crop_size"], spec["num_channels"]
    out = []
    cin = c
    for _ in range(2):
        out.append(("conv", F.conv_macs(s, s, 5, 1, cin, 64),
                    5 * 5 * cin * 64 + 64))
        s, cin = F.same_out(s, 2), 64
    flat = s * s * 64
    for cin, cout in ((flat, 384), (384, 192), (192, spec["num_classes"])):
        out.append(("fc", cin * cout, cin * cout + cout))
    return out


def param_count(spec: dict) -> int:
    return sum(n for _, _, n in _layers(spec))


def train_flops_per_image(spec: dict) -> int:
    layers = _layers(spec)
    return F.train_flops(sum(m for _, m, _ in layers), layers[0][1])
