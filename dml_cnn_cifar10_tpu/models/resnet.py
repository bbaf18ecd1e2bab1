"""ResNet-18/50 — the "deeper conv stack" rungs of the config ladder.

No reference counterpart (the reference model is the 5-layer CNN,
``cifar10cnn.py:94-147``); these are the BASELINE.json ladder configs
"ResNet-18 on CIFAR-10 (deeper conv stack, BatchNorm psum)" and
"ResNet-50 on ImageNet-1k". Design notes:

- Functional pytrees like :mod:`~dml_cnn_cifar10_tpu.models.cnn`; BatchNorm
  running stats live in a parallel ``state`` pytree (the framework's
  ``model_state``) so the train step stays pure.
- Cross-replica BN (SURVEY §2.3): batch stats are global means — automatic
  under jit auto-partitioning, explicit ``lax.pmean`` via ``axis_name``
  under the shard_map step. See :func:`ops.layers.batch_norm`.
- Stem adapts to input size: CIFAR-scale inputs (≤64 px) use the 3×3/s1
  stem with no maxpool; larger (ImageNet) inputs use 7×7/s2 + 3×3/s2
  maxpool.
- All convs are bias-free (BN's offset absorbs the bias); final BN of each
  residual branch is gamma-zero-initialized so blocks start as identity —
  standard large-batch trick, keeps the big-LR parity regime stable.
- ``cfg.resnet_norm="nf"`` swaps every BN for scaled weight
  standardization (per-kernel fan-in standardize + learnable gain —
  weight bytes only) + per-conv biases + a SkipInit residual scalar
  (init 0 — identity start, like the gamma-zero BN); nf removes
  every activation-sized stats read/write. Different training semantics
  (the NFNet line of work shows the class reaches BN-level accuracy
  with care); the byte-reduction rung (not
  measured on the current chip).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dml_cnn_cifar10_tpu.config import DataConfig, ModelConfig
from dml_cnn_cifar10_tpu.ops import layers as L

Params = Dict[str, Any]
State = Dict[str, Any]

# depth → (blocks per stage, block kind)
STAGES = {
    18: ((2, 2, 2, 2), "basic"),
    34: ((3, 4, 6, 3), "basic"),
    50: ((3, 4, 6, 3), "bottleneck"),
}
STAGE_WIDTHS = (64, 128, 256, 512)
BOTTLENECK_EXPANSION = 4


def _conv_init(key, shape, dtype):
    return L.he_normal_init(key, shape, dtype)


def _init_basic_block(key, cin: int, width: int, stride: int, dtype):
    ks = jax.random.split(key, 3)
    p: Params = {}
    p["conv1"] = _conv_init(ks[0], (3, 3, cin, width), dtype)
    p["bn1"] = L.bn_init(width, dtype)
    p["conv2"] = _conv_init(ks[1], (3, 3, width, width), dtype)
    p["bn2"] = L.bn_init(width, dtype)
    p["bn2"]["scale"] = jnp.zeros_like(p["bn2"]["scale"])  # identity start
    if stride != 1 or cin != width:
        p["proj"] = _conv_init(ks[2], (1, 1, cin, width), dtype)
        p["proj_bn"] = L.bn_init(width, dtype)
    return p, width


def _init_bottleneck_block(key, cin: int, width: int, stride: int, dtype):
    cout = width * BOTTLENECK_EXPANSION
    ks = jax.random.split(key, 4)
    p: Params = {}
    p["conv1"] = _conv_init(ks[0], (1, 1, cin, width), dtype)
    p["bn1"] = L.bn_init(width, dtype)
    p["conv2"] = _conv_init(ks[1], (3, 3, width, width), dtype)
    p["bn2"] = L.bn_init(width, dtype)
    p["conv3"] = _conv_init(ks[2], (1, 1, width, cout), dtype)
    p["bn3"] = L.bn_init(cout, dtype)
    p["bn3"]["scale"] = jnp.zeros_like(p["bn3"]["scale"])  # identity start
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(ks[3], (1, 1, cin, cout), dtype)
        p["proj_bn"] = L.bn_init(cout, dtype)
    return p, cout


def _init_nf_basic_block(key, cin: int, width: int, stride: int, dtype):
    ks = jax.random.split(key, 3)
    p: Params = {
        "conv1": _conv_init(ks[0], (3, 3, cin, width), dtype),
        "g1": jnp.ones((width,), dtype), "c1": jnp.zeros((width,), dtype),
        "conv2": _conv_init(ks[1], (3, 3, width, width), dtype),
        "g2": jnp.ones((width,), dtype), "c2": jnp.zeros((width,), dtype),
        # SkipInit: the residual branch enters at 0 — blocks start as
        # identity, the NF analog of the gamma-zero BN init above.
        "skip_gain": jnp.zeros((), dtype),
    }
    if stride != 1 or cin != width:
        p["proj"] = _conv_init(ks[2], (1, 1, cin, width), dtype)
        p["gp"] = jnp.ones((width,), dtype)
        p["cp"] = jnp.zeros((width,), dtype)
    return p, width


def _init_nf_bottleneck_block(key, cin: int, width: int, stride: int,
                              dtype):
    cout = width * BOTTLENECK_EXPANSION
    ks = jax.random.split(key, 4)
    p: Params = {
        "conv1": _conv_init(ks[0], (1, 1, cin, width), dtype),
        "g1": jnp.ones((width,), dtype), "c1": jnp.zeros((width,), dtype),
        "conv2": _conv_init(ks[1], (3, 3, width, width), dtype),
        "g2": jnp.ones((width,), dtype), "c2": jnp.zeros((width,), dtype),
        "conv3": _conv_init(ks[2], (1, 1, width, cout), dtype),
        "g3": jnp.ones((cout,), dtype), "c3": jnp.zeros((cout,), dtype),
        "skip_gain": jnp.zeros((), dtype),
    }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(ks[3], (1, 1, cin, cout), dtype)
        p["gp"] = jnp.ones((cout,), dtype)
        p["cp"] = jnp.zeros((cout,), dtype)
    return p, cout


def init_params(key: jax.Array, cfg: ModelConfig, data: DataConfig,
                depth: int = 18) -> Params:
    if depth not in STAGES:
        raise ValueError(f"unsupported resnet depth {depth}; have "
                         f"{sorted(STAGES)}")
    blocks, kind = STAGES[depth]
    dtype = jnp.dtype(cfg.dtype)
    imagenet_stem = min(data.crop_height, data.crop_width) > 64
    nf = cfg.resnet_norm == "nf"
    if cfg.resnet_norm not in ("bn", "nf"):
        raise ValueError(
            f"resnet_norm must be 'bn' or 'nf', got {cfg.resnet_norm!r}")
    if nf:
        init_block = (_init_nf_bottleneck_block if kind == "bottleneck"
                      else _init_nf_basic_block)
    else:
        init_block = (_init_bottleneck_block if kind == "bottleneck"
                      else _init_basic_block)

    keys = jax.random.split(key, 2 + sum(blocks))
    ki = iter(range(len(keys)))

    p: Params = {}
    if imagenet_stem and cfg.resnet_s2d:
        # Space-to-depth stem: 4x4/1 conv over the
        # 2x2-folded input — same function class as 7x7/2 on the raw
        # image (zero-pad 7x7 to 8x8, fold into 4x4 x 4C), trained
        # directly in the folded parameterization as MLPerf does.
        stem_shape = (4, 4, 4 * data.num_channels, 64)
    else:
        stem_k = (7, 7) if imagenet_stem else (3, 3)
        stem_shape = (*stem_k, data.num_channels, 64)
    p["stem"] = {"conv": _conv_init(keys[next(ki)], stem_shape, dtype)}
    if nf:
        p["stem"]["g"] = jnp.ones((64,), dtype)
        p["stem"]["c"] = jnp.zeros((64,), dtype)
    else:
        p["stem"]["bn"] = L.bn_init(64, dtype)

    cin = 64
    for si, (n, width) in enumerate(zip(blocks, STAGE_WIDTHS)):
        stage: List[Params] = []
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            bp, cin = init_block(keys[next(ki)], cin, width, stride, dtype)
            stage.append(bp)
        p[f"stage{si + 1}"] = stage

    p["fc"] = {
        "kernel": L.he_normal_init(keys[next(ki)], (cin, cfg.num_classes),
                                   dtype),
        "bias": jnp.zeros((cfg.num_classes,), dtype),
    }
    return p


def init_state(params: Params) -> State:
    """Derive the running-stat pytree from the param pytree: every dict with
    ``scale``/``offset`` keys is a BN layer and gets ``mean``/``var``."""

    def walk(node):
        if isinstance(node, dict):
            if set(node) == {"scale", "offset"}:
                return {"mean": jnp.zeros(node["scale"].shape, jnp.float32),
                        "var": jnp.ones(node["scale"].shape, jnp.float32)}
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return None  # non-BN leaf: no state

    return walk(params)


def _bn(x, p, s, cfg: ModelConfig, train: bool, axis_name):
    return L.batch_norm(x, p, s, train, cfg.bn_momentum, cfg.bn_eps,
                        axis_name)


# Named scopes (metadata only; utils/devprof.scope_map reads a compiled
# instruction's layer from them): a convolution is ``conv<k>``, its batch
# norm with the ReLU that follows ``bn<k>``, the projection and its batch
# norm ``shortcut/conv`` and ``shortcut/bn``, the residual sum with its ReLU
# ``add``.

def _conv(name, x, w, stride=1):
    with jax.named_scope(name):
        return L.conv2d(x, w, stride=stride)


def _bn_act(name, x, p, s, cfg, train, axis_name, relu=True):
    with jax.named_scope(name):
        x, ns = _bn(x, p, s, cfg, train, axis_name)
        return (jax.nn.relu(x) if relu else x), ns


def _shortcut(x, p, s, ns, stride, cfg, train, axis_name):
    if "proj" not in p:
        return x
    with jax.named_scope("shortcut"):
        x = _conv("conv", x, p["proj"], stride)
        x, ns["proj_bn"] = _bn_act("bn", x, p["proj_bn"], s["proj_bn"], cfg,
                                   train, axis_name, relu=False)
    ns["proj"] = None
    return x


def _add_act(x, h):
    with jax.named_scope("add"):
        return jax.nn.relu(x + h)


def _basic_block(x, p, s, stride, cfg, train, axis_name):
    ns: State = {}
    h = _conv("conv1", x, p["conv1"], stride)
    h, ns["bn1"] = _bn_act("bn1", h, p["bn1"], s["bn1"], cfg, train,
                           axis_name)
    h = _conv("conv2", h, p["conv2"])
    h, ns["bn2"] = _bn_act("bn2", h, p["bn2"], s["bn2"], cfg, train,
                           axis_name, relu=False)
    x = _shortcut(x, p, s, ns, stride, cfg, train, axis_name)
    ns["conv1"] = ns["conv2"] = None
    return _add_act(x, h), ns


def _bottleneck_block(x, p, s, stride, cfg, train, axis_name):
    ns: State = {}
    h = _conv("conv1", x, p["conv1"])
    h, ns["bn1"] = _bn_act("bn1", h, p["bn1"], s["bn1"], cfg, train,
                           axis_name)
    h = _conv("conv2", h, p["conv2"], stride)
    h, ns["bn2"] = _bn_act("bn2", h, p["bn2"], s["bn2"], cfg, train,
                           axis_name)
    h = _conv("conv3", h, p["conv3"])
    h, ns["bn3"] = _bn_act("bn3", h, p["bn3"], s["bn3"], cfg, train,
                           axis_name, relu=False)
    x = _shortcut(x, p, s, ns, stride, cfg, train, axis_name)
    ns["conv1"] = ns["conv2"] = ns["conv3"] = None
    return _add_act(x, h), ns


def _ws_conv(w, gain, eps: float = 1e-4):
    """Scaled weight standardization (NF-ResNet recipe): standardize the
    kernel over its (kh, kw, cin) fan-in and scale by a learnable
    per-output-channel gain. Touches only WEIGHT bytes — the activation
    tensor never takes the extra stats read/write BatchNorm forces,
    which is the whole point of the nf rung (round-4 roofline: 76.5% of
    ResNet-50 step time bandwidth-bound)."""
    mu = jnp.mean(w, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(w, axis=(0, 1, 2), keepdims=True)
    fan_in = w.shape[0] * w.shape[1] * w.shape[2]
    return (w - mu) * lax.rsqrt(var * fan_in + eps) * gain


def _nf_basic_block(x, p, s, stride, cfg, train, axis_name):
    del s, train, axis_name  # stateless — no running stats
    h = jax.nn.relu(L.conv2d(x, _ws_conv(p["conv1"], p["g1"]),
                             stride=stride) + p["c1"])
    h = L.conv2d(h, _ws_conv(p["conv2"], p["g2"])) + p["c2"]
    if "proj" in p:
        x = L.conv2d(x, _ws_conv(p["proj"], p["gp"]),
                     stride=stride) + p["cp"]
    ns = {k: None for k in p}
    return jax.nn.relu(x + p["skip_gain"] * h), ns


def _nf_bottleneck_block(x, p, s, stride, cfg, train, axis_name):
    del s, train, axis_name
    h = jax.nn.relu(L.conv2d(x, _ws_conv(p["conv1"], p["g1"])) + p["c1"])
    h = jax.nn.relu(L.conv2d(h, _ws_conv(p["conv2"], p["g2"]),
                             stride=stride) + p["c2"])
    h = L.conv2d(h, _ws_conv(p["conv3"], p["g3"])) + p["c3"]
    if "proj" in p:
        x = L.conv2d(x, _ws_conv(p["proj"], p["gp"]),
                     stride=stride) + p["cp"]
    ns = {k: None for k in p}
    return jax.nn.relu(x + p["skip_gain"] * h), ns


def apply(params: Params, state: State, images: jax.Array, cfg: ModelConfig,
          train: bool = True, axis_name: Optional[str] = None
          ) -> Tuple[jax.Array, State]:
    """NHWC images → (logits [B, K], new running-stat state)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = images.astype(cdt)
    p = jax.tree.map(lambda a: a.astype(cdt), params)

    stem_kh = p["stem"]["conv"].shape[0]
    imagenet_stem = stem_kh == 7
    s2d_stem = stem_kh == 4
    nf = "g" in p["stem"]                      # static pytree property
    if nf:
        block = (_nf_bottleneck_block if "conv3" in p["stage1"][0]
                 else _nf_basic_block)
    else:
        block = (_bottleneck_block if "bn3" in p["stage1"][0]
                 else _basic_block)
    if cfg.remat:
        # Recompute each residual block's activations in the backward
        # pass — the same O(1)-in-depth activation-memory lever the ViT
        # stack has (models/vit.py), decisive at ImageNet geometry.
        # Statics ride in a closure: ModelConfig is unhashable, so
        # jax.checkpoint static_argnums is not an option.
        inner = block

        def block(x, bp, s, stride, cfg, train, axis_name):
            return jax.checkpoint(
                lambda xx, pp, ss: inner(xx, pp, ss, stride, cfg, train,
                                         axis_name))(x, bp, s)

    # Mirror init_state's structure exactly: a treedef change between step 1
    # and step 2 would silently retrigger compilation.
    new_state: State = {"fc": {"kernel": None, "bias": None}}
    with jax.named_scope("stem"):
        with jax.named_scope("conv"):
            stem_w = (_ws_conv(p["stem"]["conv"], p["stem"]["g"]) if nf
                      else p["stem"]["conv"])
            if s2d_stem:
                # Space-to-depth: [B,2h,2w,C] -> [B,h,w,4C] (2x2 phases
                # into channels), then the stride-1 4x4 conv with explicit
                # padding (1,2): the 7x7/2 SAME conv (XLA pad lo=2) reads
                # raw rows 2i-2..2i+4 for output i, which fold to rows
                # i-1..i+2 — a 7x7 kernel embeds as ws[m,n,(a,b,c)] =
                # w7[2m+a-... w8[2m+a] with w8[0:7]=w7, w8[7]=0
                # (tests/test_resnet.py pins the fold).
                b_, hh, ww, c_ = x.shape
                x = x.reshape(b_, hh // 2, 2, ww // 2, 2, c_)
                x = jnp.transpose(x, (0, 1, 3, 2, 4, 5)).reshape(
                    b_, hh // 2, ww // 2, 4 * c_)
                x = lax.conv_general_dilated(
                    x, stem_w, window_strides=(1, 1),
                    padding=((1, 2), (1, 2)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
            else:
                x = L.conv2d(x, stem_w, stride=2 if imagenet_stem else 1)
        with jax.named_scope("bn"):
            if nf:
                x = x + p["stem"]["c"]
                new_state["stem"] = {"conv": None, "g": None, "c": None}
            else:
                x, stem_bn = _bn(x, p["stem"]["bn"], state["stem"]["bn"],
                                 cfg, train, axis_name)
                new_state["stem"] = {"conv": None, "bn": stem_bn}
            x = jax.nn.relu(x)
        if imagenet_stem or s2d_stem:
            with jax.named_scope("pool"):
                x = L.max_pool(x, window=3, stride=2)

    for si in range(1, 5):
        key = f"stage{si}"
        if key not in p:
            break
        stage_state = []
        for bi, bp in enumerate(p[key]):
            stride = 2 if (bi == 0 and si > 1) else 1
            with jax.named_scope(f"{key}/block{bi}"):
                x, bs = block(x, bp, state[key][bi], stride, cfg, train,
                              axis_name)
            stage_state.append(bs)
        new_state[key] = stage_state

    with jax.named_scope("head"):
        with jax.named_scope("pool"):
            x = jnp.mean(x, axis=(1, 2))  # global average pool
        with jax.named_scope("fc"):
            logits = L.dense(x, p["fc"]["kernel"], p["fc"]["bias"])
            if cfg.logit_relu:
                # Faithful-mode switch shared with the reference CNN
                # (cifar10cnn.py:145); fixed_config turns it off.
                logits = jax.nn.relu(logits)
            logits = logits.astype(jnp.float32)
    return logits, new_state


# Shared implementation: models.param_count
from dml_cnn_cifar10_tpu.models import param_count  # noqa: E402,F401
