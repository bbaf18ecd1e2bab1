"""Model registry: name → (init, apply, has_state)."""

from __future__ import annotations

from typing import Callable, NamedTuple


class ModelDef(NamedTuple):
    init: Callable          # (key, model_cfg, data_cfg) -> params
    apply: Callable         # stateless: (params, images, cfg, train) -> logits
                            # stateful: (params, state, images, cfg, train)
                            #           -> (logits, new_state)
    init_state: Callable    # (params, model_cfg) -> mutable state pytree
                            # ({} if none)
    has_state: bool
    # apply accepts a ``mesh=`` kwarg: the ViTs route sequence-parallel
    # (ring) attention by it when the mesh's ``seq`` axis is >1, and a
    # Pallas kernel in a GSPMD program needs it to place itself (flash
    # attention, the CNN's pools).
    wants_mesh: bool = False
    # apply returns ``(logits, aux_loss)``; the step adds
    # ``model_cfg.moe_aux_coef * aux_loss`` to the training loss.
    has_aux: bool = False
    # Conv-family models support spatial partitioning: the image H dim
    # shards over the ``seq`` mesh axis (GSPMD inserts conv/pool halo
    # exchanges). ViTs use ``seq`` for token/sequence parallelism instead.
    spatial: bool = False
    # Models that lax.scan their layer stack report ~1/depth of their
    # FLOPs to XLA cost analysis (the scan body is counted once). This
    # optional hook — (model_cfg, data_cfg, microbatch) -> (depth,
    # bf_counted, bf_true) — gives the loop the per-block numbers to
    # correct the TFLOP/s metric (vit.block_flops_probe).
    stack_probe: Callable | None = None
    # A model that is no image classifier states its own loss:
    # ``loss(params, batch, model_cfg, train, mesh=None) -> (loss, stats)``
    # (with ``has_state``: ``..., model_state=state) -> (loss, stats,
    # new_state)``)
    # with ``stats["accuracy"]`` in place of the argmax over logits that
    # nothing holds (parallel/step.py asks for it before ``apply``, which
    # such a model leaves None). Its batch is what ``batch_shape(model_cfg,
    # data_cfg, batch) -> ShapeDtypeStruct`` says, of ``batch_ndim``
    # dimensions (an image batch has 4), and ``step_flops(model_cfg,
    # data_cfg, batch)`` counts a training step's operations from the
    # shapes where XLA's cost analysis cannot (loops counted once, kernels
    # not at all).
    loss: Callable | None = None
    batch_shape: Callable | None = None
    batch_ndim: int = 4
    step_flops: Callable | None = None


def _cnn() -> ModelDef:
    from dml_cnn_cifar10_tpu.models import cnn
    return ModelDef(cnn.init_params, cnn.apply, lambda p, c: {}, False,
                    wants_mesh=True, spatial=True)


def _resnet(depth: int) -> Callable[[], ModelDef]:
    def make() -> ModelDef:
        from dml_cnn_cifar10_tpu.models import resnet
        return ModelDef(
            lambda k, m, d: resnet.init_params(k, m, d, depth=depth),
            resnet.apply,
            lambda p, c: resnet.init_state(p),
            True,
            spatial=True,
        )
    return make


def _vit() -> ModelDef:
    from dml_cnn_cifar10_tpu.models import vit

    def init(key, model_cfg, data_cfg):
        if model_cfg.moe_experts:
            raise ValueError(
                "vit_tiny is the dense ViT; moe_experts > 0 needs model "
                "name 'vit_moe' (its aux loss and expert sharding rules)")
        return vit.init_params(key, model_cfg, data_cfg)

    return ModelDef(init, vit.apply, lambda p, c: {}, False, wants_mesh=True,
                    stack_probe=vit.block_flops_probe)


def _vit_moe() -> ModelDef:
    from dml_cnn_cifar10_tpu.models import vit

    def init(key, model_cfg, data_cfg):
        if model_cfg.moe_experts < 2:
            raise ValueError(
                "vit_moe needs moe_experts >= 2 "
                f"(got {model_cfg.moe_experts}); set ModelConfig.moe_experts")
        return vit.init_params(key, model_cfg, data_cfg)

    return ModelDef(init, vit.apply_with_aux, lambda p, c: {}, False,
                    wants_mesh=True, has_aux=True,
                    stack_probe=vit.block_flops_probe)


def _looped_decoder() -> ModelDef:
    from dml_cnn_cifar10_tpu.models import looped_decoder as m
    return ModelDef(m.init_params, None, lambda p, c: {}, False,
                    wants_mesh=True, loss=m.loss, batch_shape=m.batch_shape,
                    batch_ndim=2, step_flops=m.step_flops)


def _hybrid_decoder() -> ModelDef:
    from dml_cnn_cifar10_tpu.models import hybrid_decoder as m
    return ModelDef(m.init_params, None, m.init_state, True,
                    wants_mesh=True, loss=m.loss, batch_shape=m.batch_shape,
                    batch_ndim=2, step_flops=m.step_flops)


MODELS = {
    "cnn": _cnn,
    "resnet18": _resnet(18),
    "resnet50": _resnet(50),
    "vit_tiny": _vit,
    "vit_moe": _vit_moe,
    "looped_decoder": _looped_decoder,
    "hybrid_decoder": _hybrid_decoder,
}


def get_model(name: str) -> ModelDef:
    if name not in MODELS:
        raise ValueError(f"unknown model {name!r}; have {sorted(MODELS)}")
    try:
        return MODELS[name]()
    except ImportError as e:
        raise NotImplementedError(
            f"model {name!r} is registered but its module is not built yet "
            f"({e}); available today: cnn") from e
