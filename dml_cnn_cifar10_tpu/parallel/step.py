"""The compiled SPMD training step.

One ``jit``-compiled function replaces the reference's per-step machinery —
graph pruning/partitioning, PS→worker param Recv, worker compute,
worker→PS grad Send, PS apply (``cifar10cnn.py:228-230`` and SURVEY §3.3).
Parameters are replicated over the mesh, the batch is sharded on ``data``,
and XLA compiles the gradient all-reduce (psum over ICI) directly into the
step. Two modes:

- default: ``jit`` with sharding annotations; the partitioner inserts the
  collectives (idiomatic, composes with tensor/sequence axes).
- ``explicit_collectives``: the same math under ``shard_map`` with a literal
  ``lax.psum``/``lax.pmean`` — the hand-written SPMD form, used by tests to
  pin down the semantics and as the template for custom-collective work.

Three weight-update paths exist, with PINNED (tested) equivalence
tolerances — see PARITY.md "Update-path equivalence":

- replicated (the default) vs ``explicit_collectives``: bit-identical
  (``test_step.py`` asserts exact equality — same reduction schedule).
- ``--optimizer_sharding zero1`` (reduce-scatter / sharded update /
  all-gather) vs replicated: final params within 1e-6 absolute
  (``test_zero1.py`` — the reduce-scatter may reorder the gradient sum).
- the fused single-pass optimizer (``ops/optimizer.py``) vs the
  ``tree_map`` chain: the XLA form is bit-identical (same f32
  elementwise expression); the Pallas kernel is within a few f32 ULPs
  of it (≤ 5e-7 absolute — FMA contraction differences; both pinned in
  ``test_zero1.py``).

Every mode donates the input state so parameter memory is updated in
place in HBM.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dml_cnn_cifar10_tpu.compilecache import mesh_context
from dml_cnn_cifar10_tpu.compilecache import wrap as _cc_wrap
from dml_cnn_cifar10_tpu.config import DataConfig, ModelConfig, OptimConfig
from dml_cnn_cifar10_tpu.models.registry import ModelDef
from dml_cnn_cifar10_tpu.ops import kernel_paths
from dml_cnn_cifar10_tpu.parallel import mesh as mesh_lib
from dml_cnn_cifar10_tpu.parallel import shardings as shardings_lib
from dml_cnn_cifar10_tpu.train import loss as loss_lib
from dml_cnn_cifar10_tpu.train import metrics as metrics_lib
from dml_cnn_cifar10_tpu.train import optim as optim_lib


class TrainState(NamedTuple):
    """Replicated training state: params + optimizer + model state (BN).

    NamedTuple => already a pytree; flows through jit/shard_map/device_put.
    """

    params: Any
    opt: Any
    model_state: Any

    @property
    def step(self) -> jax.Array:
        return self.opt["step"]


def init_train_state(
    key: jax.Array,
    model_def: ModelDef,
    model_cfg: ModelConfig,
    data_cfg: DataConfig,
    optim_cfg: OptimConfig,
    mesh: Optional[Mesh] = None,
    state_sharding: Optional[TrainState] = None,
    compile_cache=None,
) -> TrainState:
    """Initialize params/opt/model-state and place them on the mesh.

    Replaces chief-initializes-variables-on-PS + workers-wait
    (``cifar10cnn.py:222`` via MonitoredTrainingSession): under SPMD every
    process runs the same deterministic init from the same seed, and the
    mesh placement guarantees consistent values on every chip.

    Placement defaults to replicated — symmetric with ``make_train_step``'s
    default in_shardings. For tensor parallelism pass the SAME
    ``train_state_shardings`` tree to both (as ``Trainer`` does).

    The whole construction is ONE jitted program when a mesh/sharding is
    given (``out_shardings`` places every leaf directly): initializing a
    deep model leaf-by-leaf eagerly costs one device dispatch (and one
    small compile) per tensor — ~60 for a ResNet — where the fused init
    is a single dispatch.
    """
    def build(key):
        params = model_def.init(key, model_cfg, data_cfg)
        opt = optim_lib.sgd_init(params, optim_cfg)
        model_state = model_def.init_state(params, model_cfg)
        if optim_cfg.ema_decay and model_def.has_state and model_state:
            # BatchNorm running stats track the RAW param trajectory; eval
            # with EMA params needs matching averaged stats, so the EMA
            # covers model_state too ("ema_mstate" — replicated like the
            # live model_state by the sharding rules' default).
            opt["ema_mstate"] = jax.tree.map(jnp.array, model_state)
        return TrainState(params=params, opt=opt, model_state=model_state)

    def _cached(jitted):
        # The fused init is a single compiled dispatch — worth caching:
        # a supervisor/elastic restart re-runs it before every restore.
        return _cc_wrap(jitted, compile_cache, "init",
                        mesh_context(mesh, compute_dtype=model_cfg.dtype,
                                     model=model_cfg.name))

    if state_sharding is not None:
        return _cached(jax.jit(build, out_shardings=state_sharding))(key)
    if mesh is not None:
        return _cached(jax.jit(
            build, out_shardings=mesh_lib.replicated(mesh)))(key)
    return build(key)


def train_state_shardings(
    mesh: Mesh,
    model_def: ModelDef,
    model_cfg: ModelConfig,
    data_cfg: DataConfig,
    optim_cfg: OptimConfig,
    fsdp: bool = False,
    zero1: bool = False,
    rules=None,
    strict: bool = False,
) -> TrainState:
    """The ``TrainState`` sharding tree (tensor-parallel rules applied) for
    a model config, computed shape-only via ``eval_shape``. Compute it ONCE
    and hand the same tree to ``make_train_step`` / ``make_eval_step`` /
    ``restore_checkpoint`` — it is the single currency for state layout.
    ``fsdp=True`` adds the ZeRO-3 ``data``-axis sharding of params +
    moments; ``zero1=True`` shards ONLY the optimizer moments (+ EMA)
    over ``data`` (``--optimizer_sharding zero1`` — the state is
    ALLOCATED sharded from init on, which is the HBM win). ``rules`` is
    an optional ``--partition_rules`` table overriding the model's
    default (:mod:`~dml_cnn_cifar10_tpu.parallel.shardings`); ``strict``
    errors on leaves no rule matches."""
    abstract = jax.eval_shape(
        lambda k: init_train_state(k, model_def, model_cfg, data_cfg,
                                   optim_cfg),
        jax.random.key(0))
    return shardings_lib.state_shardings(mesh, model_cfg.name, abstract,
                                         fsdp=fsdp, zero1=zero1,
                                         rules=rules, strict=strict)


def _mesh_kwargs(model_def: ModelDef, mesh: Optional[Mesh]) -> dict:
    """``mesh=`` for a model whose ``apply`` / ``loss`` takes one."""
    return {"mesh": mesh} if (model_def.wants_mesh
                              and mesh is not None) else {}


def _forward_loss(model_def: ModelDef, model_cfg: ModelConfig,
                  axis_name: Optional[str] = None,
                  mesh: Optional[Mesh] = None,
                  label_smoothing: float = 0.0):
    """loss_fn(params, model_state, images, labels) →
    (loss, (logits, new_model_state, stats)).

    ``stats`` is the auxiliary-metrics dict destined for the step metrics
    stream — ``moe_*`` router health for MoE models (aux loss, dropped
    fraction, [E] per-expert load; round-4 verdict #1), ``{}`` otherwise.
    Pytree structure is static per model config, so it scans/accumulates
    like any other metric.

    A model that states its own loss (``model_def.loss``: no image, no
    logits a batch could hold) is asked for it: ``images`` is then its
    batch as the dataset gives it, ``labels`` is not read, ``logits`` is
    None and ``stats["accuracy"]`` takes the argmax's place.
    """
    mesh_kwargs = _mesh_kwargs(model_def, mesh)
    if model_def.loss is not None:
        def own_loss_fn(params, model_state, batch, labels):
            del labels
            if model_def.has_state:
                loss, stats, model_state = model_def.loss(
                    params, batch, model_cfg, train=True,
                    model_state=model_state, **mesh_kwargs)
            else:
                loss, stats = model_def.loss(params, batch, model_cfg,
                                             train=True, **mesh_kwargs)
            return loss, (None, model_state, stats)

        return own_loss_fn
    ce = functools.partial(loss_lib.softmax_cross_entropy,
                           label_smoothing=label_smoothing)

    def loss_fn(params, model_state, images, labels):
        stats = {}
        if model_def.has_state:
            kwargs = {"axis_name": axis_name} if axis_name else {}
            logits, new_state = model_def.apply(
                params, model_state, images, model_cfg, train=True, **kwargs)
            loss = ce(logits, labels)
        elif model_def.has_aux:
            logits, aux = model_def.apply(params, images, model_cfg,
                                          train=True, **mesh_kwargs)
            new_state = model_state
            if isinstance(aux, dict):
                loss = ce(logits, labels) \
                    + model_cfg.moe_aux_coef * aux["aux_loss"]
                stats = {"moe_" + k: lax.stop_gradient(v)
                         for k, v in aux.items()}
            else:
                loss = ce(logits, labels) \
                    + model_cfg.moe_aux_coef * aux
        else:
            logits = model_def.apply(params, images, model_cfg, train=True,
                                     **mesh_kwargs)
            new_state = model_state
            loss = ce(logits, labels)
        return loss, (logits, new_state, stats)

    return loss_fn


def _fsdp_gather_wrap(loss_fn, mesh: Optional[Mesh], model_cfg: ModelConfig,
                      state_sharding: Optional[TrainState], rules=None):
    """ZeRO-3's gather-before-compute, stated explicitly.

    When the parameter STORAGE layout shards over ``data`` (FSDP), leaving
    the layout implicit lets GSPMD propagate the data-axis weight sharding
    into forward/backward, where it meets batch-over-``data`` activations
    at reshape boundaries the partitioner cannot reshard efficiently (the
    "Involuntary full rematerialization" the 8-device dryrun surfaced on
    the CNN's flatten↔conv edge). Constraining params to their base
    (tensor-parallel-only) layout at the point of use compiles to one
    all-gather per step before compute; the constraint's transpose applies
    the same layout to the gradient cotangents, and XLA's
    all-reduce-reassociation turns the grad psum + storage-layout slice
    back into a reduce-scatter — exactly the ZeRO-3 schedule.
    """
    if mesh is None or state_sharding is None:
        return loss_fn
    if not shardings_lib.specs_name_axis(state_sharding.params, "data"):
        return loss_fn
    pipe = mesh.shape.get("pipe", 1) > 1

    def gathered(params, model_state, images, labels):
        specs = shardings_lib.param_pspecs(model_cfg.name, params,
                                           pipe=pipe, rules=rules)
        shs = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                           is_leaf=lambda x: isinstance(x, P))
        params = lax.with_sharding_constraint(params, shs)
        return loss_fn(params, model_state, images, labels)

    return gathered


def _zero1_update(mesh: Mesh, model_cfg: ModelConfig,
                  optim_cfg: OptimConfig, rules=None):
    """The ZeRO-1 weight-update schedule (arxiv 2004.13336), stated as
    sharding constraints: ``(grads, opt, params) -> (new_params,
    new_opt)``.

    Gradients are constrained to the ``data``-sharded layout of the
    optimizer moments, which — composed with the batch-sharded loss's
    gradient psum — XLA's all-reduce reassociation compiles to a
    REDUCE-SCATTER over ``data``; the optimizer update then runs on 1/N
    of the param bytes per replica (the moments live sharded, so the
    elementwise update partitions to match), and constraining the new
    params back to their base (tensor-parallel-only) layout compiles to
    the ALL-GATHER that rebuilds the full weights for the next forward.
    Same math as the replicated update to reduction-reorder tolerance
    (pinned ≤ 1e-6 by ``test_zero1.py``; PARITY.md)."""
    ndata = mesh.shape["data"]
    pipe = mesh.shape.get("pipe", 1) > 1

    def named(specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def update(grads, opt, params):
        shard_sh = named(shardings_lib.param_pspecs(
            model_cfg.name, params, pipe=pipe, fsdp_data=ndata,
            rules=rules))
        base_sh = named(shardings_lib.param_pspecs(
            model_cfg.name, params, pipe=pipe, rules=rules))
        grads = lax.with_sharding_constraint(grads, shard_sh)
        # pallas_ok=False: the update operands are data-sharded here —
        # the XLA expression is what GSPMD partitions into the 1/N
        # per-replica update (ops/optimizer.py module docstring).
        new_params, new_opt = optim_lib.sgd_update(grads, opt, params,
                                                   optim_cfg,
                                                   pallas_ok=False)
        new_params = lax.with_sharding_constraint(new_params, base_sh)
        return new_params, new_opt

    return update


def _global_norm(tree) -> jax.Array:
    """L2 norm over every leaf of a pytree (f32 accumulation so bf16
    params/grads don't overflow the sum of squares)."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def _health_stats(params, new_params, grads) -> dict:
    """Training-health scalars, compiled into the step so they ride the
    loop's single fused boundary fetch: global grad norm (exploding /
    vanishing gradients), param norm (weight growth / decay balance), and
    update ratio ||Δθ||/||θ|| (the effective step size — healthy runs sit
    around 1e-3; ~1 means the optimizer is overwriting the weights)."""
    pnorm = _global_norm(params)
    unorm = _global_norm(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        new_params, params))
    return {"health_grad_norm": _global_norm(grads),
            "health_param_norm": pnorm,
            "health_update_ratio": unorm / (pnorm + 1e-12)}


def _step_body(loss_fn, optim_cfg: OptimConfig,
               health_metrics: bool = False, update_fn=None,
               pallas_ok=None, mesh: Optional[Mesh] = None):
    """``(state, images, labels) -> (new_state, metrics)`` — the shared
    grad/update/metrics math of ``make_train_step`` and
    ``make_train_chunk`` (one source of truth for both).

    ``optim_cfg.grad_accum > 1`` scans over that many microbatches,
    averaging grads/metrics, then applies ONE optimizer update — the same
    math as the full batch (equal-sized microbatches ⇒ mean of means) in
    1/accum of the activation memory.

    ``update_fn(grads, opt, params) -> (new_params, new_opt)`` overrides
    the plain ``optim_lib.sgd_update`` apply — the ZeRO-1 schedule
    (:func:`_zero1_update`) rides this seam; the default is the
    replicated update. ``pallas_ok=False`` vetoes the fused optimizer's
    Pallas lowering and ``mesh`` is the GSPMD program's mesh the kernel
    is placed on (see :func:`_pallas_veto`).
    """
    accum = max(1, optim_cfg.grad_accum)
    if update_fn is None:
        def update_fn(grads, opt, params):
            return optim_lib.sgd_update(grads, opt, params, optim_cfg,
                                        pallas_ok=pallas_ok, mesh=mesh)

    def grad_and_metrics(params, model_state, images, labels):
        # named_scope prefixes the emitted ops so a --profile_at_steps
        # device-time table (utils/devprof.py) can attribute fwd/bwd
        # work vs the optimizer update by name; no numeric effect.
        with jax.named_scope("fwd_bwd"):
            (loss, (logits, new_model_state, stats)), grads = \
                jax.value_and_grad(loss_fn, has_aux=True)(
                    params, model_state, images, labels)
            if logits is None:    # the model's own loss counted it
                stats = dict(stats)
                acc = stats.pop("accuracy")
            else:
                acc = metrics_lib.batch_accuracy(logits, labels)
        metrics = {"loss": loss, "accuracy": acc, **stats}
        return grads, metrics, new_model_state

    staleness = max(0, optim_cfg.async_staleness)

    def step(state: TrainState, images, labels):
        # Async-PS staleness emulation: the forward/backward runs at a
        # snapshot S-1 updates old (slot t%S of the ring), the update
        # applies to the LIVE params — exactly a PS worker whose fetch
        # raced S-1 other workers' applies (cifar10cnn.py:162,230;
        # SURVEY §3.3), made deterministic.
        if staleness >= 2:
            slot = state.opt["step"] % staleness
            fwd_params = jax.tree.map(
                lambda b: lax.dynamic_index_in_dim(b, slot, 0,
                                                   keepdims=False),
                state.opt["stale"])
        else:
            fwd_params = state.params
        if accum == 1:
            grads, metrics, new_model_state = grad_and_metrics(
                fwd_params, state.model_state, images, labels)
        else:
            b = images.shape[0]
            if b % accum:
                raise ValueError(
                    f"batch {b} not divisible by grad_accum {accum}")
            ims = images.reshape(accum, b // accum, *images.shape[1:])
            lbs = labels.reshape(accum, b // accum)

            def micro(carry, xs):
                gsum, msum, mstate = carry
                g, m, mstate = grad_and_metrics(fwd_params, mstate,
                                                xs[0], xs[1])
                return (jax.tree.map(jnp.add, gsum, g),
                        jax.tree.map(jnp.add, msum, m), mstate), None

            # Trace-time structure of the metrics dict (loss/accuracy +
            # any model stats) so the scan carry starts from zeros of the
            # right pytree.
            m_abs = jax.eval_shape(grad_and_metrics, fwd_params,
                                   state.model_state, ims[0], lbs[0])[1]
            zeros = jax.tree.map(jnp.zeros_like, state.params)
            zeros_m = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), m_abs)
            (gsum, msum, new_model_state), _ = lax.scan(
                micro, (zeros, zeros_m, state.model_state), (ims, lbs))
            grads = jax.tree.map(lambda g: g / accum, gsum)
            metrics = jax.tree.map(lambda v: v / accum, msum)
        with jax.named_scope("optimizer"):
            new_params, new_opt = update_fn(grads, state.opt, state.params)
        if health_metrics:
            metrics.update(_health_stats(state.params, new_params, grads))
        if staleness >= 2:
            # The slot just consumed receives the freshly updated params
            # (the worker pushes its apply and re-fetches).
            new_opt["stale"] = jax.tree.map(
                lambda b, p: lax.dynamic_update_index_in_dim(
                    b, p.astype(b.dtype), slot, 0),
                state.opt["stale"], new_params)
        if "ema_mstate" in state.opt:
            d = optim_lib.ema_decay_at(optim_cfg, new_opt["step"])
            new_opt["ema_mstate"] = jax.tree.map(
                lambda e, m: (d * e + (1 - d) * m).astype(e.dtype),
                state.opt["ema_mstate"], new_model_state)
        return TrainState(new_params, new_opt, new_model_state), metrics

    return step


def _check_optimizer_sharding(optim_cfg: OptimConfig,
                              explicit_collectives: bool = False) -> None:
    """Reject invalid ``--optimizer_sharding`` combinations at build
    time (every step builder calls this)."""
    mode = getattr(optim_cfg, "optimizer_sharding", "none")
    if mode not in ("none", "zero1"):
        raise ValueError(
            f"optimizer_sharding={mode!r} must be one of none | zero1")
    if mode == "zero1":
        if explicit_collectives:
            raise ValueError(
                "optimizer_sharding=zero1 needs the GSPMD (default) "
                "step: the explicit_collectives shard_map path applies "
                "the update replicated per device")
        if optim_cfg.async_staleness >= 2:
            raise ValueError(
                "optimizer_sharding=zero1 does not compose with "
                "async_staleness: the snapshot ring serves the forward "
                "pass and must stay whole, but zero1 shards the update "
                "state it is refreshed from")


def _maybe_zero1(mesh: Optional[Mesh], model_cfg: ModelConfig,
                 optim_cfg: OptimConfig, rules=None):
    """The ZeRO-1 update override when configured and meaningful
    (a mesh exists), else None (plain replicated update)."""
    if mesh is None or \
            getattr(optim_cfg, "optimizer_sharding", "none") != "zero1":
        return None
    return _zero1_update(mesh, model_cfg, optim_cfg, rules=rules)


def _pallas_veto(state_sharding: Optional[TrainState]):
    """``pallas_ok`` for the fused optimizer. The rule the GSPMD step
    builders apply, in full:

    - the compiled Pallas kernel runs only on a TPU backend, for the
      fused SGD update, when EVERY operand of the update is replicated:
      no param spec names a mesh axis (this function returns ``None``,
      the platform default) and the update is not the zero1 schedule
      (:func:`_zero1_update` passes ``pallas_ok=False`` itself);
    - on a mesh of more than one device that kernel is placed by a
      ``shard_map`` with replicated specs over the whole mesh
      (``ops/optimizer.py``) — a compiled ``pallas_call`` cannot sit
      bare in an auto-partitioned program, Mosaic refuses to lower
      there;
    - sharded operands (tp/fsdp/pipe/seq param layout: this function
      returns ``False``; zero1) keep the identical-math XLA expression,
      which GSPMD partitions into one loop over the local shard.

    Whichever was compiled is printed once per builder
    (:func:`_announced`)."""
    if state_sharding is None:
        return None
    if any(shardings_lib.specs_name_axis(state_sharding.params, ax)
           for ax in ("model", "pipe", "seq", "data")):
        return False
    return None


def _announced(fn, phase: str, mesh: Optional[Mesh]):
    """``fn``, saying once which update, attention and pool path it
    compiled (and, where the model has them, what a recomputed layer keeps
    and how the experts' rows reach their tokens).

    The choice is made where the shapes are known, inside the trace
    (``ops/kernel_paths.py``), so the line prints when the step is
    first traced — the moment the program is built — and never on a
    steady-state call. A re-trace for AOT lowering repeats the same
    line and is dropped."""
    said = set()

    @functools.wraps(fn)
    def traced(*args):
        with kernel_paths.recording() as rec:
            out = fn(*args)
        line = (f"[step] {phase} on {mesh.size if mesh is not None else 1}"
                f" device(s): update={rec.get('update', 'none')} "
                f"attention={rec.get('attention', 'none')} "
                f"pool={rec.get('pool', 'none')}"
                + "".join(f" {kind}={rec[kind]}"
                          for kind in ("remat", "experts") if kind in rec))
        if line not in said:
            said.add(line)
            print(line, flush=True)
        return out

    return traced


def make_train_step(
    model_def: ModelDef,
    model_cfg: ModelConfig,
    optim_cfg: OptimConfig,
    mesh: Optional[Mesh] = None,
    explicit_collectives: bool = False,
    state_sharding: Optional[TrainState] = None,
    health_metrics: bool = False,
    compile_cache=None,
    rules=None,
) -> Callable[[TrainState, jax.Array, jax.Array],
              Tuple[TrainState, dict]]:
    """Build the jitted train step:
    ``(state, images, labels) -> (new_state, {"loss", "accuracy"})``.

    ``state_sharding`` (a ``train_state_shardings`` tree) keeps weights
    partitioned per the model's tensor-parallel rules
    (:mod:`~dml_cnn_cifar10_tpu.parallel.shardings`); ``None`` means
    replicated state — identical layout when the ``model`` axis is 1.
    ``rules`` is the optional ``--partition_rules`` table (must match
    the one ``state_sharding`` was built with).
    """
    _check_optimizer_sharding(optim_cfg, explicit_collectives)

    if explicit_collectives and mesh is not None:
        if (mesh.shape["model"] * mesh.shape["seq"]
                * mesh.shape.get("pipe", 1)) > 1:
            raise ValueError(
                "explicit_collectives is the pedagogical dp-only path; "
                "tensor/sequence/pipeline axes need the GSPMD (default) step")
        if optim_cfg.grad_accum > 1:
            raise ValueError(
                "grad_accum > 1 is not implemented on the "
                "explicit_collectives path; use the GSPMD (default) step")
        if optim_cfg.async_staleness >= 2:
            raise ValueError(
                "async_staleness needs the GSPMD (default) step, not "
                "explicit_collectives")
        return _make_explicit_train_step(model_def, model_cfg, optim_cfg,
                                         mesh, health_metrics=health_metrics)

    if (optim_cfg.async_staleness >= 2 and mesh is not None
            and mesh.shape.get("pipe", 1) > 1):
        # The pipe layout rule shards the LEADING axis of stacked
        # leaves, which for the stale ring is the snapshot axis S, not
        # depth — the layouts conflict. (Pipelined async emulation has
        # no meaningful reference counterpart either.)
        raise ValueError(
            "async_staleness does not compose with pipeline parallelism "
            "(the pipe sharding rule would claim the snapshot ring's "
            "leading axis)")

    loss_fn = _fsdp_gather_wrap(
        _forward_loss(model_def, model_cfg, mesh=mesh,
                      label_smoothing=optim_cfg.label_smoothing),
        mesh, model_cfg, state_sharding, rules=rules)
    step = _step_body(loss_fn, optim_cfg, health_metrics=health_metrics,
                      update_fn=_maybe_zero1(mesh, model_cfg, optim_cfg,
                                             rules),
                      pallas_ok=_pallas_veto(state_sharding), mesh=mesh)
    step = _announced(step, "train_step", mesh)

    def _cached(jitted):
        return _cc_wrap(jitted, compile_cache, "train_step",
                        mesh_context(mesh, donate=(0,),
                                     compute_dtype=model_cfg.compute_dtype,
                                     model=model_cfg.name))

    if mesh is None:
        return _cached(jax.jit(step, donate_argnums=0))
    repl = mesh_lib.replicated(mesh)
    state_sh = state_sharding if state_sharding is not None else repl
    # Conv models use a nontrivial ``seq`` axis for spatial partitioning:
    # the image H dim shards over ``seq`` and GSPMD inserts the conv/pool
    # halo exchanges (the vision analog of sequence parallelism).
    spatial = mesh_lib.spatial_enabled(model_def, mesh)
    data = mesh_lib.batch_sharding(mesh, model_def.batch_ndim,
                                   spatial=spatial)
    lab = mesh_lib.batch_sharding(mesh, 1)
    return _cached(jax.jit(
        step,
        in_shardings=(state_sh, data, lab),
        out_shardings=(state_sh, repl),
        donate_argnums=0,
    ))


def _chunk_body(loss_fn, optim_cfg: OptimConfig,
                data_cfg: Optional[DataConfig],
                health_metrics: bool = False, update_fn=None,
                pallas_ok=None, mesh: Optional[Mesh] = None):
    """``(state, images [K,B,...], labels [K,B]) -> (state, last-step
    metrics)`` — the shared scan-over-K-steps math of ``make_train_chunk``
    and ``make_train_chunk_resident`` (one source of truth).

    With ``data_cfg``, images are RAW uint8 and cast/crop/normalize run
    on device first — one vectorized op over the whole [K,B,...] chunk
    BEFORE the scan (uint8 stays a single layout-friendly op, the scan
    then slices float32). Augmented configs fold the global step into the
    data seed so every chunk draws fresh crops/flips, deterministically
    per (seed, step).
    """
    one_step = _step_body(loss_fn, optim_cfg,
                          health_metrics=health_metrics,
                          update_fn=update_fn, pallas_ok=pallas_ok,
                          mesh=mesh)
    if data_cfg is not None and data_cfg.tokens:
        data_cfg = None           # token rows go to the model as they are
    if data_cfg is not None:
        from dml_cnn_cifar10_tpu.ops.preprocess import device_preprocess

    augmented = data_cfg is not None and data_cfg.augmented
    # Whole-chunk decode materializes [K, B, crop, crop, C] float32. At
    # CIFAR geometry that is ~90 MB and the single vectorized op wins; at
    # ImageNet geometry (224², K=100, B=256) it is ~15 GB — past HBM. Past
    # this threshold the decode moves INSIDE the scan: fp32 exists one
    # step at a time, only the uint8 chunk stays whole.
    DECODE_IN_SCAN_BYTES = 1 << 30

    def decode(imgs, step):
        # One source of truth for both size regimes: per-(seed, step) key
        # so draws are distinct and deterministic wherever decode runs.
        if augmented:
            with jax.named_scope("decode"):   # the key is the decode's
                key = jax.random.fold_in(jax.random.key(data_cfg.seed),
                                         step)
            return device_preprocess(imgs, data_cfg, key)
        return device_preprocess(imgs, data_cfg)

    def run(state: TrainState, images, labels):
        decode_in_scan = False
        if data_cfg is not None:
            # Peak decode allocation is the float32 view at the LARGER of
            # the source and crop geometry: device_preprocess casts the
            # full-size [K,B,H,W,C] to fp32 before cropping (and the
            # random-crop einsum materializes that operand), while a
            # crop-larger-than-source config pads up instead.
            k, b, h, w = images.shape[:4]
            ph = max(h, data_cfg.crop_height)
            pw = max(w, data_cfg.crop_width)
            decoded = k * b * ph * pw * data_cfg.num_channels * 4
            decode_in_scan = decoded > DECODE_IN_SCAN_BYTES
            if not decode_in_scan:
                images = decode(images, state.step)

        def body(st, batch):
            imgs, lbs = batch
            if decode_in_scan:
                imgs = decode(imgs, st.step)
            return one_step(st, imgs, lbs)

        state, ms = lax.scan(body, state, (images, labels))
        return state, jax.tree.map(lambda x: x[-1], ms)

    return run


def make_train_chunk(
    model_def: ModelDef,
    model_cfg: ModelConfig,
    optim_cfg: OptimConfig,
    mesh: Optional[Mesh] = None,
    state_sharding: Optional[TrainState] = None,
    data_cfg: Optional[DataConfig] = None,
    health_metrics: bool = False,
    compile_cache=None,
    rules=None,
) -> Callable[[TrainState, jax.Array, jax.Array],
              Tuple[TrainState, dict]]:
    """K training steps per dispatch: ``(state, images [K,B,...], labels
    [K,B]) -> (new_state, metrics of the LAST step)``.

    A ``lax.scan`` over stacked batches amortizes per-step host dispatch —
    the small-model regime (the reference CNN is ~1 ms of MXU work per
    step) is dispatch-bound otherwise. Same math as ``make_train_step``
    applied K times; the chunk is the unit the driver hands to the device,
    metrics cadence stays per-chunk.

    With ``data_cfg`` the chunk takes RAW uint8 full-size images
    ([K, B, H, W, C]) and runs cast/crop/normalize on device
    (:func:`~dml_cnn_cifar10_tpu.ops.preprocess.device_preprocess`) — the
    host only shuffles bytes, H2D moves uint8.
    """
    _check_optimizer_sharding(optim_cfg)
    chunk = _chunk_body(
        _fsdp_gather_wrap(
            _forward_loss(model_def, model_cfg, mesh=mesh,
                          label_smoothing=optim_cfg.label_smoothing),
            mesh, model_cfg, state_sharding, rules=rules),
        optim_cfg, data_cfg, health_metrics=health_metrics,
        update_fn=_maybe_zero1(mesh, model_cfg, optim_cfg, rules),
        pallas_ok=_pallas_veto(state_sharding), mesh=mesh)
    chunk = _announced(chunk, "train_chunk", mesh)

    def _cached(jitted):
        return _cc_wrap(jitted, compile_cache, "train_chunk",
                        mesh_context(mesh, donate=(0,),
                                     compute_dtype=model_cfg.compute_dtype,
                                     model=model_cfg.name))

    if mesh is None:
        return _cached(jax.jit(chunk, donate_argnums=0))
    repl = mesh_lib.replicated(mesh)
    state_sh = state_sharding if state_sharding is not None else repl
    spatial = mesh_lib.spatial_enabled(model_def, mesh)
    data = mesh_lib.batch_sharding(mesh, 1 + model_def.batch_ndim,
                                   leading_dims=1, spatial=spatial)
    lab = mesh_lib.batch_sharding(mesh, 2, leading_dims=1)
    return _cached(jax.jit(
        chunk,
        in_shardings=(state_sh, data, lab),
        out_shardings=(state_sh, repl),
        donate_argnums=0,
    ))


def make_train_chunk_resident(
    model_def: ModelDef,
    model_cfg: ModelConfig,
    optim_cfg: OptimConfig,
    mesh: Mesh,
    dataset_images: jax.Array,
    dataset_labels: jax.Array,
    state_sharding: Optional[TrainState] = None,
    data_cfg: Optional[DataConfig] = None,
    index_stream: Optional[Tuple[int, int, int]] = None,
    health_metrics: bool = False,
    compile_cache=None,
    rules=None,
) -> Callable[[TrainState, jax.Array], Tuple[TrainState, dict]]:
    """Chunked training against an HBM-resident dataset:
    ``(state, idx [K, B] int32) -> (new_state, metrics of the LAST step)``.

    The decisive TPU-native data-path move for small-sample workloads: the
    full uint8 dataset (CIFAR-10 train = 50k x 3073B = 154 MB) lives in
    HBM once, replicated over the mesh; per chunk the host ships only the
    shuffled **index** array (K*B int32 = ~10 KB), and the gather, decode,
    augment, and K training steps all run on device. Eliminates the
    host-side image gather + 8 MB H2D per chunk that otherwise bound
    throughput (measured ~8 ms/chunk host vs ~0.1-2 ms/chunk device on the
    reference CNN).

    ``dataset_images`` [N, H, W, C] uint8 and ``dataset_labels`` [N] int32
    should be placed replicated on ``mesh`` (``jax.device_put`` with
    ``mesh_lib.replicated``) before building the step. Same math as
    ``make_train_chunk`` on the same indices (tests assert it).

    ``index_stream=(seed, global_batch, K)`` goes one step further
    (round-3 verdict #4): the shuffled indices are GENERATED ON DEVICE
    inside the scan (``data/device_stream.py``'s stateless per-epoch
    pseudo-permutation keyed on ``state.step``), so the chunk signature
    becomes ``(state,) -> (new_state, metrics)`` — a training dispatch
    moves NOTHING host→device. Exact resume is free: the stream position
    is the step itself.
    """
    if data_cfg is None:
        # The resident input is ALWAYS raw uint8 from HBM; without a
        # decode config the model would silently train on 0-255
        # un-cropped pixels.
        raise ValueError(
            "make_train_chunk_resident requires data_cfg (the gathered "
            "dataset rows are raw uint8 and must be decoded on device)")
    _check_optimizer_sharding(optim_cfg)
    loss = _fsdp_gather_wrap(
        _forward_loss(model_def, model_cfg, mesh=mesh,
                      label_smoothing=optim_cfg.label_smoothing),
        mesh, model_cfg, state_sharding, rules=rules)

    spatial = mesh_lib.spatial_enabled(model_def, mesh)
    repl = mesh_lib.replicated(mesh)
    state_sh = state_sharding if state_sharding is not None else repl

    body = _chunk_body(loss, optim_cfg, data_cfg,
                       health_metrics=health_metrics,
                       update_fn=_maybe_zero1(mesh, model_cfg, optim_cfg,
                                              rules),
                       pallas_ok=_pallas_veto(state_sharding), mesh=mesh)
    body = _announced(body, "train_chunk_resident", mesh)
    gathered_sh = mesh_lib.batch_sharding(mesh, 1 + model_def.batch_ndim,
                                          leading_dims=1, spatial=spatial)

    def _cached(jitted, donate):
        # Wrapped BEFORE the dataset-binding partial: the cache key then
        # covers the dataset avals too (a different split size is a
        # different program).
        return _cc_wrap(jitted, compile_cache, "train_chunk_resident",
                        mesh_context(mesh, donate=(donate,),
                                     compute_dtype=model_cfg.compute_dtype,
                                     model=model_cfg.name))

    if index_stream is not None:
        from dml_cnn_cifar10_tpu.data import device_stream

        seed, global_batch, k = index_stream
        n = dataset_images.shape[0]
        idx_sh2 = mesh_lib.batch_sharding(mesh, 2, leading_dims=1)

        def chunk_dev(ds_images, ds_labels, state: TrainState):
            # The whole chunk's [K, B] indices in one vectorized call
            # from state.step — then the identical whole-chunk gather +
            # vectorized decode as the host-index path (a per-step
            # in-scan gather measured ~10 % slower).
            idx = device_stream.chunk_shuffle_indices(
                seed, state.step, global_batch, k, n)
            idx = lax.with_sharding_constraint(idx, idx_sh2)
            with jax.named_scope("gather"):
                images = ds_images[idx]
                labels = ds_labels[idx]
            if spatial:
                images = lax.with_sharding_constraint(images, gathered_sh)
            return body(state, images, labels)

        jitted_dev = _cached(jax.jit(
            chunk_dev,
            in_shardings=(repl, repl, state_sh),
            out_shardings=(state_sh, repl),
            donate_argnums=2,
        ), donate=2)
        fn = functools.partial(jitted_dev, dataset_images, dataset_labels)

        def lower_dev(*abs_args):
            from dml_cnn_cifar10_tpu.utils.profiling import abstractify
            return jitted_dev.lower(*abstractify((dataset_images,
                                                  dataset_labels)),
                                    *abs_args)

        fn.lower = lower_dev
        if compile_cache is not None:
            def flops_dev(abs_args):
                from dml_cnn_cifar10_tpu.utils.profiling import abstractify
                return jitted_dev.cached_flops(
                    (*abstractify((dataset_images, dataset_labels)),
                     *abs_args))
            fn.cached_flops = flops_dev
        return fn

    def chunk(dataset_images, dataset_labels, state: TrainState, idx):
        # Device-side gather: [K, B] indices into the HBM-resident arrays.
        # Conv models on a seq>1 mesh pin the gathered chunk to the
        # spatial (H-over-seq) layout so the resident path partitions
        # activations the same way the host-fed paths do.
        with jax.named_scope("gather"):
            images = dataset_images[idx]
            labels = dataset_labels[idx]
        if spatial:
            images = lax.with_sharding_constraint(images, gathered_sh)
        return body(state, images, labels)

    idx_sh = mesh_lib.batch_sharding(mesh, 2, leading_dims=1)
    jitted = _cached(jax.jit(
        chunk,
        in_shardings=(repl, repl, state_sh, idx_sh),
        out_shardings=(state_sh, repl),
        donate_argnums=2,
    ), donate=2)
    fn = functools.partial(jitted, dataset_images, dataset_labels)

    def lower(*abs_args):
        # Expose AOT lowering through the partial so the driver's
        # flops probe (utils/profiling.compiled_flops) works on the
        # resident path too: prepend the bound dataset avals.
        from dml_cnn_cifar10_tpu.utils.profiling import abstractify
        return jitted.lower(*abstractify((dataset_images,
                                          dataset_labels)), *abs_args)

    fn.lower = lower
    if compile_cache is not None:
        def flops_idx(abs_args):
            from dml_cnn_cifar10_tpu.utils.profiling import abstractify
            return jitted.cached_flops(
                (*abstractify((dataset_images, dataset_labels)),
                 *abs_args))
        fn.cached_flops = flops_idx
    return fn


def _eval_accuracy_fn(model_def: ModelDef, model_cfg: ModelConfig, mesh):
    """``(state, batch, labels) -> accuracy`` of one batch: the argmax over
    the logits against the labels, or what a model that states its own
    loss counts as right (``stats["accuracy"]``)."""
    if model_def.loss is None:
        logits_fn = _eval_logits_fn(model_def, model_cfg, mesh)
        return lambda state, batch, labels: metrics_lib.batch_accuracy(
            logits_fn(state, batch), labels)
    mesh_kwargs = _mesh_kwargs(model_def, mesh)

    def accuracy(state: TrainState, batch, labels):
        del labels
        params = state.opt.get("ema", state.params)
        kwargs = dict(mesh_kwargs)
        if model_def.has_state:
            kwargs["model_state"] = state.opt.get("ema_mstate",
                                                  state.model_state)
        return model_def.loss(params, batch, model_cfg, train=False,
                              **kwargs)[1]["accuracy"]

    return accuracy


def _eval_logits_fn(model_def: ModelDef, model_cfg: ModelConfig, mesh):
    mesh_kwargs = _mesh_kwargs(model_def, mesh)

    def logits_fn(state: TrainState, images):
        # When the optimizer tracks a parameter EMA, eval uses it (the
        # standard recipe: train on raw params, evaluate the average),
        # paired with the matching EMA of the BN running stats. Key
        # presence is a static pytree property — resolved at trace.
        params = state.opt.get("ema", state.params)
        if model_def.has_state:
            mstate = state.opt.get("ema_mstate", state.model_state)
            logits, _ = model_def.apply(params, mstate,
                                        images, model_cfg, train=False)
        elif model_def.has_aux:
            logits, _ = model_def.apply(params, images, model_cfg,
                                        train=False, **mesh_kwargs)
        else:
            logits = model_def.apply(params, images, model_cfg,
                                     train=False, **mesh_kwargs)
        return logits

    return logits_fn


def make_eval_resident(
    model_def: ModelDef,
    model_cfg: ModelConfig,
    mesh: Mesh,
    images_u8,
    labels,
    data_cfg: DataConfig,
    state_sharding: Optional[TrainState] = None,
    batch_size: int = 128,
    num_shards: int = 1,
    total_records: Optional[int] = None,
    expected_batches: Optional[int] = None,
    compile_cache=None,
):
    """Full-split eval in ONE dispatch against an HBM-resident split:
    returns ``(fn, total)`` with ``fn(state) -> GLOBAL correct count``
    (device scalar, replicated) over all ``total`` real records.

    The split is padded to a whole number of batches (pad labels -1 ⇒ 0
    correct, mirroring ``full_sweep_padded``), reshaped ``[M, B, ...]``,
    and placed once; eval is a ``lax.scan`` of decode→forward→count over
    the M batches. Replaces M host-fed eval dispatches + M device→host
    fetches per eval with one dispatch + one fetch.

    Multi-host (``num_shards`` > 1): ``images_u8``/``labels`` are THIS
    process's strided shard and ``batch_size`` its per-process share of
    the global eval batch. Every process pads to the same batch count
    ``M = ceil(ceil(total/num_shards)/batch_size)`` (strided shards
    differ by ≤1 record — same rule as ``full_sweep_padded``) and
    contributes its slice of the global ``[M, B_global, ...]`` arrays
    (``place_local``); the replicated output scalar IS the global
    correct count (GSPMD inserts the cross-data-axis reduction), so one
    dispatch + one ``device_get`` per process covers the whole split —
    round 2's multi-host host-fed fallback (M H2D uploads per eval) is
    gone.
    """
    import numpy as np

    from dml_cnn_cifar10_tpu.ops.preprocess import device_preprocess

    n = images_u8.shape[0]                       # local shard size
    if num_shards > 1 and total_records is None:
        # m derived from the LOCAL shard would differ across processes
        # (strided shards differ by 1 record) → mismatched global arrays
        # and a hang instead of an error. Fail at build time.
        raise ValueError(
            "make_eval_resident with num_shards > 1 needs total_records "
            "(the pre-shard split size) so every process pads to the "
            "same batch count")
    total = int(total_records) if total_records is not None else n
    largest_shard = -(-total // max(num_shards, 1))
    m = -(-largest_shard // batch_size)
    if expected_batches is not None and m != expected_batches:
        # The iterator's padded-sweep rule
        # (pipeline.num_padded_sweep_batches) and this one must agree —
        # the host-fed and resident paths count correctness over the
        # same geometry, and multi-host correctness needs every process
        # on the same M.
        raise ValueError(
            f"resident eval computed {m} padded batches but the "
            f"iterator's sweep rule says {expected_batches}")
    pad = m * batch_size - n
    if pad:
        images_u8 = np.concatenate(
            [images_u8, np.zeros((pad, *images_u8.shape[1:]),
                                 images_u8.dtype)])
        labels = np.concatenate([labels, np.full((pad,), -1, labels.dtype)])
    ims = images_u8.reshape(m, batch_size, *images_u8.shape[1:])
    lbs = labels.reshape(m, batch_size).astype(np.int32)

    logits_fn = _eval_logits_fn(model_def, model_cfg, mesh)
    eval_cfg = _eval_data_cfg(data_cfg)

    def ev(ims, lbs, state: TrainState):
        def body(total, batch):
            images = device_preprocess(batch[0], eval_cfg)
            logits = logits_fn(state, images)
            return total + metrics_lib.correct_count(logits, batch[1]), None

        total, _ = lax.scan(body, jnp.zeros((), jnp.int32), (ims, lbs))
        return total

    repl = mesh_lib.replicated(mesh)
    state_sh = state_sharding if state_sharding is not None else repl
    data_sh = mesh_lib.batch_sharding(
        mesh, ims.ndim, leading_dims=1,
        spatial=mesh_lib.spatial_enabled(model_def, mesh))
    lab_sh = mesh_lib.batch_sharding(mesh, 2, leading_dims=1)
    jitted = _cc_wrap(
        jax.jit(ev, in_shardings=(data_sh, lab_sh, state_sh),
                out_shardings=repl),
        compile_cache, "eval_resident",
        mesh_context(mesh, compute_dtype=model_cfg.compute_dtype,
                     model=model_cfg.name))
    ims_d = mesh_lib.place_local(data_sh, ims)
    lbs_d = mesh_lib.place_local(lab_sh, lbs)
    return functools.partial(jitted, ims_d, lbs_d), total


def make_batch_eval_resident(
    model_def: ModelDef,
    model_cfg: ModelConfig,
    mesh: Mesh,
    dataset_images: jax.Array,
    dataset_labels: jax.Array,
    data_cfg: DataConfig,
    state_sharding: Optional[TrainState] = None,
    compile_cache=None,
    scope: str = "train_acc",
):
    """Single-batch accuracy against an HBM-resident dataset:
    ``fn(state, idx [B] int32) -> accuracy`` (device scalar). The
    index-fed mirror of ``make_eval_step`` for the boundary metrics —
    ~0.5 KB host→device instead of a decoded image batch. ``scope`` is
    the named scope the whole pass runs under (metadata only)."""
    from dml_cnn_cifar10_tpu.ops.preprocess import device_preprocess

    accuracy_fn = _eval_accuracy_fn(model_def, model_cfg, mesh)
    eval_cfg = _eval_data_cfg(data_cfg)

    spatial = mesh_lib.spatial_enabled(model_def, mesh)
    gathered_sh = mesh_lib.batch_sharding(mesh, model_def.batch_ndim,
                                          spatial=spatial)

    def ev(dataset_images, dataset_labels, state: TrainState, idx):
        with jax.named_scope(scope):
            with jax.named_scope("gather"):
                images = dataset_images[idx]
                labels = dataset_labels[idx]
            if spatial:
                images = lax.with_sharding_constraint(images, gathered_sh)
            if not eval_cfg.tokens:
                images = device_preprocess(images, eval_cfg)
            return accuracy_fn(state, images, labels)

    repl = mesh_lib.replicated(mesh)
    state_sh = state_sharding if state_sharding is not None else repl
    jitted = _cc_wrap(
        jax.jit(
            ev,
            in_shardings=(repl, repl, state_sh,
                          mesh_lib.batch_sharding(mesh, 1)),
            out_shardings=repl,
        ),
        compile_cache, "eval_batch_resident",
        mesh_context(mesh, compute_dtype=model_cfg.compute_dtype,
                     model=model_cfg.name))
    fn = functools.partial(jitted, dataset_images, dataset_labels)

    def lower(*abs_args):
        # AOT lowering through the partial, as the resident chunk offers
        # it: the telemetry probe reads this program's scope map.
        from dml_cnn_cifar10_tpu.utils.profiling import abstractify
        return jitted.lower(*abstractify((dataset_images,
                                          dataset_labels)), *abs_args)

    fn.lower = lower
    return fn


def _eval_data_cfg(data_cfg: DataConfig) -> DataConfig:
    """Eval-time decode config: deterministic (all augmentation off)."""
    return data_cfg.without_augmentation()


def _make_explicit_train_step(model_def, model_cfg, optim_cfg, mesh: Mesh,
                              health_metrics: bool = False):
    """shard_map form: per-device forward/backward on the local batch shard,
    explicit ``lax.psum`` of gradients — the literal translation of
    "workers compute grads, aggregation applies them" minus the
    asynchrony (SURVEY §2.3, §3.3)."""
    loss_fn = _forward_loss(model_def, model_cfg, axis_name="data",
                             label_smoothing=optim_cfg.label_smoothing)
    ndev = mesh.shape["data"]

    def local_step(state: TrainState, images, labels):
        (loss, (logits, new_model_state, stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, state.model_state, images,
                                   labels)
        # Gradient all-reduce over ICI — the replacement for worker→PS
        # gradient RPCs (cifar10cnn.py:230, SURVEY §3.3). Mean, because each
        # device's loss is already a mean over its local shard.
        grads = lax.pmean(grads, "data")
        loss = lax.pmean(loss, "data")
        acc = lax.pmean(metrics_lib.batch_accuracy(logits, labels), "data")
        stats = lax.pmean(stats, "data")
        new_params, new_opt = optim_lib.sgd_update(grads, state.opt,
                                                   state.params, optim_cfg)
        # Health scalars come AFTER the pmean: the reduced grads/params
        # are replicated, so the norms match the GSPMD step's and satisfy
        # the out_specs=P() replication contract.
        if health_metrics:
            stats = {**stats, **_health_stats(state.params, new_params,
                                              grads)}
        if model_def.has_state:
            new_model_state = lax.pmean(new_model_state, "data")
        if "ema_mstate" in state.opt:
            d = optim_lib.ema_decay_at(optim_cfg, new_opt["step"])
            new_opt["ema_mstate"] = jax.tree.map(
                lambda e, m: (d * e + (1 - d) * m).astype(e.dtype),
                state.opt["ema_mstate"], new_model_state)
        return (TrainState(new_params, new_opt, new_model_state),
                {"loss": loss, "accuracy": acc, **stats})

    shmapped = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(shmapped, donate_argnums=0)


def make_eval_step(
    model_def: ModelDef,
    model_cfg: ModelConfig,
    mesh: Optional[Mesh] = None,
    state_sharding: Optional[TrainState] = None,
    compile_cache=None,
) -> Callable[[TrainState, jax.Array, jax.Array], dict]:
    """Jitted eval: ``(state, images, labels) -> {"accuracy", "correct"}`` —
    single-batch accuracy for faithful parity eval (``cifar10cnn.py:
    237-241``); ``correct`` is the global summable count for full-test-set
    eval (pad rows labeled -1 contribute 0)."""

    if model_def.loss is not None:
        accuracy_fn = _eval_accuracy_fn(model_def, model_cfg, mesh)

        def step(state: TrainState, batch, labels):
            # no per-row count: a batch's accuracy is over its tokens
            return {"accuracy": accuracy_fn(state, batch, labels)}
    else:
        logits_fn = _eval_logits_fn(model_def, model_cfg, mesh)

        def step(state: TrainState, images, labels):
            logits = logits_fn(state, images)
            return {
                "accuracy": metrics_lib.batch_accuracy(logits, labels),
                "correct": metrics_lib.correct_count(logits, labels),
            }

    def _cached(jitted):
        return _cc_wrap(jitted, compile_cache, "eval_step",
                        mesh_context(mesh,
                                     compute_dtype=model_cfg.compute_dtype,
                                     model=model_cfg.name))

    if mesh is None:
        return _cached(jax.jit(step))
    repl = mesh_lib.replicated(mesh)
    state_sh = state_sharding if state_sharding is not None else repl
    spatial = mesh_lib.spatial_enabled(model_def, mesh)
    return _cached(jax.jit(
        step,
        in_shardings=(state_sh,
                      mesh_lib.batch_sharding(mesh, model_def.batch_ndim,
                                              spatial=spatial),
                      mesh_lib.batch_sharding(mesh, 1)),
        out_shardings=repl,
    ))
