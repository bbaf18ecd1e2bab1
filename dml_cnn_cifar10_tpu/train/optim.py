"""Optimizer + LR schedule.

Reference (``train_step``, ``cifar10cnn.py:159-164``): plain
``GradientDescentOptimizer`` with an ``exponential_decay(0.1, gen, 250, 0.9,
staircase=True)`` schedule — where ``gen`` is a variable that is never
incremented (``:216``), so the *effective* reference LR is a constant 0.1.
``OptimConfig.dead_lr_decay=True`` (faithful default) reproduces that;
``False`` keys the decay on the global step as the code intended.

Implemented as a minimal functional optimizer (init/update pytrees) with
optional momentum / weight decay / grad clipping for the config-ladder
models. It is deliberately optax-shaped; ``as_optax()`` exposes the same
thing as a ``GradientTransformation`` for users who want to compose.

The plain-SGD apply runs fused by default (``ops/optimizer.py``:
momentum + weight decay + LR in ONE pass over the param bytes — a
Pallas TPU kernel with an identical-math XLA fallback by platform;
``--fused_optimizer false`` restores the tree_map chain). Under
``--optimizer_sharding zero1`` the caller (``parallel/step.py``)
wraps this update in the reduce-scatter/all-gather schedule; the
moments it reads are then ``data``-sharded and the same elementwise
math partitions 1/N per replica (docs/SHARDING.md).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dml_cnn_cifar10_tpu.config import OptimConfig
from dml_cnn_cifar10_tpu.ops import kernel_paths

OptState = Dict[str, Any]


def learning_rate(cfg: OptimConfig, step: jax.Array) -> jax.Array:
    """LR schedule at ``step``.

    ``exponential`` (reference parity): ``tf.train.exponential_decay``
    staircase; faithful (dead_lr_decay) freezes the decay argument at 0 →
    constant base LR, exactly the reference's runtime behavior
    (``cifar10cnn.py:161,216``).
    ``cosine``: half-cosine from base LR to 0 over ``cosine_decay_steps``
    (the ViT/ResNet ladder standard). ``constant``: base LR.
    Any schedule composes with a linear ``warmup_steps`` ramp.
    """
    stepf = step.astype(jnp.float32)
    if cfg.schedule == "exponential":
        decay_steps = jnp.where(cfg.dead_lr_decay, 0.0, stepf)
        exponent = decay_steps / cfg.decay_every
        if cfg.staircase:
            exponent = jnp.floor(exponent)
        lr = cfg.learning_rate * cfg.lr_decay ** exponent
    elif cfg.schedule == "cosine":
        if cfg.cosine_decay_steps <= cfg.warmup_steps:
            raise ValueError(
                f"cosine schedule needs cosine_decay_steps "
                f"({cfg.cosine_decay_steps}) > warmup_steps "
                f"({cfg.warmup_steps}); otherwise the LR collapses to 0 "
                f"right after warmup")
        horizon = cfg.cosine_decay_steps - cfg.warmup_steps
        prog = jnp.clip((stepf - cfg.warmup_steps) / horizon, 0.0, 1.0)
        lr = cfg.learning_rate * 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    elif cfg.schedule == "constant":
        lr = jnp.asarray(cfg.learning_rate, jnp.float32)
    else:
        raise ValueError(f"unknown schedule {cfg.schedule!r}")
    if cfg.warmup_steps > 0:
        lr = lr * jnp.clip((stepf + 1.0) / cfg.warmup_steps, 0.0, 1.0)
    return lr


def sgd_init(params: Any, cfg: OptimConfig) -> OptState:
    """Optimizer-state init for the configured family (name kept for the
    historical sgd-only API; dispatches on ``cfg.optimizer``)."""
    state: OptState = {"step": jnp.zeros((), jnp.int32)}
    if cfg.optimizer in ("adamw", "lamb"):
        if cfg.momentum:
            raise ValueError(
                f"momentum is an SGD/LARS knob; {cfg.optimizer}'s first "
                "moment is adam_b1 — drop --momentum")
        state["mu"] = jax.tree.map(jnp.zeros_like, params)
        state["nu"] = jax.tree.map(jnp.zeros_like, params)
    elif cfg.optimizer == "lars":
        # LARS always carries momentum (paper default 0.9; our
        # cfg.momentum=0 means "use the conventional 0.9").
        state["momentum"] = jax.tree.map(jnp.zeros_like, params)
    elif cfg.optimizer == "adafactor":
        if cfg.momentum:
            raise ValueError(
                "adafactor's memory-saving mode carries no first moment "
                "(Shazeer & Stern 2018 §9) — drop --momentum")
        # Factored second moments: matrices (ndim>=2) keep only row/col
        # statistics over the trailing two dims — O(n+m) state instead
        # of Adam's O(n*m) — vectors keep the full accumulator. Three
        # parallel full-structure trees (size-0-cost () placeholders on
        # the branch a leaf doesn't use) so every optimizer family
        # checkpoints through the same pytree machinery. Under --fsdp
        # these stats stay replicated by design (shardings.state_pspecs:
        # they are sub-linear in the first place).
        state["vr"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape[:-1], jnp.float32)
            if p.ndim >= 2 else jnp.zeros((), jnp.float32), params)
        state["vc"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)
            if p.ndim >= 2 else jnp.zeros((), jnp.float32), params)
        state["v"] = jax.tree.map(
            lambda p: jnp.zeros((), jnp.float32)
            if p.ndim >= 2 else jnp.zeros(p.shape, jnp.float32), params)
    elif cfg.optimizer == "sgd":
        if cfg.momentum:
            state["momentum"] = jax.tree.map(jnp.zeros_like, params)
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    if cfg.async_staleness >= 2:
        if cfg.optimizer in ("sgd", "lars") and cfg.weight_decay:
            # SGD and LARS couple L2 decay into the gradient — a real
            # async worker would compute that term at its STALE
            # snapshot, but the update necessarily couples at the live
            # params, so the emulation would silently deviate. AdamW /
            # LAMB decay decoupled at apply time (a PS-side op in the
            # async world), which IS faithful; gradient-coupled
            # families must run wd=0 like the reference.
            raise ValueError(
                f"async_staleness with {cfg.optimizer}-coupled "
                "weight_decay would not reproduce async semantics (the "
                "L2 term would use live params); use weight_decay=0 "
                "(the reference config) or a decoupled-decay optimizer "
                "(adamw/lamb)")
        # Round-robin snapshot ring for async-PS staleness emulation
        # (config.py:async_staleness): slot t%S serves the forward pass
        # at step t and receives the post-update params.
        state["stale"] = jax.tree.map(
            lambda p: jnp.stack([p] * cfg.async_staleness), params)
    if cfg.ema_decay:
        if not 0.0 <= cfg.ema_decay < 1.0:
            raise ValueError(
                f"ema_decay must be in [0, 1) (got {cfg.ema_decay}); 1.0 "
                "would freeze the EMA at random init forever")
        # Eval-time parameter EMA, seeded at the initial params.
        state["ema"] = jax.tree.map(jnp.array, params)
    return state


def ema_decay_at(cfg: OptimConfig, t) -> jax.Array:
    """Warmup-ramped EMA decay: ``min(d, (1+t)/(10+t))`` for update count
    ``t`` — the standard schedule (optax/TF EMA) that keeps the early
    average close to the live params instead of the random init (a flat
    d=0.999 would leave ~37% init weight after 1000 steps)."""
    t = jnp.asarray(t, jnp.float32)
    return jnp.minimum(jnp.asarray(cfg.ema_decay, jnp.float32),
                       (1.0 + t) / (10.0 + t))


def _clipped(grads: Any, cfg: OptimConfig) -> Any:
    if cfg.grad_clip_norm is None:
        return grads
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, cfg.grad_clip_norm / (gnorm + 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def sgd_update(
    grads: Any, state: OptState, params: Any, cfg: OptimConfig,
    pallas_ok: Optional[bool] = None, mesh=None
) -> Tuple[Any, OptState]:
    """One optimizer step; returns (new_params, new_state).

    The step counter increments on apply, mirroring ``minimize(...,
    global_step=global_step)`` (``cifar10cnn.py:163``). SGD couples weight
    decay into the gradient (classic L2); AdamW decays decoupled, applied
    directly to the weights (Loshchilov & Hutter). ``cfg.ema_decay`` also
    tracks an eval-time parameter EMA across every family.

    ``pallas_ok=False`` vetoes the fused path's Pallas lowering (same
    math via the XLA expression): the step builders pass it when the
    update's operands are GSPMD-sharded (tp/fsdp/pipe state) — an
    opaque ``pallas_call`` there would force the partitioner to
    materialize full replicas. ``None`` resolves by platform. ``mesh``
    is the enclosing GSPMD program's mesh (the GSPMD step builders pass
    theirs; callers inside a ``shard_map`` pass none): on more than one
    device the kernel runs under a replicated ``shard_map`` over it
    (``ops/optimizer.py``).
    """
    new_params, new_state = _base_update(grads, state, params, cfg,
                                         pallas_ok=pallas_ok, mesh=mesh)
    if cfg.ema_decay:
        d = ema_decay_at(cfg, new_state["step"])
        new_state["ema"] = jax.tree.map(
            lambda e, p: (d * e + (1 - d) * p).astype(e.dtype),
            state["ema"], new_params)
    return new_params, new_state


def _base_update(
    grads: Any, state: OptState, params: Any, cfg: OptimConfig,
    pallas_ok: Optional[bool] = None, mesh=None
) -> Tuple[Any, OptState]:
    step = state["step"]
    if cfg.optimizer != "sgd" or not getattr(cfg, "fused_optimizer", True):
        kernel_paths.note("update", f"xla ({cfg.optimizer} tree_map)")
    lr = learning_rate(cfg, step)
    grads = _clipped(grads, cfg)

    if cfg.optimizer in ("adamw", "lamb"):
        t = (step + 1).astype(jnp.float32)
        b1, b2 = cfg.adam_b1, cfg.adam_b2
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                          state["mu"], grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g),
                          state["nu"], grads)
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t
        lamb = cfg.optimizer == "lamb"

        def upd(p, m, v):
            # AdamW direction; LAMB then rescales the step to the
            # weight's own norm per layer (You et al. 2019 /
            # optax.scale_by_trust_ratio semantics: ratio 1 when either
            # norm is zero).
            r = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.adam_eps) \
                + cfg.weight_decay * p
            scale = lr * _trust_ratio(p, r) if lamb else lr
            return p - (scale * r).astype(p.dtype)

        new_params = jax.tree.map(upd, params, mu, nu)
        return new_params, {"step": step + 1, "mu": mu, "nu": nu}

    if cfg.optimizer == "adafactor":
        # Shazeer & Stern 2018: scheduled decay b2_t = 1 - t^-0.8 (no
        # bias correction needed), factored rsqrt preconditioner, update
        # RMS-clipped at 1.0, relative (parameter-scale) step size,
        # decoupled weight decay like AdamW. The factored estimate
        # vr_i*vc_j/mean(vr) is EXACT whenever g^2 is rank-1
        # (test-pinned) and an upper-biased approximation otherwise.
        t = (step + 1).astype(jnp.float32)
        b2 = 1.0 - t ** -0.8
        eps1 = 1e-30

        def one(p, g, vr, vc, v):
            g = g.astype(jnp.float32)
            g2 = jnp.square(g) + eps1
            if p.ndim >= 2:
                vr = b2 * vr + (1 - b2) * jnp.mean(g2, axis=-1)
                vc = b2 * vc + (1 - b2) * jnp.mean(g2, axis=-2)
                row = vr / jnp.mean(vr, axis=-1, keepdims=True)
                # Two separate rsqrts, NOT rsqrt(row*vc): for a
                # zero-gradient row the product underflows f32 to 0
                # (~1e-28 * ~1e-30), rsqrt(0)=inf and 0*inf NaNs the
                # update; the factors individually stay normal.
                u = (g * jax.lax.rsqrt(row)[..., None]
                     * jax.lax.rsqrt(vc)[..., None, :])
            else:
                v = b2 * v + (1 - b2) * g2
                u = g * jax.lax.rsqrt(v)
            rms = jnp.sqrt(jnp.mean(jnp.square(u)))
            u = u / jnp.maximum(1.0, rms)
            # Parameter-scale multiply (the paper's relative step /
            # optax default): alpha = lr * max(RMS(p), eps2). Without it
            # the early steps are near-sign-SGD with absolute magnitude
            # lr — catastrophic for layers initialized at small scale.
            alpha = lr * jnp.maximum(
                jnp.sqrt(jnp.mean(jnp.square(p.astype(jnp.float32)))),
                1e-3)
            new_p = p - (alpha * (u + cfg.weight_decay * p)).astype(p.dtype)
            return new_p, vr, vc, v

        out = jax.tree.map(one, params, grads, state["vr"], state["vc"],
                           state["v"])
        # Structural transpose (treedef-driven): params-of-4-tuples →
        # 4-tuple-of-params-trees. An isinstance(tuple) is_leaf unzip
        # would misfire on param trees that use tuples as containers.
        new_params, vr, vc, v = jax.tree_util.tree_transpose(
            jax.tree.structure(params), jax.tree.structure((0, 0, 0, 0)),
            out)
        return new_params, {"step": step + 1, "vr": vr, "vc": vc, "v": v}

    if cfg.optimizer == "lars":
        beta = cfg.momentum or 0.9

        def local_gradient(p, g):
            # Trust-adapted gradient, optax-style convention: local LR
            # eta*||w||/(||g + wd*w|| + eps) — the decayed gradient's
            # norm, NOT the paper's ||g|| + wd*||w|| split (they differ
            # when g and w aren't parallel; test_lars_local_lr_formula
            # pins this form). 1-D leaves (biases, BN) skip the
            # adaptation, the standard practice.
            g = g + cfg.weight_decay * p
            if p.ndim <= 1:
                return g
            pn = jnp.linalg.norm(p)
            gn = jnp.linalg.norm(g)
            local = jnp.where(
                pn > 0,
                jnp.where(gn > 0,
                          cfg.lars_trust_coef * pn / (gn + cfg.lars_eps),
                          1.0),
                1.0)
            return local * g

        adapted = jax.tree.map(local_gradient, params, grads)
        mom = jax.tree.map(lambda m, g: beta * m + g,
                           state["momentum"], adapted)
        new_params = jax.tree.map(lambda p, m: p - (lr * m).astype(p.dtype),
                                  params, mom)
        return new_params, {"step": step + 1, "momentum": mom}

    new_state: OptState = {"step": step + 1}
    if getattr(cfg, "fused_optimizer", True):
        # Fused single-pass update (ops/optimizer.py): decay + momentum
        # + apply in ONE pass over the param bytes — a Pallas TPU kernel,
        # or the identical (bit-equal, PARITY.md) f32 expression as one
        # fused XLA loop on other platforms / under GSPMD-sharded
        # (zero1, fsdp, tensor-parallel) layouts. --fused_optimizer
        # false keeps the historical tree_map chain below.
        from dml_cnn_cifar10_tpu.ops import optimizer as fused_lib

        new_params, mom = fused_lib.fused_sgd_update(
            params, grads, state.get("momentum") if cfg.momentum else None,
            lr, cfg.momentum, cfg.weight_decay,
            optimizer_sharding=getattr(cfg, "optimizer_sharding", "none"),
            use_pallas=False if pallas_ok is False else None, mesh=mesh)
        if mom is not None:
            new_state["momentum"] = mom
        return new_params, new_state
    if cfg.weight_decay:
        grads = jax.tree.map(lambda g, p: g + cfg.weight_decay * p,
                             grads, params)
    if cfg.momentum:
        mom = jax.tree.map(lambda m, g: cfg.momentum * m + g,
                           state["momentum"], grads)
        new_state["momentum"] = mom
        grads = mom
    new_params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                              params, grads)
    return new_params, new_state


def _trust_ratio(p: jax.Array, u: jax.Array) -> jax.Array:
    """||p|| / ||u|| with optax's safe guards: 1 when either norm is 0."""
    pn = jnp.linalg.norm(p)
    un = jnp.linalg.norm(u)
    return jnp.where(pn > 0, jnp.where(un > 0, pn / un, 1.0), 1.0)


def as_optax(cfg: OptimConfig):
    """The configured optimizer as an optax ``GradientTransformation``.

    sgd/adamw/lamb compose to the same math as :func:`sgd_update` (LAMB is
    test-pinned to ``optax.lamb``). LARS is the closest optax composition
    — see the inline note on the lr-vs-trace ordering difference.
    ``cfg.ema_decay`` is NOT represented: the parameter EMA is eval-side
    state the driver tracks, not part of the gradient transform."""
    import optax

    def schedule(count):
        return learning_rate(cfg, count)

    clip = ([optax.clip_by_global_norm(cfg.grad_clip_norm)]
            if cfg.grad_clip_norm is not None else [])
    if cfg.optimizer == "adamw":
        return optax.chain(*clip, optax.adamw(
            schedule, b1=cfg.adam_b1, b2=cfg.adam_b2, eps=cfg.adam_eps,
            weight_decay=cfg.weight_decay))
    if cfg.optimizer == "lamb":
        return optax.chain(*clip, optax.lamb(
            schedule, b1=cfg.adam_b1, b2=cfg.adam_b2, eps=cfg.adam_eps,
            weight_decay=cfg.weight_decay))
    if cfg.optimizer == "adafactor":
        # Closest optax composition, NOT bit-identical: optax's
        # scale_by_factored_rms only factors dims >= its
        # min_dim_size_to_factor and picks the two largest dims, where
        # sgd_update always factors the trailing two of any matrix.
        return optax.chain(*clip, optax.adafactor(
            schedule, multiply_by_parameter_scale=True,
            clipping_threshold=1.0, decay_rate=0.8,
            weight_decay_rate=cfg.weight_decay or None))
    if cfg.optimizer == "lars":
        # Closest optax composition, NOT bit-identical to sgd_update's
        # LARS: optax scales by lr before the momentum trace (ours
        # after), so momentum trajectories diverge under a non-constant
        # schedule. The adaptation mask (skip 1-D leaves) and eps ARE
        # forwarded to match.
        return optax.chain(*clip, optax.lars(
            schedule, weight_decay=cfg.weight_decay,
            trust_coefficient=cfg.lars_trust_coef, eps=cfg.lars_eps,
            trust_ratio_mask=lambda params: jax.tree.map(
                lambda p: p.ndim > 1, params),
            momentum=cfg.momentum or 0.9))
    tx = clip + ([optax.trace(decay=cfg.momentum)] if cfg.momentum else [])
    if cfg.weight_decay:
        tx.append(optax.add_decayed_weights(cfg.weight_decay))
    tx.append(optax.scale_by_learning_rate(schedule))
    return optax.chain(*tx)
