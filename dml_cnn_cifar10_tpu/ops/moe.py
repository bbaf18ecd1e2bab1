"""Mixture-of-Experts MLP — expert parallelism over the ``model`` mesh axis.

No reference counterpart (SURVEY §2.3: expert parallelism absent), built
TPU-first as the framework's ``ep`` capability:

- **Switch-style top-1 / GShard-style top-2 routing** with a **static
  capacity**: every shape is known at trace time (tokens = B*S, capacity =
  ceil(T/E · factor · k)), so the whole layer is dense einsums XLA can
  tile onto the MXU — no dynamic gather/scatter, no data-dependent shapes
  (the TPU-idiomatic formulation from the Switch/GShard line of work).
- **Dispatch/combine as one-hot einsum contractions**: routing becomes
  ``[T,E,C]`` tensors contracted against tokens. With the expert-major
  weights (``w1 [E,D,H]``, ``w2 [E,H,D]``) sharded over ``model`` on the
  leading expert dim (parallel/shardings.py), GSPMD compiles the dispatch
  contraction into the all-to-all over ICI — expert parallelism falls out
  of the sharding annotation, exactly like tp/sp elsewhere in this repo.
- **Load-balancing aux loss** (Switch eq. 4): E · Σ_e f_e·p_e, where f_e is
  the routed-token fraction and p_e the mean router probability. Scaled by
  the caller (``ModelConfig.moe_aux_coef``).

Tokens that overflow an expert's capacity are dropped (combine weight 0);
with the residual connection around the layer they pass through unchanged.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def init_moe_params(key: jax.Array, dim: int, hidden: int, num_experts: int,
                    dtype=jnp.float32) -> Params:
    """Expert-major MoE MLP params: gate [D,E], w1 [E,D,H], w2 [E,H,D]."""
    kg, k1, k2 = jax.random.split(key, 3)
    scale1 = math.sqrt(2.0 / dim)
    scale2 = math.sqrt(2.0 / hidden)
    return {
        "gate": {"kernel": 0.02 * jax.random.normal(kg, (dim, num_experts),
                                                    dtype)},
        "w1": scale1 * jax.random.normal(k1, (num_experts, dim, hidden),
                                         dtype),
        "b1": jnp.zeros((num_experts, hidden), dtype),
        "w2": scale2 * jax.random.normal(k2, (num_experts, hidden, dim),
                                         dtype),
        "b2": jnp.zeros((num_experts, dim), dtype),
    }


def moe_mlp(x: jax.Array, params: Params, capacity_factor: float,
            top_k: int = 1, dispatch: str = "einsum"
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Top-k MoE MLP: ``[B,S,D] -> ([B,S,D], router stats dict)``.

    ``top_k=1`` is Switch routing (output scaled by the router prob p1);
    ``top_k=2`` is GShard routing (two experts per token, combine weights
    p_i renormalized over the chosen pair). All shapes static; the expert
    dim of every einsum below is the sharded (``model``) axis under
    expert parallelism. First-choice assignments take queue priority over
    second choices, so under capacity pressure a token loses its backup
    expert before anyone loses their primary.

    ``dispatch`` selects the dispatch/combine formulation — identical
    semantics (tests pin them bit-comparable), different cost shape:

    - ``"einsum"`` (default): [T,E,C] one-hot contractions — all-MXU,
      no scatter/gather, but O(T·E·C·D) flops; at capacity ≈ T/E·f the
      dispatch pair costs O(T²·f·D), dwarfing the expert MLPs at long T
      (the ratio is not measured on the current chip).
    - ``"scatter"``: tokens scatter-add into the [E,C,D] expert buffer
      by (expert, queue-slot) index and gather back — O(T·D) data
      movement, no quadratic term; rides XLA's TPU scatter/gather.

    The stats dict carries the router's health for the metrics stream
    (round-4 verdict #1 — no capability without a number):

    - ``aux_loss``  — load-balance loss (differentiable; the ONLY entry
      gradients flow through — the caller scales it into the train loss);
    - ``dropped_frac`` — fraction of the T*k expert assignments that
      overflowed a capacity queue this batch (those tokens ride the
      residual unchanged);
    - ``expert_load`` — [E] fraction of first-choice assignments routed
      to each expert (uniform = 1/E; a collapsed router shows a spike).
    """
    b, s, d = x.shape
    e = params["w1"].shape[0]
    if not 1 <= top_k <= e:
        raise ValueError(f"top_k={top_k} must be in [1, num_experts={e}]")
    t = b * s
    capacity = max(1, math.ceil(t / e * capacity_factor * top_k))

    tokens = x.reshape(t, d)
    gate_logits = tokens.astype(jnp.float32) @ \
        params["gate"]["kernel"].astype(jnp.float32)          # [T,E]
    probs = jax.nn.softmax(gate_logits, axis=-1)

    # Rank the k chosen experts per token (sequential masked argmax —
    # k is tiny and static, so this unrolls into k dense passes).
    masked = probs
    ranks = []                                                # [(1h, prob)]
    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=-1)                     # [T]
        oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)        # [T,E]
        ranks.append((oh, jnp.sum(masked * oh, axis=-1)))     # prob at idx
        masked = masked * (1.0 - oh)
    # Switch keeps the raw p1 scale; GShard renormalizes over the pair.
    renorm = sum(p for _, p in ranks) if top_k > 1 else \
        jnp.ones((t,), jnp.float32)

    cdt = x.dtype
    if dispatch == "scatter":
        # Per-token (expert, queue-slot) coordinates — same queue
        # semantics as the one-hot path (cumsum order = token order,
        # prior ranks' FULL counts offset later ranks' slots).
        offset = jnp.zeros((e,), jnp.int32)
        coords = []                         # [(expert, slot, keep, w)]
        for oh, prob in ranks:
            ohi = oh.astype(jnp.int32)
            idx = jnp.argmax(ohi, axis=-1)                     # [T]
            pos = jnp.cumsum(ohi, axis=0) - 1 + offset[None, :]
            pos_i = jnp.take_along_axis(pos, idx[:, None], 1)[:, 0]
            keep_i = pos_i < capacity
            coords.append((idx, jnp.clip(pos_i, 0, capacity - 1),
                           keep_i, prob / jnp.maximum(renorm, 1e-9)))
            offset = offset + jnp.sum(ohi, axis=0)
        xe = jnp.zeros((e, capacity, d), cdt)
        for idx, slot, keep_i, _ in coords:
            # Kept slots are unique; dropped tokens clip onto slot C-1,
            # so they contribute ZERO via the mask and .add (not .set)
            # keeps collisions harmless.
            # Round-5 negative result: replacing this scatter-add with a
            # stable-argsort + [E,C] masked GATHER build measured 2.5x
            # faster in a standalone layer microbench (8.6 -> 3.4 ms
            # fwd+bwd at T=16k) but end-to-end vit_moe throughput was
            # parity-to-worse (6,406 vs 6,677 img/s) — the full step is
            # bound elsewhere once the einsum dispatch is gone. Kept the
            # simpler form; don't retry without a step-level profile
            # showing this op on top.
            xe = xe.at[idx, slot].add(
                tokens * keep_i[:, None].astype(cdt))
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", xe, params["w1"])
                        + params["b1"][:, None, :])
        ye = jnp.einsum("ech,ehd->ecd", h, params["w2"]) \
            + params["b2"][:, None, :]                         # [E,C,D]
        y = jnp.zeros((t, d), cdt)
        kept_total = jnp.zeros((), jnp.float32)
        for idx, slot, keep_i, w in coords:
            y = y + ye[idx, slot] * (w * keep_i)[:, None].astype(cdt)
            kept_total = kept_total + jnp.sum(keep_i)
        dropped = 1.0 - kept_total / float(t * top_k)
    elif dispatch == "einsum":
        disp = jnp.zeros((t, e, capacity), jnp.float32)
        combine = jnp.zeros((t, e, capacity), jnp.float32)
        offset = jnp.zeros((e,), jnp.float32)  # queue slots of prior ranks
        for oh, prob in ranks:
            position = (jnp.cumsum(oh, axis=0) - 1.0 + offset[None, :]) * oh
            keep = (oh > 0) & (position < capacity)
            pos_1h = jax.nn.one_hot(position.astype(jnp.int32), capacity,
                                    dtype=jnp.float32) * keep[..., None]
            disp = disp + pos_1h
            combine = combine + pos_1h * (prob / jnp.maximum(renorm, 1e-9)
                                          )[:, None, None]
            offset = offset + jnp.sum(oh, axis=0)

        xe = jnp.einsum("tec,td->ecd", disp.astype(cdt), tokens)  # [E,C,D]
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", xe, params["w1"])
                        + params["b1"][:, None, :])
        ye = jnp.einsum("ech,ehd->ecd", h, params["w2"]) \
            + params["b2"][:, None, :]                             # [E,C,D]
        y = jnp.einsum("tec,ecd->td", combine.astype(cdt), ye)     # [T,D]
        dropped = 1.0 - jnp.sum(disp) / float(t * top_k)
    else:
        raise ValueError(
            f"dispatch must be 'einsum' or 'scatter', got {dispatch!r}")

    # Load-balance loss on FIRST choices (Switch eq. 4 / GShard l_aux):
    # E * sum_e f_e * p_e.
    f = jnp.mean(ranks[0][0], axis=0)                          # [E]
    p = jnp.mean(probs, axis=0)                                # [E]
    aux = e * jnp.sum(f * p)
    stats = {
        "aux_loss": aux,
        "dropped_frac": jax.lax.stop_gradient(
            dropped.astype(jnp.float32)),
        "expert_load": jax.lax.stop_gradient(f),
    }
    return y.reshape(b, s, d), stats
