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

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dml_cnn_cifar10_tpu.ops import kernel_paths, sum_rows
from dml_cnn_cifar10_tpu.ops.layers import grouped_matmul, rms_norm

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


# --- one chip's share of an expert layer that drops nothing ------------------

def route_top_k(x: jax.Array, router: jax.Array, bias, top_k: int,
                norm_topk: bool = True, scaling: float = 1.0,
                score: str = "sigmoid", with_scores: bool = False):
    """Routing over ALL of the layer's experts: ``x [T, D]``, ``router [D,
    E_all]`` -> ``(chosen [T, k] int32, weights [T, k])``, and ``s`` after
    them where ``with_scores``. ``s = sigmoid(x
    router)``, each expert's score alone, or with ``score="softmax"`` ``s =
    softmax(x router)`` over all ``E_all`` logits; a token takes the ``k``
    experts with the largest ``s + bias`` (``bias [E_all]`` or None: it
    decides the choice and nothing else, and no gradient reaches it); its
    weights are the chosen experts' ``s``, where ``norm_topk`` over their
    sum (plus 1e-6 for sigmoid scores, whose sum has no floor; a softmax's
    ``k`` largest of ``E_all`` sum to ``k / E_all`` at least and take
    none), times ``scaling``. All float32, the product at the highest
    precision: the choice is a function of these numbers alone, so a
    recomputation chooses as the forward pass did."""
    if score not in ("sigmoid", "softmax"):
        raise ValueError(f"router score {score!r} is not sigmoid or softmax")
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    ranked = s if bias is None else s + bias.astype(jnp.float32)
    _, chosen = lax.top_k(lax.stop_gradient(ranked), top_k)
    # the chosen experts' scores, picked out by comparison: a gather of
    # single numbers, and the scatter-add that is its gradient, cost the
    # TPU 7 ns a number (PERF.md, Findings, PR 33)
    picked = chosen[..., None] == jnp.arange(s.shape[-1])
    weights = jnp.sum(jnp.where(picked, s[:, None, :], 0.0), -1)
    if norm_topk:
        total = jnp.sum(weights, -1, keepdims=True)
        weights = weights / (total + 1e-6 if score == "sigmoid" else total)
    if with_scores:
        return chosen, weights * scaling, s
    return chosen, weights * scaling


def sequence_balance_loss(s: jax.Array, chosen: jax.Array, sequences: int,
                          alpha: float) -> jax.Array:
    """DeepSeek-V2's sequence-wise balance loss (arXiv:2405.04434 §2.2.3,
    ``seq_aux``) of one expert layer: ``s [T, E_all]`` the router's scores
    of ``sequences`` sequences of ``T / sequences`` tokens each, in order,
    ``chosen [T, k]`` each token's experts. ``alpha mean_b sum_i f_bi
    P_bi`` with ``f_bi = E_all / (k S)`` times the slots of sequence ``b``
    on expert ``i`` (no gradient) and ``P_bi`` its mean score of expert
    ``i``: 1 alpha under an even load. Over ALL of the router's experts,
    held here or not: every chip that holds a share of the layer computes
    the same number from the same router."""
    t, e_all = s.shape
    k = chosen.shape[-1]
    per = t // sequences
    slots = chosen.reshape(sequences, per * k)
    counts = jnp.sum(slots[..., None] == jnp.arange(e_all), axis=1,
                     dtype=jnp.float32)
    f = counts * (e_all / (k * per))
    p = jnp.mean(s.reshape(sequences, per, e_all), axis=1)
    return alpha * jnp.mean(jnp.sum(f * p, -1))


@jax.custom_vjp
def _moved(values, to, back):
    """``values [N]`` with ``values[i]`` moved to place ``to[i]``: ``out[j]
    = values[back[j]]``, ``to`` and ``back`` a permutation and its inverse.
    By a sort on ``to``, forward, and on ``back`` for the gradient: a
    gather or a scatter-add of single numbers costs the TPU several times
    a sort of as many."""
    return lax.sort((to, values), num_keys=1)[1]


_moved.defvjp(lambda values, to, back: (_moved(values, to, back), (to, back)),
              lambda res, g: (_moved(g, res[1], res[0]), None, None))


def _gated_rows(rows, w1, w3, w2, sizes, dtype, mesh):
    """The experts' gated SiLU MLP on ``rows [R, D]`` in the order of their
    experts, ``sizes [E]`` of them to each expert from the front."""
    hidden = jax.nn.silu(grouped_matmul(rows, w1, sizes, dtype, mesh)) \
        * grouped_matmul(rows, w3, sizes, dtype, mesh)
    return grouped_matmul(hidden, w2, sizes, dtype, mesh)


def _block(blocks_in, j):
    return tuple(lax.dynamic_index_in_dim(a, j, 0, keepdims=False)
                 for a in blocks_in)


def buffer_rounds(here, held: int, blocks: int, rows: int):
    """Times a buffer of ``held`` of the ``blocks`` blocks of ``rows`` rows
    is filled to take ``here`` rows in, at least once; the integer 1 where
    it holds them all."""
    if held >= blocks:
        return 1
    return jnp.maximum(1, (here + held * rows - 1) // (held * rows))


def _in_rounds(rounds, one_round, carry):
    """``one_round(r, carry) -> (what round r adds to each token, carry)``
    for ``r = 0 .. rounds - 1`` -> ``(the rounds' sum, carry)``. The first
    round runs whatever ``rounds`` says and its sum is taken as it comes:
    the one round of an even load adds nothing to anything."""
    total, carry = one_round(0, carry)
    if isinstance(rounds, int):     # the buffer holds every block: 1
        return total, carry

    def another(r, state):
        total, carry = state
        more, carry = one_round(r, carry)
        return total + more, carry

    return lax.fori_loop(1, rounds, another, (total, carry))


def _round_of_blocks(static, rows, here, r, store_block, carry):
    """Round ``r`` of the buffer: ``store_block(j, at, (buffer, *carry))``
    for each block ``j`` of the round that holds a row, ``at`` the block's
    first row in the buffer -> ``(buffer, *carry)``, the rows' range ``lo,
    hi`` in the experts' order."""
    _, _, held, row, _ = static
    room = held * rows
    # never cleared: a row is stored before the range that holds it is read
    buffer = lax.empty((room, *row), jnp.float32)
    state = lax.fori_loop(
        r * held, jnp.minimum((here + rows - 1) // rows, (r + 1) * held),
        lambda j, state: store_block(j, (j - r * held) * rows, state),
        (buffer, *carry))
    return state, r * room, jnp.minimum(here, (r + 1) * room)


def _store(buffer, block, at):
    """``block [rows, D]`` into the buffer's rows from ``at``, in the shape
    the buffer holds a row in."""
    return lax.dynamic_update_slice_in_dim(
        buffer, block.reshape(block.shape[0], *buffer.shape[1:]), at, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _expert_blocks(static, m, w1, w3, w2, here, blocks_in, pos):
    """What the rows routed here add to their tokens: ``m [T, D]`` (in the
    products' dtype) -> ``[T, D]`` float32. ``blocks_in`` holds, a block of
    rows a leading index, each row's slot (token * top_k + choice; the
    rows in the order of their experts), how many rows each expert takes
    from the block's front, and each row's weight (0 past the ``here``
    rows); ``pos [T, top_k]`` each slot's row in that order. The blocks
    that hold a row are visited one after the other (a loop of as many
    rounds as ``here`` needs: an empty block costs nothing), each gathering
    its rows, forming the three products and storing the weighted results
    at its place in a buffer of ``held`` blocks; when the buffer is full
    or the rows are at an end each token fetches and sums the rows it has
    there (``ops.sum_rows``), and a load that exceeds the buffer fills it
    again. The backward pass is written out as a second such loop that
    forms a block again, takes its gradient and sends the rows' gradient
    to their tokens the same way, so that one block's rows and hidden
    activations are held at a time in both passes, whatever the worst case
    is. ``static``: ``(top_k, dtype, held, the shape of a buffer's row,
    the enclosing program's mesh or None)``."""
    top_k, dtype, held, _, mesh = static
    rows = blocks_in[0].shape[1]

    def store_block(j, at, state):
        buffer, = state
        slot, sizes, weight = _block(blocks_in, j)
        with jax.named_scope("dispatch"):
            taken = m[slot // top_k]
        with jax.named_scope("experts"):
            out = _gated_rows(taken, w1, w3, w2, sizes, dtype, mesh)
        with jax.named_scope("combine"):
            # (rows past the groups' sum belong to no expert: zero, weight 0)
            return _store(buffer, out * weight[:, None], at),

    def one_round(r, carry):
        (buffer,), lo, hi = _round_of_blocks(static, rows, here, r,
                                             store_block, carry)
        with jax.named_scope("combine"):
            return sum_rows.sum_rows_by_token(buffer, pos, lo, hi), carry

    return _in_rounds(buffer_rounds(here, held, *blocks_in[0].shape),
                      one_round, ())[0]


def _expert_blocks_fwd(static, m, w1, w3, w2, here, blocks_in, pos):
    return _expert_blocks(static, m, w1, w3, w2, here, blocks_in, pos), \
        (m, w1, w3, w2, here, blocks_in, pos)


def _expert_blocks_bwd(static, res, g):
    top_k, dtype, held, _, mesh = static
    m, w1, w3, w2, here, blocks_in, pos = res
    rows = blocks_in[0].shape[1]

    def store_block(j, at, state):
        buffer, dws, dweight_of = state
        slot, sizes, weight = _block(blocks_in, j)
        with jax.named_scope("dispatch"):
            token = slot // top_k
            taken = m[token]
        with jax.named_scope("combine"):
            g_rows = g[token]
        with jax.named_scope("experts"):
            out, vjp = jax.vjp(
                lambda taken, w1, w3, w2: _gated_rows(taken, w1, w3, w2,
                                                      sizes, dtype, mesh),
                taken, w1, w3, w2)
            dtaken, *dw = vjp(g_rows * weight[:, None])
            dws = jax.tree.map(jnp.add, dws, tuple(dw))
        with jax.named_scope("combine"):
            dweight_of = lax.dynamic_update_index_in_dim(
                dweight_of, jnp.sum(out * g_rows, -1), j, 0)
        with jax.named_scope("dispatch"):
            buffer = _store(buffer, dtaken.astype(jnp.float32), at)
        return buffer, dws, dweight_of

    def one_round(r, carry):
        (buffer, *carry), lo, hi = _round_of_blocks(static, rows, here, r,
                                                    store_block, carry)
        with jax.named_scope("dispatch"):
            return sum_rows.sum_rows_by_token(buffer, pos, lo, hi), tuple(carry)

    dm, (dws, dweight_of) = _in_rounds(
        buffer_rounds(here, held, *blocks_in[0].shape), one_round,
        (tuple(jnp.zeros_like(w) for w in (w1, w3, w2)),
         jnp.zeros_like(blocks_in[2])))
    return (dm.astype(m.dtype), *dws, None, (None, None, dweight_of), None)


_expert_blocks.defvjp(_expert_blocks_fwd, _expert_blocks_bwd)


def balanced_bias(bias: jax.Array, load: jax.Array, rate: float
                  ) -> jax.Array:
    """The experts' bias after one step of balancing without a loss term:
    up by ``rate`` for an expert that got fewer slots than the mean over
    all of the router's experts (``load [E_all]``), down by ``rate`` for
    one that got more. It moves the choice of experts toward an even load
    and touches nothing else (:func:`route_top_k`)."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def routed_experts(x: jax.Array, params: Params, *, first_expert: int,
                   top_k: int, dtype, bias=None, norm_topk: bool = True,
                   scaling: float = 1.0, block_rows: int | None = None,
                   norm_scale=None, norm_eps: float = 1e-5, mesh=None,
                   score: str = "sigmoid", shared=None,
                   balance_alpha: float = 0.0, sequences: int = 1
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """What the experts held here add to every token: ``x [T, D]``
    (float32) -> ``([T, D] float32, stats)``.

    ``params``: ``router [D, E_all]`` over all of the layer's experts and
    the ``E`` experts with the contiguous ids ``first_expert ..
    first_expert + E - 1``, expert-major: ``w1``, ``w3`` ``[E, D, H]``,
    ``w2`` ``[E, H, D]``, each a gated SiLU MLP ``(silu(m w1) * (m w3))
    w2``; ``bias [E_all]`` (a buffer, not a parameter) or None. With
    ``norm_scale`` the router and the experts read ``m = rms(x;
    norm_scale)`` (the layer's norm, formed again in the backward pass, so
    that ``x`` alone is kept), else ``x``. Every token is routed over all
    ``E_all`` experts (:func:`route_top_k`, by sigmoid scores or, ``score``,
    a softmax's); of its ``top_k`` slots those
    whose expert lives here are kept, put in the order of their experts,
    and the three products run over exactly those rows
    (``ops.layers.grouped_matmul``); each row's result, times its weight,
    is added back to its token. A token none of whose experts is here
    gets zero. **No token is dropped and there is no capacity**: the rows
    are taken ``block_rows`` at a time (all ``T * top_k`` where None), one
    block after the other, as many as hold a row when the program runs:
    the worst case costs no memory beyond a block's, and neither an empty
    block nor the padding of the last one costs a product. The blocks'
    results are not scattered to their tokens: they are stored, in the
    experts' order, in a float32 buffer of as many blocks as the load
    takes when every expert of the router gets the same (``T * top_k * E /
    E_all`` rows), and each token then fetches the rows it has there and
    sums them in the order of its choices (``ops.sum_rows``; ``mesh``, the
    mesh of the enclosing GSPMD program if any, is read only to decide
    between that op's kernel and its XLA expression, and so between the
    grouped products' kernels and ``lax.ragged_dot``). **A round** is one
    filling of that buffer with the sum by token that empties it. A load
    within the buffer takes one; a load that exceeds it, however skewed
    the routing, takes another round for each further bufferful: a pass
    more over the tokens, and no more memory. On one chip nothing is
    exchanged; what the absent experts would add is left out.

    ``shared``, where given, is a function of ``m [T, D]`` that every token
    takes (shared experts: every chip computes them for its own tokens),
    added to the routed part under scope ``shared``. ``balance_alpha`` > 0
    adds :func:`sequence_balance_loss` over ``sequences`` sequences of the
    ``T`` tokens, from the router's own scores.

    ``stats``: ``rows_here_frac``, the slots on experts held here over ``T
    * top_k``; ``load_max_over_mean``, the fullest held expert's rows
    over the mean's; ``buffer_rounds``, the rounds the layer took (1 when
    the load is within the buffer); ``expert_load [E_all]``, the slots
    each of the router's experts got, held here or not
    (:func:`balanced_bias`); none of them with a gradient. And, with
    ``balance_alpha``, ``balance_loss``: the loss term, WITH its
    gradient, for the caller to add to its loss."""
    t, d = x.shape
    e, e_all = params["w1"].shape[0], params["router"].shape[1]
    slots = t * top_k
    rows = min(block_rows or slots, slots)
    blocks = -(-slots // rows)
    # blocks of the buffer: the load when every expert gets the same
    held = max(1, min(blocks, -(-(slots * e) // (e_all * rows))))

    @jax.checkpoint
    def normed(x, scale):
        with jax.named_scope("ffn_norm"):
            return x if scale is None else rms_norm(x, scale, norm_eps)

    @jax.checkpoint
    def route(m, router, bias):
        with jax.named_scope("route"):
            return route_top_k(m, router, bias, top_k, norm_topk, scaling,
                               score, with_scores=balance_alpha > 0)

    m = normed(x, norm_scale)
    chosen, weights, *scores = route(m, params["router"], bias)
    if scores:
        with jax.named_scope("route"):
            balance = sequence_balance_loss(scores[0], chosen, sequences,
                                            balance_alpha)
    with jax.named_scope("dispatch"):
        local = chosen.reshape(slots) - first_expert
        local = jnp.where((local >= 0) & (local < e), local, e)
        # slots in the order of their experts, those of absent experts last
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        counts = jnp.sum(local[:, None] == jnp.arange(e)[None, :], axis=0,
                         dtype=jnp.int32)
        ends = jnp.cumsum(counts)
        here = ends[-1]
        lows = jnp.arange(blocks, dtype=jnp.int32) * rows
        slot_of = jnp.pad(order, (0, blocks * rows - slots)).reshape(
            blocks, rows)
        # of each expert's rows, those that fall into each block
        sizes_of = jnp.clip(ends[None, :], lows[:, None],
                            lows[:, None] + rows) \
            - jnp.clip((ends - counts)[None, :], lows[:, None],
                       lows[:, None] + rows)
        # each slot's row in the experts' order: the inverse of `order`
        pos = jnp.argsort(order).astype(jnp.int32)
        weight_of = jnp.where(
            lows[:, None] + jnp.arange(rows)[None, :] < here,
            jnp.pad(_moved(weights.reshape(slots), pos, order),
                    (0, blocks * rows - slots)).reshape(blocks, rows), 0.0)
        pos = pos.reshape(t, top_k)
    y = _expert_blocks(
        (top_k, jnp.dtype(dtype), held, sum_rows.row_shape(t, top_k, d, mesh),
         mesh),
        m.astype(dtype), params["w1"], params["w3"], params["w2"], here,
        (slot_of, sizes_of, weight_of), pos)
    if shared is not None:
        with jax.named_scope("shared"):
            y = y + shared(m)
    # the step's line: how the grouped products ran, then how the rows
    # reached their tokens (each chooser noted its own)
    kernel_paths.note("experts", f"{kernel_paths.noted('grouped')}, "
                                 f"{kernel_paths.noted('experts')}")
    mean = jnp.maximum(here, 1).astype(jnp.float32) / e
    stats = {"rows_here_frac": here.astype(jnp.float32) / slots,
             "load_max_over_mean": jnp.max(counts).astype(jnp.float32) / mean,
             "buffer_rounds": jnp.float32(
                 buffer_rounds(here, held, blocks, rows)),
             "expert_load": jnp.sum(
                 chosen.reshape(slots)[:, None] == jnp.arange(e_all)[None, :],
                 axis=0, dtype=jnp.int32)}
    stats = jax.tree.map(lax.stop_gradient, stats)
    if scores:
        stats["balance_loss"] = balance
    return y, stats
