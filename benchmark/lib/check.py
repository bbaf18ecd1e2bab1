"""The comparison that decides ``correct``: what the program's first
dispatch made of the benchmark's weights, against what the plain
reference makes of them over the same K steps.

Each number is a relative gap, and has its own limit in the cell's file
of limits:

- ``loss``: the loss of the dispatch's last step, where the program
  logged it (a dispatch that ends on a metrics boundary).
- ``dparam`` / ``dparam_mid``: the norm of each parameter leaf's change
  over the K steps: the gap between the program's norm and the
  reference's (not the norm of their difference), against the
  reference's norm of that leaf or of the median leaf, whichever is
  larger; the worst leaf, and the median leaf.
- ``dstate``, ``moment``: the same two readings' worst leaf for the
  model's running statistics and for the optimizer's momentum trace,
  where the configuration has them.
- ``dstate_mid``, ``moment_mid``: their median leaf.
- ``ddiff``, ``sdiff``, ``mdiff`` (and each ``_mid``): for the same three
  trees the norm of the DIFFERENCE between the program's
  change and the reference's, against the same denominator; worst and
  median leaf. A gap of norms moves only in the second order under
  rounding that points anywhere (a perturbation at right angles to a
  vector does not lengthen it), so it cannot tell one precision from the
  next; the norm of the difference moves in the first order.
- every other tree of the optimizer's state that both sides hold reads
  the same four numbers under its own name: for AdamW's ``nu``, ``nu``,
  ``nu_mid``, ``nu_diff``, ``nu_diff_mid``.

A leaf whose first gradient in the reference is under a thousandth of the
median leaf's is left out of the parameters' and the momentum's numbers:
its change is round-off on both sides.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np


def _leaves(tree) -> List[Tuple[str, np.ndarray]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), np.asarray(x, np.float64))
            for p, x in flat]


def _norms(after, before=None) -> Dict[str, float]:
    a = dict(_leaves(after))
    b = dict(_leaves(before)) if before is not None else {}
    return {k: float(np.linalg.norm(v - b[k] if b else v))
            for k, v in a.items()}


def norm_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep=None) -> Dict[str, float]:
    """Per leaf: |program's norm - reference's norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    if set(prog) != set(ref):
        raise ValueError(f"trees differ: {sorted(set(prog) ^ set(ref))}")
    names = [k for k in ref if keep is None or k in keep]
    if not names:
        raise ValueError("no leaf to compare")
    median = float(np.median([ref[k] for k in names]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], median, 1e-300)
            for k in names}


def diff_norms(prog, ref, start=None, keep=None) -> Dict[str, float]:
    """Per leaf: the norm of (program's change - reference's change) over
    the larger of the reference's norm of that leaf's change and of the
    median leaf's."""
    a, b = dict(_leaves(prog)), dict(_leaves(ref))
    if set(a) != set(b):
        raise ValueError(f"trees differ: {sorted(set(a) ^ set(b))}")
    s = dict(_leaves(start)) if start is not None else {}
    names = [k for k in b if keep is None or k in keep]
    if not names:
        raise ValueError("no leaf to compare")
    size = {k: float(np.linalg.norm(b[k] - s[k] if s else b[k]))
            for k in names}
    median = float(np.median(list(size.values())))
    return {k: float(np.linalg.norm(a[k] - b[k]))
            / max(size[k], median, 1e-300) for k in names}


def _worst_and_mid(out: Dict[str, float], name: str,
                   per_leaf: Dict[str, float]) -> None:
    out[name] = max(per_leaf.values())
    out[name + "_mid"] = float(np.median(list(per_leaf.values())))


def compare(first, start_params, start_state, ref) -> Dict[str, float]:
    """``first`` is the program's ``FirstDispatch`` (host arrays), ``ref``
    the reference's ``ChunkResult`` from the same start."""
    out: Dict[str, float] = {}
    if first.loss is not None:
        ref_loss = float(np.asarray(ref.losses)[-1])
        out["loss"] = abs(first.loss - ref_loss) / max(abs(ref_loss), 1e-300)

    grad = {k: float(v) for k, v in _leaves(ref.first_grad_norms)}
    floor = 1e-3 * float(np.median(list(grad.values())))
    moved = {k for k, g in grad.items() if g >= floor}
    _worst_and_mid(out, "dparam", norm_gaps(
        _norms(first.params, start_params), _norms(ref.params, start_params),
        keep=moved))
    _worst_and_mid(out, "ddiff", diff_norms(first.params, ref.params,
                                            start_params, keep=moved))
    if _leaves(ref.model_state):
        _worst_and_mid(out, "dstate", norm_gaps(
            _norms(first.model_state, start_state),
            _norms(ref.model_state, start_state)))
        _worst_and_mid(out, "sdiff", diff_norms(
            first.model_state, ref.model_state, start_state))
    for name in sorted(set(ref.opt) & set(first.opt)):
        gap, diff = ("moment", "mdiff") if name == "momentum" \
            else (name, name + "_diff")
        theirs, mine = _norms(first.opt[name]), _norms(ref.opt[name])
        # a tree shaped like the parameters leaves out the leaves they do
        keep = moved if set(mine) == set(grad) else None
        _worst_and_mid(out, gap, norm_gaps(theirs, mine, keep=keep))
        _worst_and_mid(out, diff, diff_norms(first.opt[name], ref.opt[name],
                                             keep=keep))
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, List[float]]]:
    """``correct`` and, for the result line, each number compared beside
    its limit. A number that is not finite fails; a limit without a
    number fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        if value is None or not np.isfinite(value):
            compared[name], ok = [None, limit], False   # JSON has no NaN
        else:
            compared[name] = [float(value), limit]
            ok = ok and value <= limit
    return bool(ok), compared
