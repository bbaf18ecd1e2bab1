"""Which implementation a traced program took, reported from the place
that chose it.

The fused optimizer update (``ops/optimizer.py``), the attention
dispatch (``ops/attention.py``), the CNN's bias + ReLU + pool
(``ops/relu_pool.py``), the experts' grouped products
(``ops/layers.grouped_matmul``) and their sum of rows by token
(``ops/sum_rows.py``) each pick between a compiled Pallas kernel and
an XLA expression from what they can see at trace time: the platform,
the mesh, the token count, the shape. A step builder
(``parallel/step.py``) opens :func:`recording` around its step body;
the choosers :func:`note` their pick; the builder prints the record
once. The recorder is thread-local and only live inside ``recording``,
so untraced callers pay a dict lookup and nothing is kept.
"""

from __future__ import annotations

import contextlib
import threading

_local = threading.local()


@contextlib.contextmanager
def recording():
    """Collect the notes made while tracing the body → ``{kind: path}``."""
    prev = getattr(_local, "rec", None)
    _local.rec = rec = {}
    try:
        yield rec
    finally:
        _local.rec = prev


def note(kind: str, path: str) -> None:
    """Record that ``kind`` ("update" | "attention" | "pool" | "remat" |
    "grouped" | "experts") compiled ``path``."""
    rec = getattr(_local, "rec", None)
    if rec is not None:
        rec[kind] = path


def noted(kind: str):
    """What :func:`note` last recorded for ``kind`` in the live recording,
    or None: for a caller that adds to a chooser's note what only it knows
    (a model: in how many of its layers)."""
    rec = getattr(_local, "rec", None)
    return None if rec is None else rec.get(kind)
