"""Multi-host bootstrap over DCN.

Replaces ``tf.train.ClusterSpec`` + ``tf.train.Server`` (``cifar10cnn.py:
184-192``): instead of a gRPC parameter-server cluster there is one SPMD
program per host, bootstrapped by ``jax.distributed.initialize`` (the
coordinator fills the role of the TF master; all training traffic is XLA
collectives over ICI/DCN, not parameter RPCs).

The reference CLI shape is preserved: a comma list of ``host:port`` worker
addresses plus a task index maps 1:1 onto (coordinator_address,
num_processes, process_id) — see ``cli/main.py``.

Bootstrap is hardened two ways (docs/RESILIENCE.md):
- inputs are validated up front — a bad ``--task_index`` or a duplicated
  ``host:port`` used to surface as a late ``jax.distributed`` hang, the
  single worst failure mode to debug on a pod;
- ``initialize`` retries a refused/slow coordinator with the shared
  bounded exponential backoff (``utils/backoff.py``) under
  ``--coordinator_timeout_s`` per attempt — workers routinely win the
  race against the coordinator process on real schedulers, and losing
  that race should be a retry, not a crash.
"""

from __future__ import annotations

import time
from typing import List, Optional

import jax

from dml_cnn_cifar10_tpu.config import ParallelConfig
from dml_cnn_cifar10_tpu.utils import backoff


def validate_hosts(worker_hosts: List[str], task_index: int) -> None:
    """Fail fast with a clear ``ValueError`` on inputs that would
    otherwise hang ``jax.distributed`` late: empty/duplicate
    ``host:port`` entries, entries without a port, or a ``task_index``
    outside ``[0, len(worker_hosts))``."""
    if not worker_hosts:
        raise ValueError("worker_hosts is empty: need at least one "
                         "host:port entry")
    seen = set()
    for i, entry in enumerate(worker_hosts):
        entry = entry.strip()
        if not entry:
            raise ValueError(
                f"worker_hosts[{i}] is empty — a trailing/doubled comma "
                f"in --worker_hosts?")
        host, sep, port = entry.rpartition(":")
        if not sep or not host or not port.isdigit():
            raise ValueError(
                f"worker_hosts[{i}] = {entry!r} is not host:port")
        if entry in seen:
            raise ValueError(
                f"worker_hosts[{i}] = {entry!r} is duplicated — two "
                f"processes on one address never form a cluster, they "
                f"hang it")
        seen.add(entry)
    if not 0 <= task_index < len(worker_hosts):
        raise ValueError(
            f"task_index={task_index} out of range for "
            f"{len(worker_hosts)} worker host(s)")


def initialize_from_hosts(worker_hosts: List[str], task_index: int) -> None:
    """README-recipe compat: ``--worker_hosts=a:2222,b:2222 --task_index=i``.

    The first worker is the coordinator, exactly as task 0 is the TF chief
    (``cifar10cnn.py:222`` ``is_chief=(task_index==0)``).
    """
    validate_hosts(worker_hosts, task_index)
    initialize(ParallelConfig(
        coordinator_address=worker_hosts[0],
        num_processes=len(worker_hosts),
        process_id=task_index,
    ))


def initialize(cfg: ParallelConfig) -> None:
    """Idempotent ``jax.distributed.initialize`` from config, with
    bounded retry + backoff around a slow-to-start coordinator."""
    if cfg.num_processes <= 1:
        return
    # NB: must not touch jax.process_count() here — it initializes the XLA
    # backend, after which jax.distributed.initialize refuses to run.
    if jax.distributed.is_initialized():
        return
    attempt = 0
    while True:
        try:
            jax.distributed.initialize(
                coordinator_address=cfg.coordinator_address,
                num_processes=cfg.num_processes,
                process_id=cfg.process_id,
                initialization_timeout=int(cfg.coordinator_timeout_s),
            )
            return
        except (RuntimeError, ConnectionError, OSError, TimeoutError) as e:
            attempt += 1
            if attempt > cfg.coordinator_retries:
                raise RuntimeError(
                    f"coordinator {cfg.coordinator_address} unreachable "
                    f"after {attempt} attempt(s) x "
                    f"{cfg.coordinator_timeout_s:.0f}s: {e}") from e
            delay = backoff.delay_s(1.0, 30.0, attempt)
            print(f"[multihost] coordinator {cfg.coordinator_address} "
                  f"not ready (attempt {attempt}/"
                  f"{cfg.coordinator_retries}): {e}; retrying in "
                  f"{delay:.1f}s")
            time.sleep(delay)


def is_chief(cfg: Optional[ParallelConfig] = None) -> bool:
    """Process 0 plays the chief role (init/checkpointing decisions).

    With a :class:`ParallelConfig` that declares a multi-process world
    (``num_processes > 1``), chiefness comes from ``cfg.process_id`` —
    this is what the cluster-resilience CPU simulation relies on, where
    every simulated host is ``jax.process_index() == 0`` in its own
    single-process JAX world. Without one, the live JAX process index
    decides, as before."""
    if cfg is not None and cfg.num_processes > 1:
        return cfg.process_id == 0
    return jax.process_index() == 0
