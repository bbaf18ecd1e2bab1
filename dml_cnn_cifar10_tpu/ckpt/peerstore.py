"""Peer-redundant shard replicas — the diskless-recovery transport.

Every recovery path in the repo funnels through disk checkpoints: the
supervisor's restart restores the newest verifiable checkpoint, elastic
shrink/expand pick a restore step from the same archive, and at pod
scale that walk is storage-bound even with the sharded codec's
concurrent IO. This module keeps a **cold replica of each host's shard
payload on a peer**, so an elastic restart can rebuild the lost host's
state from a surviving peer's copy — zero checkpoint reads — and fall
back to disk (unchanged behavior) only when a replica is missing, stale,
or corrupt.

Protocol (docs/RESILIENCE.md, diskless-recovery section):

- **Ring assignment.** Hosts form a ring over the sorted live world;
  each host pushes its own payload to its ring-successor
  (:func:`ring_successor`). A 1-host world degrades to a no-op — the
  flag stays legal, nothing is pushed.
- **Push.** At every checkpoint boundary the trainer collects its local
  shard payload (``collect_local_shards`` — the same device→host fetch
  the save already pays, on the MAIN thread: donated step buffers make
  background device reads unsafe) and hands it to a bounded background
  push thread: the train step never blocks on replica IO. The payload
  is split (``_split_payload``) and written with the sharded codec's
  per-shard sha256 sidecars into a step-tagged directory under
  ``<cluster_dir>/replicas/host_<owner>/``, committed by atomic
  tmp→rename of the whole directory, retained for the last ``keep``
  steps. Push failures retry with the shared bounded backoff
  (``utils/backoff.py``) and are logged, never raised into training.
- **Staleness.** The owner's newest committed step
  (:attr:`PeerReplicaStore.replica_step`) is advertised in the
  heartbeat ``extra`` payload, so the chief's ``decide_restart`` can
  tell whether a peer restore is viable — and how stale — without
  touching the store.
- **Restore.** Survivors restore their own live shards from the
  in-memory payload cache (falling back to their own on-disk replica
  when the cache misses the decided step), reconstruct each lost
  host's shard from the replica its ring-predecessor pushed, verify
  every sidecar, and assemble the full state with the same
  coverage-mask logic as the sharded codec. Any miss raises the
  classified :class:`ReplicaMiss` so the caller falls back to the
  disk restore walk.

Telemetry: pushes/verifies/reconstructs emit ``peer_replica`` JSONL
records; replica reads emit ``shard_io`` records with
``source="peer"`` (disk reads say ``source="disk"``), so the
zero-disk-reads claim of a peer restore is pinned by the stream.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from dml_cnn_cifar10_tpu.ckpt import sharded
from dml_cnn_cifar10_tpu.ckpt.sharded import collect_local_shards  # noqa: F401  (re-export: the trainer's push seam)
from dml_cnn_cifar10_tpu.utils import backoff

#: Store directory under ``cluster_dir`` — a sibling of ``heartbeats/``.
REPLICAS_DIRNAME = "replicas"

#: Per-replica commit marker, written INSIDE the step dir before the
#: atomic directory rename publishes it.
INDEX = "INDEX.json"

#: Push retry budget: attempts over the shared bounded backoff before a
#: push is abandoned (logged ``ok=False``; the next boundary pushes a
#: fresher payload anyway).
PUSH_ATTEMPTS = 3


class ReplicaMiss(ValueError):
    """A needed replica is missing, stale, or failed integrity
    verification. Classified (a ``ValueError`` naming the replica), so
    the restore seam falls back to the disk walk instead of crashing."""


def ring_successor(pid: int, world: Sequence[int]) -> int:
    """The host ``pid`` pushes its replica TO — the next id on the
    sorted ring. A 1-host world maps a host to itself (no-op)."""
    ring = sorted(world)
    i = ring.index(pid)
    return ring[(i + 1) % len(ring)]


def ring_predecessor(pid: int, world: Sequence[int]) -> int:
    """The host whose replica ``pid`` holds — the previous ring id."""
    ring = sorted(world)
    i = ring.index(pid)
    return ring[(i - 1) % len(ring)]


def _payload_nbytes(payload: Dict[str, list]) -> int:
    total = 0
    for entries in payload.values():
        if isinstance(entries, dict):
            entries = list(entries.values())
        for e in entries:
            total += int(np.asarray(e["data"]).nbytes)
    return total


class PeerReplicaStore:
    """File-backed peer-replica store next to the heartbeat dir.

    File-backed for the same reason the heartbeat store is: it must
    work where the collectives do not, be inspectable post-mortem, and
    be simulatable on CPU — a real RDMA/KV transport can replace it
    behind the same push/read API. One background thread drains a
    bounded queue of at most two pending payloads (newest wins: under
    a slow store the freshest state is the one worth replicating).
    """

    def __init__(self, cluster_dir: str, process_id: int,
                 world: Sequence[int], keep: int = 2,
                 log_fn: Optional[Callable[..., None]] = None,
                 threads: int = 1, client=None):
        # Optional network transport (parallel/net.py CoordClient):
        # pushes stage locally then travel to the coordination service
        # host, committed by a server-side atomic rename — the same
        # tmp→rename protocol, one hop further away. None = the
        # file-backed store (shared directory) as before. TransportError
        # subclasses OSError, so every retry/abandon path below handles
        # a network failure exactly like a filesystem one.
        self._client = client
        self.root = os.path.join(cluster_dir, REPLICAS_DIRNAME)
        self.process_id = process_id
        self.world = sorted(world) if world else [process_id]
        self.keep = max(int(keep), 1)
        self.threads = max(int(threads or 1), 1)
        self._log = log_fn
        #: Committed pushes (the pushes-vs-steps pin reads this).
        self.pushes = 0
        self._mem: Dict[int, Dict[str, list]] = {}
        self._queue: List[Tuple[int, Dict[str, list]]] = []
        self._cv = threading.Condition()
        self._closing = False
        self._inflight = 0
        # Recover continuity after an in-process restart (the supervisor
        # rebuilds the Trainer but the store spans attempts): the newest
        # committed own replica still counts as pushed.
        steps = self.committed_steps(process_id)
        self._replica_step = steps[-1] if steps else -1
        self._worker = threading.Thread(
            target=self._drain, daemon=True, name="peer-replica-push")
        self._worker.start()

    # -- identity ---------------------------------------------------------

    @property
    def enabled(self) -> bool:
        """Redundancy is meaningful only with a peer to hold the copy."""
        return len(self.world) > 1

    @property
    def replica_step(self) -> int:
        """Newest OWN committed replica step (-1 = none yet) — the
        staleness number the heartbeat ``extra`` payload advertises."""
        return self._replica_step

    def successor(self) -> int:
        return ring_successor(self.process_id, self.world)

    def set_world(self, world: Sequence[int]) -> None:
        """Adopt a restart decision's survivor set: the ring re-forms
        over the new world (a 1-host world stops pushing)."""
        with self._cv:
            self.world = sorted(world) if world else [self.process_id]

    # -- paths ------------------------------------------------------------

    def _host_dir(self, owner: int) -> str:
        return os.path.join(self.root, f"host_{owner}")

    def _step_dir(self, owner: int, step: int) -> str:
        return os.path.join(self._host_dir(owner), f"step_{step:08d}")

    def _host_rel(self, owner: int) -> str:
        """Server-relative path of an owner's replica dir (net mode)."""
        return f"{REPLICAS_DIRNAME}/host_{owner}"

    def _step_rel(self, owner: int, step: int) -> str:
        return f"{self._host_rel(owner)}/step_{step:08d}"

    def committed_steps(self, owner: int) -> List[int]:
        """Sorted committed replica steps for ``owner`` (commit marker
        present; half-renamed tmp dirs are invisible). Over the network
        transport an unreachable coordinator reads as no commits — the
        decide seam then falls back to disk, which is the right
        degradation."""
        out: List[int] = []
        if self._client is not None:
            try:
                names = self._client.list_dir(self._host_rel(owner))
            except OSError:
                return out
            # Visibility == committed: the server publishes a step dir
            # only by the atomic rename that ends a push.
            for name in names:
                if not name.startswith("step_") or ".tmp" in name:
                    continue
                try:
                    out.append(int(name[len("step_"):]))
                except ValueError:
                    continue
            return sorted(out)
        try:
            names = os.listdir(self._host_dir(owner))
        except OSError:
            return out
        for name in names:
            if not name.startswith("step_") or ".tmp" in name:
                continue
            try:
                step = int(name[len("step_"):])
            except ValueError:
                continue
            if os.path.isfile(os.path.join(self._host_dir(owner), name,
                                           INDEX)):
                out.append(step)
        return sorted(out)

    # -- telemetry --------------------------------------------------------

    def _emit(self, op: str, step=None, owner=None, nbytes=None,
              secs=None, ok=None, error=None, staleness=None) -> None:
        if self._log is not None:
            self._log("peer_replica", op=op, step=step, owner=owner,
                      bytes=nbytes, secs=secs, ok=ok, error=error,
                      staleness=staleness)

    # -- push side --------------------------------------------------------

    def push_state_async(self, step: int, state: Any) -> bool:
        """The trainer's checkpoint-boundary seam: collect THIS
        process's shard payload (synchronously — the fetch must happen
        before the next donated dispatch reuses the buffers) and hand
        it to the background push thread. Returns whether a push was
        enqueued (False in a 1-host world: no-op by design)."""
        if not self.enabled:
            return False
        return self.push_async(step, collect_local_shards(state))

    def push_async(self, step: int, payload: Dict[str, list]) -> bool:
        if not self.enabled:
            return False
        with self._cv:
            self._mem[int(step)] = payload
            for old in sorted(self._mem)[:-self.keep]:
                del self._mem[old]
            self._queue.append((int(step), payload))
            if len(self._queue) > 2:
                self._queue.pop(0)  # newest wins under a slow store
            self._cv.notify()
        return True

    def _drain(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._closing:
                    self._cv.wait()
                if not self._queue:
                    return
                step, payload = self._queue.pop(0)
                self._inflight += 1
            try:
                self._push_with_retry(step, payload)
            finally:
                with self._cv:
                    self._inflight -= 1
                    self._cv.notify_all()

    def _push_with_retry(self, step: int, payload: Dict[str, list]) -> None:
        err = None
        for attempt in range(1, PUSH_ATTEMPTS + 1):
            try:
                self._push(step, payload)
                return
            except OSError as e:
                err = e
                if attempt < PUSH_ATTEMPTS:
                    time.sleep(backoff.delay_s(0.05, 1.0, attempt))
        # Abandoned push: logged, never raised — the next checkpoint
        # boundary replicates a fresher payload anyway, and the decide
        # seam sees the gap through the advertised replica_step.
        self._emit("push", step=step, owner=self.process_id, ok=False,
                   error=str(err)[:300])

    def _push(self, step: int, payload: Dict[str, list]) -> None:
        if self._client is not None:
            return self._push_net(step, payload)
        t0 = time.perf_counter()
        final = self._step_dir(self.process_id, step)
        if os.path.isfile(os.path.join(final, INDEX)):
            return  # already committed (a replayed boundary)
        tmp = final + f".tmp{os.getpid()}"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        parts = sharded._split_payload(payload, self.threads)
        names = [f"part_{j}.msgpack" for j in range(len(parts))]
        total = 0
        for name, part in zip(names, parts):
            _, nbytes, _ = sharded._write_one_shard(tmp, name, part,
                                                    on_event=None,
                                                    source="peer")
            total += nbytes
        index = {"owner": self.process_id, "dest": self.successor(),
                 "step": int(step), "files": names}
        idx_tmp = os.path.join(tmp, INDEX + ".tmp")
        with open(idx_tmp, "w") as f:
            json.dump(index, f)
        os.replace(idx_tmp, os.path.join(tmp, INDEX))
        os.rename(tmp, final)  # the commit point
        self._replica_step = max(self._replica_step, int(step))
        self.pushes += 1
        self._emit("push", step=step, owner=self.process_id,
                   nbytes=total,
                   secs=round(time.perf_counter() - t0, 6), ok=True)
        self._prune()

    def _push_net(self, step: int, payload: Dict[str, list]) -> None:
        """Network push: stage the split + sidecar-bearing part files
        in a local scratch dir (the same codec writes them), upload
        each under a ``.tmpnet`` step dir, then commit with ONE
        server-side atomic rename — visibility still equals commit."""
        t0 = time.perf_counter()
        if step in self.committed_steps(self.process_id):
            return  # already committed (a replayed boundary)
        rel_final = self._step_rel(self.process_id, step)
        rel_tmp = rel_final + f".tmpnet{os.getpid()}"
        scratch = tempfile.mkdtemp(prefix="dml_peer_push_")
        try:
            parts = sharded._split_payload(payload, self.threads)
            names = [f"part_{j}.msgpack" for j in range(len(parts))]
            total = 0
            for name, part in zip(names, parts):
                _, nbytes, _ = sharded._write_one_shard(
                    scratch, name, part, on_event=None, source="peer")
                total += nbytes
            # Upload parts AND their .sha256 sidecars; INDEX last so a
            # server-side listing of the tmp dir is never mistaken for
            # complete (belt — the rename commit is the suspenders).
            for fname in sorted(os.listdir(scratch)):
                with open(os.path.join(scratch, fname), "rb") as f:
                    self._client.put(f"{rel_tmp}/{fname}", f.read())
            index = {"owner": self.process_id, "dest": self.successor(),
                     "step": int(step), "files": names}
            self._client.put(f"{rel_tmp}/{INDEX}",
                             json.dumps(index).encode())
            self._client.rename(rel_tmp, rel_final)
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
        self._replica_step = max(self._replica_step, int(step))
        self.pushes += 1
        self._emit("push", step=step, owner=self.process_id,
                   nbytes=total,
                   secs=round(time.perf_counter() - t0, 6), ok=True)
        self._prune()

    def _prune(self) -> None:
        for step in self.committed_steps(self.process_id)[:-self.keep]:
            if self._client is not None:
                try:
                    self._client.delete_tree(
                        self._step_rel(self.process_id, step))
                except OSError:
                    pass  # the next boundary's prune retries
            else:
                shutil.rmtree(self._step_dir(self.process_id, step),
                              ignore_errors=True)

    def flush(self, timeout_s: float = 10.0) -> None:
        """Drain pending pushes (tests, lockstep sim; off the step path)."""
        deadline = time.time() + timeout_s
        with self._cv:
            while (self._queue or self._inflight) \
                    and time.time() < deadline:
                self._cv.wait(timeout=0.05)

    # -- read side --------------------------------------------------------

    def _fetch_replica(self, owner: int, step: int) -> str:
        """Net mode: download one committed replica (commit marker,
        parts, sidecars) into a scratch dir shaped like the on-disk
        layout, so the verify path below runs unchanged. Unreachable or
        uncommitted reads raise :class:`ReplicaMiss` — the caller falls
        back to the disk walk."""
        rel = self._step_rel(owner, step)
        try:
            idx_payload = self._client.get(f"{rel}/{INDEX}")
        except OSError as e:
            raise ReplicaMiss(
                f"replica of host {owner} at step {step} unreachable "
                f"over the net transport: {e}")
        if idx_payload is None:
            raise ReplicaMiss(
                f"replica of host {owner} at step {step} is missing or "
                f"stale (committed steps: "
                f"{self.committed_steps(owner) or 'none'})")
        try:
            files = json.loads(idx_payload)["files"]
        except (ValueError, TypeError, KeyError) as e:
            raise ReplicaMiss(
                f"replica of host {owner} at step {step} has an "
                f"undecodable commit marker: {e}")
        scratch = os.path.join(
            tempfile.mkdtemp(prefix="dml_peer_read_"),
            f"step_{step:08d}")
        os.makedirs(scratch)
        with open(os.path.join(scratch, INDEX), "wb") as f:
            f.write(idx_payload)
        for fname in files:
            for name in (fname, sharded.shard_checksum_path(fname)):
                try:
                    payload = self._client.get(f"{rel}/{name}")
                except OSError as e:
                    raise ReplicaMiss(
                        f"replica of host {owner} at step {step} "
                        f"unreachable mid-read: {e}")
                if payload is None:
                    continue  # sidecar-less legacy replica decodes
                with open(os.path.join(scratch, name), "wb") as f:
                    f.write(payload)
        return scratch

    def read_replica(self, owner: int, step: int,
                     on_event=None) -> Dict[str, list]:
        """Read + sidecar-verify one committed replica. Every failure —
        missing dir, missing commit marker, truncated file, digest
        mismatch — raises the classified :class:`ReplicaMiss`, never an
        unclassified crash. A sidecar-less legacy replica decodes (the
        sharded codec's own back-compat rule)."""
        if self._client is not None:
            d = self._fetch_replica(owner, step)
            try:
                return self._read_replica_dir(d, owner, step, on_event)
            finally:
                shutil.rmtree(os.path.dirname(d), ignore_errors=True)
        return self._read_replica_dir(self._step_dir(owner, step),
                                      owner, step, on_event)

    def _read_replica_dir(self, d: str, owner: int, step: int,
                          on_event=None) -> Dict[str, list]:
        idx = os.path.join(d, INDEX)
        if not os.path.isfile(idx):
            newest = self.committed_steps(owner)
            raise ReplicaMiss(
                f"replica of host {owner} at step {step} is missing or "
                f"stale (committed steps: {newest or 'none'})")
        t0 = time.perf_counter()
        try:
            with open(idx) as f:
                files = json.load(f)["files"]
        except (OSError, ValueError, KeyError) as e:
            raise ReplicaMiss(
                f"replica of host {owner} at step {step} has an "
                f"undecodable commit marker: {e}")
        payload: Dict[str, list] = {}
        total = 0
        for fname in files:
            try:
                part = sharded._read_one_shard(d, fname, on_event,
                                               source="peer")
            except (OSError, ValueError) as e:
                self._emit("verify", step=step, owner=owner, ok=False,
                           error=str(e)[:300])
                raise ReplicaMiss(
                    f"replica of host {owner} at step {step} failed "
                    f"verification: {e}") from e
            total += os.path.getsize(os.path.join(d, fname))
            for path, entries in part.items():
                if isinstance(entries, dict):
                    entries = list(entries.values())
                payload.setdefault(path, []).extend(entries)
        self._emit("verify", step=step, owner=owner, nbytes=total,
                   secs=round(time.perf_counter() - t0, 6), ok=True)
        return payload

    def restore(self, target: Any, step: int, world: Sequence[int],
                lost: Sequence[int] = (), on_event=None) -> Any:
        """Assemble the full state at ``step`` from peer replicas onto
        ``target``'s structure — ZERO checkpoint reads. ``world`` is the
        OLD world that wrote the payloads (survivors + lost). Own
        payload comes from the in-memory cache (own replica file when
        the cache misses the step); every other owner's from its
        committed replica, sidecar-verified. Raises :class:`ReplicaMiss`
        when any needed payload is missing/corrupt or coverage is
        incomplete — the caller falls back to the disk walk."""
        lost_set = set(lost)
        payloads: List[Tuple[int, Dict[str, list]]] = []
        # Own payload first: deterministic precedence when replicas
        # redundantly cover the same index ranges (the 1-JAX-world-per-
        # process CPU simulation, where every payload is full-coverage).
        owners = sorted(set(world), key=lambda p: (p != self.process_id,
                                                   p))
        for owner in owners:
            if owner == self.process_id and step in self._mem:
                payload = self._mem[step]
                if on_event is not None:
                    on_event("shard_io", op="restore",
                             shard=f"host_{owner}/step_{step:08d}/memory",
                             bytes=_payload_nbytes(payload), secs=0.0,
                             verify=None, source="peer")
            else:
                t0 = time.perf_counter()
                payload = self.read_replica(owner, step,
                                            on_event=on_event)
                if owner in lost_set:
                    self._emit("reconstruct", step=step, owner=owner,
                               nbytes=_payload_nbytes(payload),
                               secs=round(time.perf_counter() - t0, 6),
                               ok=True)
            payloads.append((owner, payload))
        return _assemble(target, payloads, step)

    # -- lifecycle --------------------------------------------------------

    def close(self) -> None:
        with self._cv:
            self._closing = True
            self._cv.notify_all()
        self._worker.join(timeout=5.0)


def _assemble(target: Any, payloads: List[Tuple[int, Dict[str, list]]],
              step: int) -> Any:
    """Coverage-mask assembly onto ``target``'s structure (shapes and
    dtypes come from the target itself — a peer restore needs no
    manifest). Fully-duplicate entries from redundant replicas are
    skipped (payload order is deterministic); a PARTIAL overlap or a
    coverage hole raises :class:`ReplicaMiss`."""
    shards: Dict[str, list] = {}
    for _owner, payload in payloads:
        for path, entries in payload.items():
            if isinstance(entries, dict):
                entries = list(entries.values())
            shards.setdefault(path, []).extend(entries)

    def build(path: str, leaf: Any) -> np.ndarray:
        shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
        dtype = np.dtype(getattr(leaf, "dtype", None)
                         or np.asarray(leaf).dtype)
        full = np.empty(shape, dtype=dtype)
        seen = np.zeros(shape, dtype=bool)
        for e in shards.get(path, ()):
            idx = tuple(slice(int(s), int(t)) for s, t in
                        np.asarray(e["index"], dtype=np.int64))
            sub = seen[idx]
            if sub.size and sub.all():
                continue  # redundant coverage from a second replica
            if sub.any():
                raise ReplicaMiss(
                    f"leaf {path!r} has partially-overlapping replica "
                    f"entries at {e['index']} for step {step}")
            full[idx] = e["data"]
            seen[idx] = True
        if not seen.all():
            raise ReplicaMiss(
                f"leaf {path!r} only {int(seen.sum())}/{full.size} "
                f"elements covered by peer replicas at step {step}")
        return full

    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: build(sharded._key_str(kp), leaf), target)
