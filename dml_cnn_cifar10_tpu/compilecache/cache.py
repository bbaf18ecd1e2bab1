"""Disk-backed XLA executable cache with AOT warm-start semantics.

TensorFlow's distributed runtime amortized graph construction across
sessions implicitly (the reference inherits that via
MonitoredTrainingSession); under JAX every process restart — a
supervisor recovery, an elastic world-shrink re-entry, a serve bucket
warmup — pays a full retrace + XLA compile on entry, and the AOT
``lower().compile()`` path the FLOPs probes use doesn't even share the
in-process executable cache. This
module makes the amortization an explicit, observable subsystem:

- **Keying** (:meth:`CompileCache.fingerprint`): sha256 over the lowered
  StableHLO module text (which embeds shapes, in/out shardings, and
  donation aliasing) mixed with an explicit context dict — mesh shape +
  axis names, donation argnums, compute dtype — and the environment
  (jax/jaxlib version, backend platform, device kind, device count).
  Same program twice ⇒ same key; a dtype/mesh/donation change ⇒ a
  different key. Deterministic across processes, so a restarted run
  lands on the entries its predecessor wrote.
- **Entries** are flat files committed via atomic rename with the same
  integrity discipline as the checkpoint sidecars (``ckpt/checkpoint.py``):
  ``<key>.exec`` (pickled ``jax.experimental.serialize_executable``
  payload) → ``<key>.exec.sha256`` (digest sidecar) → ``<key>.hlo.z``
  (zlib StableHLO) → ``<key>.meta.json`` **last** — the meta file is the
  commit point, so a crash mid-store can never publish a partial entry.
- **Fail-open everywhere**: a corrupt payload, a bad sidecar, an
  unsupported backend, a full disk — every cache failure degrades to a
  plain recompile (with a ``compile`` miss event naming the reason),
  never to a crashed or wrong run. When executable serialization is
  unsupported, the entry keeps the lowered StableHLO + cost analysis
  (``source="stablehlo"``) so FLOPs consumers still skip their
  recompile.
- **Bounded**: LRU eviction by ``max_bytes`` over the whole directory,
  applied after each store (per-entry ``last_used`` rides the meta
  file). ``tools/compile_cache_cli.py`` inspects/verifies/prunes the
  same layout offline.
- **Observable**: every lookup emits one ``compile`` JSONL event
  (key, phase, hit, compile_s, source) through the run's
  ``MetricsLogger`` — wired into the schema lint, the
  ``tools/telemetry_report.py`` compile-cost section, and (via the
  Trainer's ``on_event`` hook) the goodput ``compile`` fraction.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
import zlib
from typing import Any, Callable, Optional, Tuple

#: event sources (the ``source`` field of ``compile`` JSONL records).
#: memory — this process already holds the live executable (an earlier
#:   seam compiled or deserialized it), reused with zero load cost —
#:   the in-process sharing the AOT path historically lacked;
#: executable — deserialized a cached executable, no XLA compile;
#: stablehlo — entry had module+cost analysis but no executable
#:   (serialization unsupported when it was written), compiled;
#: miss — no entry, compiled and stored;
#: corrupt — entry failed integrity/decode, was dropped, recompiled;
#: error — the cache machinery itself failed, fail-open compile;
#: uncached — no cache configured (emitted by seams that always
#:   report their compiles, e.g. serve warmup).
SOURCES = ("memory", "executable", "stablehlo", "miss", "corrupt",
           "error", "uncached")

#: Process-level fingerprint → live Compiled registry. Two jobs: (1) a
#: same-process re-entry (supervisor restart, elastic re-entry, a
#: second Trainer) reuses the live executable at zero cost; (2) it
#: guarantees a program is deserialized AT MOST ONCE per process, so
#: the disk path is reserved for the fresh-process warm start it exists
#: for (a second deserialize beside a live executable of the same
#: program was seen to corrupt memory on an earlier jaxlib; not
#: re-tested on 0.9.0 because the swap path is off, see below).
_PROCESS_EXECUTABLES: dict = {}

#: Backends where executing an AOT/deserialized executable in place of
#: the jit call path is allowed. DEFAULT: NONE. When this was written
#: (jaxlib 0.4.x) a swapped-in executable fed donated buffers corrupted
#: state in ways fail-open cannot catch: training drifted to NaN on a
#: TPU, and on CPU donating checkpoint-restored buffers into a
#: deserialized executable aborted the process (heap corruption, ~5/6
#: of supervisor-resume runs). Nobody has repeated that A/B on the
#: installed jax/jaxlib 0.9.0, so the swap stays off until someone
#: proves it on the chip (ROADMAP, Design: "a compile cache that may
#: not swap executables"). Everywhere by default the cache runs
#: DEGRADED: execution stays on the plain jit call path, warm start is
#: jax's own persistent compilation cache (placed by
#: :func:`arm_native_cache`), and our entries keep the StableHLO + cost
#: analysis + hit/miss telemetry. Opt in per backend you have verified
#: via DML_COMPILECACHE_EXEC_BACKENDS=cpu,tpu (tests pass
#: ``executable_backends=("cpu",)`` explicitly to exercise the
#: machinery on small donation-free programs).
EXECUTABLE_BACKENDS = tuple(
    b.strip() for b in os.environ.get(
        "DML_COMPILECACHE_EXEC_BACKENDS", "").lower().split(",")
    if b.strip())


#: Where jax's persistent compilation cache lives when the environment
#: does not place it: ONE fixed path inside the checkout (git-ignored).
#: Fixed because the directory is part of what jax keys an entry on — a
#: cache under a temporary name, a pid or the time never hits — and
#: inside the checkout because that is the one place every entry point
#: (CLI, the benchmark, ``chip_smoke.py``) agrees on without a flag.
NATIVE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _native_cache_platform_ok() -> bool:
    """True when the process is headed for an accelerator
    (``utils.platform.accelerator_expected``: no backend is
    initialized to find out). CPU is excluded because CPU runs are
    the tests and rehearsals: they must not write a cache into the
    checkout, and their compiles are seconds. (The exclusion was first
    added for heap corruption when loading cached XLA:CPU executables
    on jaxlib 0.4.36; that has not been re-tested on 0.9.0 and is no
    longer the reason.) Force with DML_COMPILECACHE_NATIVE_CACHE=1/0."""
    force = os.environ.get("DML_COMPILECACHE_NATIVE_CACHE", "").lower()
    if force in ("1", "true", "yes", "on"):
        return True
    if force in ("0", "false", "no", "off"):
        return False
    from dml_cnn_cifar10_tpu.utils.platform import accelerator_expected
    return accelerator_expected()


def arm_native_cache() -> Optional[str]:
    """Place jax's persistent compilation cache and return its
    directory (``None`` when it stays off). The one resolver every
    entry point calls — ``cli/main.py``, ``benchmark/lib/driver.py``,
    ``chip_smoke.py`` — straight after flag parsing:

    - ``JAX_COMPILATION_CACHE_DIR`` set: jax has already taken it from
      the environment; the program sets no directory in code, on any
      platform.
    - unset, headed for an accelerator: :data:`NATIVE_CACHE_DIR`.
    - unset, on CPU: off (:func:`_native_cache_platform_ok`).

    Where the cache is on, every program is cached: the default 1 s
    floor would skip the small eval/init programs whose recompiles
    still cost a restart.

    Call it before the process compiles anything: jax opens its cache
    once, at the first compile that finds a directory configured, and
    keeps that directory from then on (jax 0.9.0,
    ``compilation_cache._initialize_cache``). ``--compile_cache_dir``
    does not move this cache; it places the repo's own keyed store
    (:class:`CompileCache`) and its telemetry."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        if not _native_cache_platform_ok():
            return None
        cache_dir = NATIVE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def _avals_of(args):
    """Avals for ``lower``: shape/dtype, keeping the sharding only of
    COMMITTED arrays. An uncommitted array (e.g. the fresh PRNG key fed
    to init) carries an incidental single-device sharding that `lower`
    would treat as an explicit placement and reject against the
    program's mesh-wide out_shardings; the jit call path moves such
    arrays freely, so the aval must too."""
    import jax

    def aval(x):
        sh = getattr(x, "sharding", None)
        if sh is not None and not getattr(x, "committed", True):
            sh = None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    return jax.tree.map(aval, args)


def _flops_of(cost) -> Optional[float]:
    """``flops`` out of an XLA cost analysis that may be a dict (TPU) or
    a list of per-program dicts (CPU backends on current jaxlib)."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        return None
    f = cost.get("flops", 0.0)
    try:
        f = float(f)
    except (TypeError, ValueError):
        return None
    return f if f > 0 else None


def _jsonable_cost(cost):
    """Cost analysis as plain JSON (dict of float), or None."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        return None
    out = {}
    for k, v in cost.items():
        try:
            out[str(k)] = float(v)
        except (TypeError, ValueError):
            continue
    return out or None


def mesh_context(mesh, donate=(), compute_dtype: Optional[str] = None,
                 **extra) -> dict:
    """The explicit half of the cache key for a compile seam: mesh shape
    + axis names, donation argnums, compute dtype, plus any
    caller-specific discriminators. The StableHLO hash already embeds
    shapes/shardings/donation aliasing — this dict states the intent
    redundantly so key provenance survives lowering-format changes."""
    ctx = {"donate": sorted(int(d) for d in donate)}
    if mesh is not None:
        ctx["mesh_axes"] = list(getattr(mesh, "axis_names", ()))
        ctx["mesh_shape"] = [int(v) for v in
                             dict(getattr(mesh, "shape", {})).values()]
    if compute_dtype:
        ctx["compute_dtype"] = str(compute_dtype)
    ctx.update(extra)
    return ctx


class CompileCache:
    """The disk store. One instance per process/run; all methods are
    fail-open (they catch their own errors and report them through the
    returned event instead of raising into the training loop)."""

    def __init__(self, cache_dir: str, max_bytes: int = 2_000_000_000,
                 logger=None, on_event: Optional[Callable] = None,
                 executable_backends=EXECUTABLE_BACKENDS):
        self.cache_dir = cache_dir
        self.max_bytes = int(max_bytes)
        self.logger = logger
        self.on_event = on_event
        self.executable_backends = tuple(executable_backends)
        self._degraded: Optional[bool] = None  # resolved lazily (jax)
        os.makedirs(cache_dir, exist_ok=True)

    def degraded(self) -> bool:
        """True when this backend must not execute swapped-in AOT
        executables (see EXECUTABLE_BACKENDS): the cache then keeps its
        keying/telemetry/cost-analysis role, execution stays on the jit
        call path, and the warm start comes from jax's own persistent
        compilation cache (see :func:`arm_native_cache`)."""
        if self._degraded is None:
            try:
                import jax

                self._degraded = (jax.devices()[0].platform.lower()
                                  not in self.executable_backends)
            except Exception:
                self._degraded = True
        return self._degraded

    @classmethod
    def from_config(cls, cfg, logger=None, on_event=None
                    ) -> Optional["CompileCache"]:
        """Cache per ``TrainConfig`` (None when ``compile_cache_dir`` is
        unset — every seam then compiles exactly as before)."""
        if not getattr(cfg, "compile_cache_dir", None):
            return None
        return cls(cfg.compile_cache_dir,
                   max_bytes=cfg.compile_cache_max_bytes,
                   logger=logger, on_event=on_event)

    # --- keying ---

    def environment(self) -> dict:
        import jax
        import jaxlib

        dev = jax.devices()[0]
        return {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "backend": dev.platform,
            "device_kind": dev.device_kind,
            "n_devices": jax.device_count(),
        }

    def fingerprint(self, hlo_text: str, context: Optional[dict] = None
                    ) -> str:
        """Deterministic cache key: sha256 over the StableHLO module and
        the canonical-JSON (context, environment) pair."""
        h = hashlib.sha256(hlo_text.encode())
        h.update(json.dumps({"context": context or {},
                             "env": self.environment()},
                            sort_keys=True).encode())
        return h.hexdigest()[:32]

    # --- entry layout ---

    def _paths(self, key: str) -> dict:
        base = os.path.join(self.cache_dir, key)
        return {"exec": base + ".exec", "sum": base + ".exec.sha256",
                "hlo": base + ".hlo.z", "meta": base + ".meta.json"}

    @staticmethod
    def _atomic_write(path: str, data, mode: str = "wb") -> None:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, mode) as f:
            f.write(data)
        os.replace(tmp, path)

    def entries(self):
        """[(key, meta dict)] for every COMMITTED entry (meta present and
        parseable), unsorted. Unreadable metas are skipped, not raised."""
        out = []
        try:
            names = os.listdir(self.cache_dir)
        except OSError:
            return out
        for name in names:
            if not name.endswith(".meta.json") or ".tmp." in name:
                continue
            key = name[:-len(".meta.json")]
            try:
                with open(os.path.join(self.cache_dir, name)) as f:
                    out.append((key, json.load(f)))
            except (OSError, ValueError):
                continue
        return out

    def entry_bytes(self, key: str) -> int:
        return sum(os.path.getsize(p) for p in self._paths(key).values()
                   if os.path.isfile(p))

    def drop(self, key: str) -> None:
        for p in self._paths(key).values():
            try:
                os.remove(p)
            except OSError:
                pass

    # --- store / load ---

    def store(self, key: str, phase: str, exec_blob: Optional[bytes],
              hlo_text: str, cost, compile_s: float,
              context: Optional[dict]) -> None:
        """Commit one entry (exec → sha256 sidecar → hlo → meta LAST) and
        apply the LRU bound. Failures are swallowed: a cache that cannot
        write must not take the run down with it."""
        try:
            sizes = {}
            if exec_blob is not None:
                self._atomic_write(self._paths(key)["exec"], exec_blob)
                self._atomic_write(
                    self._paths(key)["sum"],
                    json.dumps({"algo": "sha256",
                                "digest": hashlib.sha256(
                                    exec_blob).hexdigest(),
                                "bytes": len(exec_blob)}), mode="w")
                sizes["exec_bytes"] = len(exec_blob)
            hlo_z = zlib.compress(hlo_text.encode(), 6)
            self._atomic_write(self._paths(key)["hlo"], hlo_z)
            sizes["hlo_bytes"] = len(hlo_z)
            meta = {
                "key": key, "phase": phase, "created": time.time(),
                "last_used": time.time(), "hits": 0,
                "compile_s": round(compile_s, 4),
                "cost_analysis": _jsonable_cost(cost),
                "has_executable": exec_blob is not None,
                "context": context or {}, **self.environment(), **sizes,
            }
            self._atomic_write(self._paths(key)["meta"],
                               json.dumps(meta), mode="w")
            self._evict()
        except Exception:
            pass

    def _touch(self, key: str, meta: dict) -> None:
        """Best-effort hit-count/recency update (LRU input)."""
        try:
            meta = dict(meta)
            meta["hits"] = int(meta.get("hits") or 0) + 1
            meta["last_used"] = time.time()
            self._atomic_write(self._paths(key)["meta"],
                               json.dumps(meta), mode="w")
        except Exception:
            pass

    def _evict(self) -> None:
        """Drop least-recently-used entries until the directory fits
        ``max_bytes``. Runs after every store; also the CLI's prune."""
        entries = self.entries()
        total = sum(self.entry_bytes(k) for k, _ in entries)
        if total <= self.max_bytes:
            return
        for key, meta in sorted(entries,
                                key=lambda km: km[1].get("last_used", 0)):
            if total <= self.max_bytes:
                break
            total -= self.entry_bytes(key)
            self.drop(key)

    def load_meta(self, key: str) -> Optional[dict]:
        try:
            with open(self._paths(key)["meta"]) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def verify_entry(self, key: str) -> Tuple[bool, str]:
        """(ok, reason) — the integrity walk ``compile_cache_cli verify``
        and the load path share. An entry without an executable (the
        StableHLO-only degraded form) verifies on its meta alone."""
        meta = self.load_meta(key)
        if meta is None:
            return False, "missing/unreadable meta"
        if not meta.get("has_executable"):
            return (os.path.isfile(self._paths(key)["hlo"]),
                    "stablehlo-only entry")
        paths = self._paths(key)
        if not os.path.isfile(paths["exec"]):
            return False, "missing exec payload"
        try:
            with open(paths["sum"]) as f:
                want = json.load(f)
        except (OSError, ValueError) as e:
            return False, f"unreadable sha256 sidecar: {e!r}"
        with open(paths["exec"], "rb") as f:
            blob = f.read()
        if hashlib.sha256(blob).hexdigest() != want.get("digest") \
                or len(blob) != want.get("bytes"):
            return False, (f"checksum mismatch ({len(blob)} bytes vs "
                           f"sidecar {want.get('bytes')})")
        return True, "verified"

    # --- the one-stop compile seam ---

    def obtain(self, jitted, avals, phase: str,
               context: Optional[dict] = None):
        """``(compiled, event)`` for one program: lower, fingerprint,
        and either deserialize the cached executable or AOT-compile and
        store it. ``compile_s`` covers the whole obtain (trace + load or
        compile) — the figure the goodput ``compile`` fraction wants.
        Raises only if the fail-open *compile itself* fails (a genuine
        program error the caller must see)."""
        t0 = time.perf_counter()
        key = None
        try:
            degraded = self.degraded()
            lowered = jitted.lower(*avals)
            hlo_text = lowered.as_text()
            key = self.fingerprint(hlo_text, context)
            mem = None if degraded else _PROCESS_EXECUTABLES.get(key)
            if mem is not None:
                # Same-process re-entry (supervisor restart / second
                # Trainer): the live executable is authoritative —
                # deserializing again would both waste the load and
                # trip jaxlib's duplicate-deserialize corruption (see
                # _PROCESS_EXECUTABLES).
                meta = self.load_meta(key)
                if meta is not None:
                    self._touch(key, meta)
                return mem, self._event(
                    key, phase, hit=True,
                    compile_s=time.perf_counter() - t0, source="memory")
            source = "miss"
            meta = self.load_meta(key)
            if meta is not None:
                ok, reason = self.verify_entry(key)
                if meta.get("has_executable") and ok and not degraded:
                    compiled = self._deserialize(key)
                    if compiled is not None:
                        _PROCESS_EXECUTABLES[key] = compiled
                        self._touch(key, meta)
                        return compiled, self._event(
                            key, phase, hit=True,
                            compile_s=time.perf_counter() - t0,
                            source="executable")
                    source = "corrupt"
                    self.drop(key)
                elif not ok and "stablehlo-only" not in reason:
                    source = "corrupt"
                    self.drop(key)
                else:
                    # Degraded entry: module + cost analysis cached,
                    # executable not serializable on this backend.
                    source = "stablehlo"
                    self._touch(key, meta)
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
            if not degraded:
                _PROCESS_EXECUTABLES[key] = compiled
            self.store(key, phase,
                       None if degraded else self._serialize(compiled),
                       hlo_text, self._cost(compiled), compile_s,
                       context)
            return compiled, self._event(key, phase, hit=False,
                                         compile_s=compile_s,
                                         source=source)
        except Exception:
            # Fail-open: any cache-machinery failure falls back to the
            # plain call-path compile in the wrapper; report it.
            return None, self._event(key, phase, hit=False,
                                     compile_s=time.perf_counter() - t0,
                                     source="error")

    def note_degraded(self, jitted, avals, phase: str,
                      context: Optional[dict], elapsed_s: float):
        """Record a degraded-mode first call (the executable that ran
        came from the jit call path, warm-started by jax's native
        persistent cache): fingerprint the program, commit a
        StableHLO + cost-analysis entry on miss, emit the ``compile``
        event. ``elapsed_s`` is the measured first-call time (trace +
        compile-or-native-cache-load + first execution)."""
        try:
            lowered = jitted.lower(*avals)
            hlo_text = lowered.as_text()
            key = self.fingerprint(hlo_text, context)
            meta = self.load_meta(key)
            if meta is not None:
                self._touch(key, meta)
                return self._event(key, phase, hit=True,
                                   compile_s=elapsed_s,
                                   source="stablehlo")
            cost = None
            try:
                # Analysis-only AOT compile, never executed; with the
                # native cache armed it is a disk hit, not a second
                # full compile.
                cost = self._cost(lowered.compile())
            except Exception:
                pass
            self.store(key, phase, None, hlo_text, cost, elapsed_s,
                       context)
            return self._event(key, phase, hit=False,
                               compile_s=elapsed_s, source="miss")
        except Exception:
            return self._event(None, phase, hit=False,
                               compile_s=elapsed_s, source="error")

    def cached_flops(self, jitted, avals,
                     context: Optional[dict] = None,
                     phase: str = "analysis") -> Optional[float]:
        """FLOPs for a program WITHOUT recompiling when the cache has
        seen it: served from the entry's recorded cost analysis on a
        hit; a miss compiles through :meth:`obtain` (storing the entry
        for next time). The cache-native replacement for the AOT
        ``lower().compile().cost_analysis()`` probe."""
        try:
            lowered = jitted.lower(*avals)
            key = self.fingerprint(lowered.as_text(), context)
            meta = self.load_meta(key)
            if meta is not None and meta.get("cost_analysis") is not None:
                self._touch(key, meta)
                self._event(key, phase, hit=True, compile_s=0.0,
                            source="executable"
                            if meta.get("has_executable")
                            else "stablehlo")
                return _flops_of(meta["cost_analysis"])
        except Exception:
            return None
        compiled, _ = self.obtain(jitted, avals, phase, context)
        if compiled is None:
            return None
        return _flops_of(self._cost(compiled))

    # --- serialization helpers ---

    @staticmethod
    def _cost(compiled):
        try:
            return compiled.cost_analysis()
        except Exception:
            return None

    @staticmethod
    def _serialize(compiled) -> Optional[bytes]:
        """Pickle of ``serialize_executable.serialize``'s
        (payload, in_tree, out_tree); None when the backend refuses —
        the entry then degrades to StableHLO + cost analysis."""
        try:
            from jax.experimental import serialize_executable
            return pickle.dumps(serialize_executable.serialize(compiled))
        except Exception:
            return None

    def _deserialize(self, key: str):
        try:
            from jax.experimental import serialize_executable
            with open(self._paths(key)["exec"], "rb") as f:
                payload, in_tree, out_tree = pickle.loads(f.read())
            return serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree)
        except Exception:
            return None

    # --- telemetry ---

    def _event(self, key, phase, hit, compile_s, source) -> dict:
        ev = {"key": key, "phase": phase, "hit": bool(hit),
              "compile_s": round(compile_s, 4), "source": source}
        if self.logger is not None:
            self.logger.log("compile", **ev)
        if self.on_event is not None:
            try:
                self.on_event(ev)
            except Exception:
                pass
        return ev


class CachedFunction:
    """Callable wrapper that routes a jitted function's FIRST call
    through a :class:`CompileCache` and every later call through the
    obtained executable (~0.5 µs/dispatch over the jit fast path,
    measured on CPU — noise against the ≥1 ms step programs cached
    here). Fail-open: any cache failure permanently falls back to the
    wrapped jit callable for this process."""

    def __init__(self, jitted, cache: CompileCache, phase: str,
                 context: Optional[dict] = None):
        self._jitted = jitted
        self._cache = cache
        self.phase = phase
        self.context = context
        self.compiled = None
        self.last_event: Optional[dict] = None
        self._fallback = False

    def __call__(self, *args):
        if self.compiled is not None:
            try:
                return self.compiled(*args)
            except (TypeError, ValueError):
                # A second input signature through the same wrapper
                # (executables are shape-exact): fall back to the jit
                # call path, which traces/compiles per shape as usual.
                # Only the first signature is disk-cached — every
                # framework seam builds one wrapper per fixed-shape
                # program, so this is a safety net, not a design path.
                return self._jitted(*args)
        if self._fallback:
            return self._jitted(*args)
        if self._cache.degraded():
            # Backend not on the executable allowlist: execute via the
            # jit call path (numerics authoritative; jax's native
            # persistent cache provides the warm start on accelerator
            # platforms), keep the fingerprint/telemetry/cost-analysis
            # role.
            t0 = time.perf_counter()
            out = self._jitted(*args)
            self.last_event = self._cache.note_degraded(
                self._jitted, _avals_of(args), self.phase, self.context,
                time.perf_counter() - t0)
            self._fallback = True
            return out
        compiled, ev = self._cache.obtain(self._jitted, _avals_of(args),
                                          self.phase, self.context)
        self.last_event = ev
        if compiled is None:
            self._fallback = True
            return self._jitted(*args)
        self.compiled = compiled
        return compiled(*args)

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def cached_flops(self, avals) -> Optional[float]:
        """FLOPs via the cache (no recompile on hits) — preferred by
        ``utils/profiling.compiled_flops``. Serves the already-obtained
        executable's analysis when this wrapper compiled the same
        avals."""
        if self.compiled is not None:
            f = _flops_of(CompileCache._cost(self.compiled))
            if f:
                return f
        return self._cache.cached_flops(self._jitted, avals,
                                        context=self.context,
                                        phase=self.phase)


def wrap(jitted, cache: Optional[CompileCache], phase: str,
         context: Optional[dict] = None):
    """``CachedFunction`` when a cache is configured, the jitted
    function untouched otherwise — so every seam can call this
    unconditionally and the no-cache hot path stays exactly as before."""
    if cache is None:
        return jitted
    return CachedFunction(jitted, cache, phase, context)
