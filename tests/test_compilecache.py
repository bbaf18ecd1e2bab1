"""Persistent compilation cache (compilecache/; docs/COMPILECACHE.md):
keying determinism, hit/miss + store/load mechanics, corruption
fail-open, LRU eviction, the CLI, and the ISSUE-5 acceptance smoke —
train under --compile_cache_dir, kill via sigterm@N, supervisor-restart
in the same cache dir, and require `compile` hit events plus final
params bit-identical to an uninterrupted run."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dml_cnn_cifar10_tpu.compilecache import CompileCache, wrap
from dml_cnn_cifar10_tpu.compilecache import cache as cc_lib
from dml_cnn_cifar10_tpu.train.loop import Trainer
from dml_cnn_cifar10_tpu.train.supervisor import fit_supervised
from tests.conftest import tiny_train_cfg
from tools import check_jsonl_schema, compile_cache_cli


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _compile_events(path):
    return [r for r in _read_jsonl(path) if r["kind"] == "compile"]


class _EventSink:
    """MetricsLogger-shaped collector for cache events."""

    def __init__(self):
        self.events = []

    def log(self, kind, **fields):
        self.events.append({"kind": kind, **fields})


# ---------------------------------------------------------------------------
# keying: determinism + sensitivity
# ---------------------------------------------------------------------------

def test_fingerprint_deterministic_and_sensitive(tmp_path):
    cache = CompileCache(str(tmp_path))
    f = jax.jit(lambda x: x * 2 + 1)
    aval32 = jax.ShapeDtypeStruct((16,), jnp.float32)
    aval16 = jax.ShapeDtypeStruct((16,), jnp.bfloat16)
    hlo32 = f.lower(aval32).as_text()
    ctx = {"donate": [], "mesh_axes": ["data"], "mesh_shape": [8]}
    # Same program + context twice -> identical key (lowering is
    # deterministic; the whole warm-start contract rests on this).
    assert cache.fingerprint(hlo32, ctx) == cache.fingerprint(hlo32, ctx)
    assert cache.fingerprint(f.lower(aval32).as_text(), ctx) \
        == cache.fingerprint(hlo32, ctx)
    # dtype changes the lowered module -> different key.
    assert cache.fingerprint(f.lower(aval16).as_text(), ctx) \
        != cache.fingerprint(hlo32, ctx)
    # mesh / donation changes re-key via the explicit context even when
    # the module text were equal.
    assert cache.fingerprint(hlo32, {**ctx, "mesh_shape": [4, 2]}) \
        != cache.fingerprint(hlo32, ctx)
    assert cache.fingerprint(hlo32, {**ctx, "donate": [0]}) \
        != cache.fingerprint(hlo32, ctx)


def test_train_step_key_determinism_across_builders(data_cfg, tmp_path):
    """The same TrainConfig builds the same train-step key twice; a
    compute-dtype flip builds a different one."""
    from dml_cnn_cifar10_tpu.config import ModelConfig, OptimConfig
    from dml_cnn_cifar10_tpu.models.registry import get_model
    from dml_cnn_cifar10_tpu.parallel import step as step_lib
    from dml_cnn_cifar10_tpu.parallel.mesh import build_mesh, shard_batch
    from dml_cnn_cifar10_tpu.config import ParallelConfig

    mesh = build_mesh(ParallelConfig())
    md = get_model("cnn")
    oc = OptimConfig()
    cache = CompileCache(str(tmp_path))
    rng = np.random.default_rng(0)
    batch = shard_batch(mesh, rng.random((32, 24, 24, 3), np.float32),
                        rng.integers(0, 10, (32,)).astype(np.int32))

    def key_for(mc):
        sh = step_lib.train_state_shardings(mesh, md, mc, data_cfg, oc)
        fn = step_lib.make_train_step(md, mc, oc, mesh,
                                      state_sharding=sh,
                                      compile_cache=cache)
        state = step_lib.init_train_state(
            jax.random.key(0), md, mc, data_cfg, oc, mesh,
            state_sharding=sh)
        fn(state, *batch)
        return fn.last_event["key"]

    k1 = key_for(ModelConfig(logit_relu=False))
    k2 = key_for(ModelConfig(logit_relu=False))
    k3 = key_for(ModelConfig(logit_relu=False,
                             compute_dtype="bfloat16"))
    assert k1 == k2 and k1 is not None
    assert k3 != k1


def test_optimizer_sharding_and_partition_rules_change_key(data_cfg,
                                                           tmp_path):
    """--optimizer_sharding and --partition_rules alter the lowered
    StableHLO (sharding constraints / in-sharding annotations), so they
    MUST re-key the compile cache — a stale hit here silently serves an
    executable with the wrong update schedule or state layout."""
    from dml_cnn_cifar10_tpu.config import (ModelConfig, OptimConfig,
                                            ParallelConfig)
    from dml_cnn_cifar10_tpu.models.registry import get_model
    from dml_cnn_cifar10_tpu.parallel import shardings
    from dml_cnn_cifar10_tpu.parallel import step as step_lib
    from dml_cnn_cifar10_tpu.parallel.mesh import build_mesh, shard_batch

    mesh = build_mesh(ParallelConfig())
    md = get_model("cnn")
    mc = ModelConfig(logit_relu=False)
    cache = CompileCache(str(tmp_path))
    rng = np.random.default_rng(0)
    batch = shard_batch(mesh, rng.random((32, 24, 24, 3), np.float32),
                        rng.integers(0, 10, (32,)).astype(np.int32))

    def key_for(oc, rules=None, zero1=False):
        sh = step_lib.train_state_shardings(mesh, md, mc, data_cfg, oc,
                                            zero1=zero1, rules=rules)
        fn = step_lib.make_train_step(md, mc, oc, mesh,
                                      state_sharding=sh, rules=rules,
                                      compile_cache=cache)
        state = step_lib.init_train_state(
            jax.random.key(0), md, mc, data_cfg, oc, mesh,
            state_sharding=sh)
        fn(state, *batch)
        return fn.last_event["key"]

    base = key_for(OptimConfig(momentum=0.9))
    zero1 = key_for(OptimConfig(momentum=0.9,
                                optimizer_sharding="zero1"), zero1=True)
    rules = shardings.parse_partition_rules(
        "full1/kernel$=data,-; .*=")     # storage layout change
    ruled = key_for(OptimConfig(momentum=0.9), rules=rules)
    assert base is not None
    assert zero1 != base
    assert ruled != base and ruled != zero1


# ---------------------------------------------------------------------------
# hit/miss mechanics + entry layout
# ---------------------------------------------------------------------------

def test_miss_stores_committed_entry_then_hits(tmp_path):
    # Executable swapping is OPT-IN (default allowlist is empty — see
    # EXECUTABLE_BACKENDS); small donation-free programs exercise the
    # serialize/store/verify machinery safely on CPU.
    sink = _EventSink()
    cache = CompileCache(str(tmp_path), logger=sink,
                         executable_backends=("cpu",))
    f = jax.jit(lambda x: jnp.sin(x) * 3)
    x = jnp.arange(8, dtype=jnp.float32)
    w1 = wrap(f, cache, "train_step")
    out1 = w1(x)
    assert w1.last_event["hit"] is False
    assert w1.last_event["source"] == "miss"
    assert w1.last_event["compile_s"] > 0
    key = w1.last_event["key"]
    # Entry committed with the full file set and a verifying sidecar.
    for suffix in (".meta.json", ".exec", ".exec.sha256", ".hlo.z"):
        assert os.path.isfile(os.path.join(str(tmp_path), key + suffix))
    ok, reason = cache.verify_entry(key)
    assert ok, reason
    # Second wrapper, same program: in-process registry hit, identical
    # numerics, hit-count bumped in the meta.
    w2 = wrap(f, cache, "train_step")
    out2 = w2(x)
    assert w2.last_event["hit"] is True
    assert w2.last_event["source"] == "memory"
    assert w2.last_event["key"] == key
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert cache.load_meta(key)["hits"] >= 1
    # Every lookup emitted one schema-shaped `compile` event.
    kinds = [(e["phase"], e["hit"], e["source"]) for e in sink.events]
    assert kinds == [("train_step", False, "miss"),
                     ("train_step", True, "memory")]


def test_wrap_without_cache_is_identity():
    f = jax.jit(lambda x: x + 1)
    assert wrap(f, None, "train_step") is f


def test_cached_flops_served_from_entry(tmp_path):
    """The loop's FLOPs probe reads the cached artifact's cost
    analysis instead of recompiling."""
    from dml_cnn_cifar10_tpu.utils.profiling import compiled_flops

    cache = CompileCache(str(tmp_path))
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((32, 32))
    w = wrap(f, cache, "train_step")
    w(a, a)
    avals = (jax.ShapeDtypeStruct((32, 32), jnp.float32),) * 2
    flops = compiled_flops(w, avals)
    # CPU cost analysis reports flops as a list of per-program dicts;
    # the cache path normalizes it (the bare AOT path returned None
    # here, so a positive figure proves the cached route was taken).
    assert flops and flops > 0
    meta = cache.load_meta(w.last_event["key"])
    assert meta["cost_analysis"]["flops"] > 0


def test_second_signature_falls_back_to_jit(tmp_path):
    """A shape the obtained executable doesn't match must not error —
    the wrapper falls back to the jit call path (safety net)."""
    cache = CompileCache(str(tmp_path), executable_backends=("cpu",))
    w = wrap(jax.jit(lambda x: x * 2), cache, "eval_step")
    np.testing.assert_array_equal(np.asarray(w(jnp.ones((4,)))),
                                  2 * np.ones((4,)))
    np.testing.assert_array_equal(np.asarray(w(jnp.ones((9,)))),
                                  2 * np.ones((9,)))


# ---------------------------------------------------------------------------
# corruption: fail-open recompile, never a crash
# ---------------------------------------------------------------------------

def _store_and_forget(cache, const):
    """Compile+store a unique tiny program, then evict it from the
    process registry so the next lookup exercises the DISK path."""
    f = jax.jit(lambda x: x * const)
    w = wrap(f, cache, "train_step")
    w(jnp.ones((16,)))
    key = w.last_event["key"]
    cc_lib._PROCESS_EXECUTABLES.pop(key, None)
    return f, key


@pytest.mark.parametrize("what", ["payload_flip", "payload_truncate",
                                  "sidecar_flip", "sidecar_truncate"])
def test_corrupt_entry_fails_open_to_recompile(tmp_path, what):
    sink = _EventSink()
    cache = CompileCache(str(tmp_path), logger=sink,
                         executable_backends=("cpu",))
    # A UNIQUE program per case: the process registry spans test cases,
    # and a shared program would memory-hit instead of re-storing into
    # this case's fresh cache dir.
    const = 3.25 + sum(map(ord, what))
    f, key = _store_and_forget(cache, const)
    target = os.path.join(
        str(tmp_path),
        key + (".exec" if what.startswith("payload") else ".exec.sha256"))
    with open(target, "rb") as fh:
        data = bytearray(fh.read())
    if what.endswith("truncate"):
        data = data[:max(1, len(data) // 2)]
    else:
        data[len(data) // 2] ^= 0xFF
    with open(target, "wb") as fh:
        fh.write(bytes(data))
    assert not cache.verify_entry(key)[0]
    # Fail-open: the lookup drops the entry, recompiles, recommits —
    # and records the miss with source="corrupt".
    sink.events.clear()
    cc_lib._PROCESS_EXECUTABLES.pop(key, None)
    w = wrap(f, cache, "train_step")
    out = w(jnp.ones((16,)))
    np.testing.assert_allclose(np.asarray(out), const * np.ones((16,)))
    assert w.last_event["hit"] is False
    assert w.last_event["source"] == "corrupt"
    assert sink.events[0]["source"] == "corrupt"
    ok, reason = cache.verify_entry(key)
    assert ok, reason  # re-stored entry verifies again


# ---------------------------------------------------------------------------
# degraded mode (backends off the executable allowlist, e.g. real TPU)
# ---------------------------------------------------------------------------

def test_degraded_backend_keeps_jit_path_and_telemetry(tmp_path):
    """With the backend off the executable allowlist (the DEFAULT
    posture everywhere, see ``EXECUTABLE_BACKENDS``), execution must
    stay on the jit call path while the cache still fingerprints,
    stores StableHLO + cost analysis, and emits hit/miss events."""
    sink = _EventSink()
    cache = CompileCache(str(tmp_path), logger=sink,
                         executable_backends=())
    assert cache.degraded()
    f = jax.jit(lambda x: x * 5 + 1)
    x = jnp.arange(6, dtype=jnp.float32)
    w1 = wrap(f, cache, "train_step")
    np.testing.assert_array_equal(np.asarray(w1(x)),
                                  5 * np.arange(6, dtype=np.float32) + 1)
    assert w1.compiled is None            # nothing swapped in
    assert w1.last_event["hit"] is False
    assert w1.last_event["source"] == "miss"
    key = w1.last_event["key"]
    meta = cache.load_meta(key)
    assert meta is not None and meta["has_executable"] is False
    assert not os.path.isfile(os.path.join(str(tmp_path), key + ".exec"))
    # Second lookup: a stablehlo hit, numerics still from the jit path.
    w2 = wrap(f, cache, "train_step")
    np.testing.assert_array_equal(np.asarray(w2(x)), np.asarray(w1(x)))
    assert w2.last_event["hit"] is True
    assert w2.last_event["source"] == "stablehlo"
    # FLOPs probes are served from the entry without any executable.
    assert w2.cached_flops((jax.ShapeDtypeStruct((6,), jnp.float32),))


def test_executable_swap_is_opt_in(tmp_path):
    """Regression pin for the memory-safety gate: without an explicit
    DML_COMPILECACHE_EXEC_BACKENDS opt-in the allowlist is EMPTY, so
    every backend runs degraded. Re-enabling a default must come back
    through this test, with the swap proven on the installed jaxlib
    (``EXECUTABLE_BACKENDS`` has the history)."""
    assert cc_lib.EXECUTABLE_BACKENDS == ()
    assert CompileCache(str(tmp_path)).degraded()


def _spy_config_updates(monkeypatch):
    calls = []
    real = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (calls.append((name, val)), real(name, val))[1])
    return calls


def test_native_cache_resolver_rule(monkeypatch):
    """The one resolver (ISSUE 21 item 5). ``JAX_COMPILATION_CACHE_DIR``
    set: jax has taken it, the program sets no directory in code.
    Unset: off on CPU (tests must not write a cache into the checkout);
    headed for an accelerator, the ONE fixed path inside the checkout."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_floor = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.delenv("DML_COMPILECACHE_NATIVE_CACHE", raising=False)
    calls = _spy_config_updates(monkeypatch)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        assert cc_lib.arm_native_cache() == "/x"
        assert "jax_compilation_cache_dir" not in [n for n, _ in calls]
        assert jax.config.jax_compilation_cache_dir == prev_dir
        assert not os.path.exists("/x")

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        del calls[:]
        # The test env requests platform cpu -> stays off.
        assert cc_lib.arm_native_cache() is None
        assert calls == []

        monkeypatch.setenv("DML_COMPILECACHE_NATIVE_CACHE", "1")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert cc_lib.NATIVE_CACHE_DIR == want
        assert cc_lib.arm_native_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_floor)


def test_native_cache_path_is_fixed_across_processes(tmp_path):
    """Never under /tmp, a temporary name, a pid or the time: two
    processes started in different directories resolve the same path,
    and with the environment variable set neither touches it."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("from dml_cnn_cifar10_tpu.compilecache import "
            "arm_native_cache; print(arm_native_cache())")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=repo, DML_COMPILECACHE_NATIVE_CACHE="1")

    def resolve(cwd, **extra):
        return subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env={**env, **extra},
            check=True, capture_output=True, text=True,
            timeout=120).stdout.strip()

    other = tmp_path / "elsewhere"
    other.mkdir()
    fixed = os.path.join(repo, ".jax_cache")
    assert resolve(repo) == resolve(str(other)) == fixed
    placed = str(tmp_path / "placed")
    assert resolve(str(other), JAX_COMPILATION_CACHE_DIR=placed) == placed


# ---------------------------------------------------------------------------
# LRU eviction
# ---------------------------------------------------------------------------

def test_lru_eviction_bounds_cache_size(tmp_path):
    cache = CompileCache(str(tmp_path), max_bytes=10**9)
    blob = b"x" * 1000
    for i, key in enumerate(("k_old", "k_mid", "k_new")):
        cache.store(key, "train_step", blob, "hlo text", None, 0.1, {})
        time.sleep(0.02)  # distinct last_used stamps
    assert {k for k, _ in cache.entries()} == {"k_old", "k_mid", "k_new"}
    # A hit on the oldest makes it most-recently-used...
    cache._touch("k_old", cache.load_meta("k_old"))
    per_entry = cache.entry_bytes("k_new")
    # ...so bounding to ~2 entries must evict k_mid (the true LRU), not
    # the just-touched k_old.
    cache.max_bytes = int(per_entry * 2.5)
    cache._evict()
    survivors = {k for k, _ in cache.entries()}
    assert survivors == {"k_old", "k_new"}
    total = sum(cache.entry_bytes(k) for k in survivors)
    assert total <= cache.max_bytes


# ---------------------------------------------------------------------------
# the CLI: inspect / verify / prune (tier-1 smoke, satellite)
# ---------------------------------------------------------------------------

def test_compile_cache_cli_inspect_verify_prune(tmp_path, capsys):
    cache = CompileCache(str(tmp_path), executable_backends=("cpu",))
    f = jax.jit(lambda x: x - 7)
    w = wrap(f, cache, "eval_step")
    w(jnp.ones((4,)))
    key = w.last_event["key"]

    assert compile_cache_cli.main(["inspect", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert key in out and "eval_step" in out

    assert compile_cache_cli.main(["verify", str(tmp_path)]) == 0
    assert "OK" in capsys.readouterr().out

    # Corrupt the payload: verify reports it and exits 1.
    with open(os.path.join(str(tmp_path), key + ".exec"), "ab") as fh:
        fh.write(b"garbage")
    assert compile_cache_cli.main(["verify", str(tmp_path)]) == 1
    assert "CORRUPT" in capsys.readouterr().out

    # prune --corrupt drops it; the cache is then empty and verifies.
    assert compile_cache_cli.main(
        ["prune", str(tmp_path), "--corrupt"]) == 0
    capsys.readouterr()
    assert compile_cache_cli.main(["verify", str(tmp_path)]) == 0
    assert "empty cache" in capsys.readouterr().out

    assert compile_cache_cli.main(["prune", str(tmp_path), "--all"]) == 0
    assert cache.entries() == []


# ---------------------------------------------------------------------------
# goodput attribution (satellite)
# ---------------------------------------------------------------------------

def test_add_secs_attributes_compile_fraction():
    from dml_cnn_cifar10_tpu.utils.telemetry import SpanTracer

    tracer = SpanTracer(enabled=True)
    tracer.add_secs("compile", 0.5)
    gp = tracer.goodput(now=tracer._epoch + 1.0)
    assert gp["compile_frac"] == pytest.approx(0.5, abs=1e-6)
    assert gp["train_frac"] == pytest.approx(0.5, abs=1e-6)
    # Disabled tracers stay no-ops.
    off = SpanTracer(enabled=False)
    off.add_secs("compile", 0.5)
    assert off._cat_secs["compile"] == 0.0


# ---------------------------------------------------------------------------
# the acceptance smoke: sigterm@N + supervisor restart in the same
# cache dir -> compile hits, bit-identical params, schema-clean stream
# ---------------------------------------------------------------------------

def _cached_cfg(data_cfg, tmpdir, cache_dir, jsonl, total_steps=40):
    cfg = tiny_train_cfg(data_cfg, tmpdir, total_steps=total_steps)
    cfg.checkpoint_every = 10
    cfg.output_every = 10
    cfg.eval_every = 20
    cfg.recovery_backoff_s = 0.01
    cfg.compile_cache_dir = cache_dir
    cfg.metrics_jsonl = jsonl
    cfg.telemetry = True
    return cfg


def test_warm_restart_after_sigterm_is_bit_identical(data_cfg, tmp_path):
    cache_dir = str(tmp_path / "ccache")
    jsonl = str(tmp_path / "m.jsonl")
    cfg = _cached_cfg(data_cfg, str(tmp_path / "run"), cache_dir, jsonl)
    cfg.fault_spec = "sigterm@15"
    result = fit_supervised(cfg)
    # SIGTERM -> PreemptionGuard checkpoint + clean preempted exit.
    assert result.preempted and 15 <= result.final_step < 40

    # "Process restart": a fresh supervised run over the same log and
    # cache dirs resumes from the preemption checkpoint and re-enters
    # every compile seam through the cache.
    cfg2 = _cached_cfg(data_cfg, str(tmp_path / "run"), cache_dir,
                       str(tmp_path / "m2.jsonl"))
    result2 = fit_supervised(cfg2)
    assert result2.final_step == 40

    evs = _compile_events(cfg2.metrics_jsonl)
    train_evs = [e for e in evs if e["phase"] == "train_step"]
    assert train_evs and all(e["hit"] for e in train_evs)
    # Default posture: degraded (executable swapping is opt-in), so
    # warm re-entries hit as "stablehlo" (entry present, execution on
    # the jit call path). With an opted-in backend they would be
    # "memory"/"executable" — all three are hits.
    assert {e["source"] for e in evs if e["hit"]} <= {
        "memory", "executable", "stablehlo"}

    # Bit-identical to an uninterrupted (uncached) run: the cache
    # returns the same compiled artifact the cold path produces.
    clean = tiny_train_cfg(data_cfg, str(tmp_path / "clean"))
    clean.checkpoint_every = 10
    clean.output_every = 10
    clean.eval_every = 20
    ref = Trainer(clean).fit()
    for a, b in zip(jax.tree.leaves(result2.state.params),
                    jax.tree.leaves(ref.state.params)):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                      np.asarray(jax.device_get(b)))

    # Both streams pass the documented-schema lint, and the report
    # prints the compile-cost section.
    assert check_jsonl_schema.check_file(jsonl, strict=True) == []
    assert check_jsonl_schema.check_file(cfg2.metrics_jsonl, strict=True) == []
    from tools import telemetry_report
    out = telemetry_report.summarize(cfg2.metrics_jsonl)
    assert "compile cost" in out

    # The warm run attributed its (near-zero) obtain time to the
    # goodput compile fraction rather than the train remainder.
    gps = [r for r in _read_jsonl(cfg2.metrics_jsonl)
           if r["kind"] == "goodput"]
    assert gps and gps[-1]["compile_frac"] is not None


@pytest.mark.slow
def test_cross_process_warm_start_deserializes(data_cfg, tmp_path):
    """With a backend OPTED IN via DML_COMPILECACHE_EXEC_BACKENDS, a
    genuinely fresh process hits the DISK path: the second run's
    train-step lookup deserializes (source "executable", no compile).
    Small donation-only program — the checkpoint-restore + donation
    combination that heap-corrupts on CPU jaxlib 0.4.36 (why the
    allowlist defaults to empty) is not in play here."""
    cache_dir = str(tmp_path / "ccache")
    script = r"""
import sys
from dml_cnn_cifar10_tpu.utils.platform import force_cpu
force_cpu(virtual_devices=8)
import json
import numpy as np, jax, jax.numpy as jnp
from dml_cnn_cifar10_tpu.compilecache import CompileCache, wrap

cache = CompileCache(sys.argv[1])
f = jax.jit(lambda s, x: (s + (x * x).sum(), x * 2), donate_argnums=0)
w = wrap(f, cache, "train_step")
s, y = w(jnp.zeros(()), jnp.arange(16, dtype=jnp.float32))
print("EVENT " + json.dumps({**w.last_event,
                             "out": float(jax.device_get(s))}))
"""
    env = {**os.environ,
           "DML_COMPILECACHE_EXEC_BACKENDS": "cpu",
           "PYTHONPATH": os.path.dirname(
               os.path.dirname(os.path.abspath(__file__)))}

    def run_once():
        proc = subprocess.run([sys.executable, "-c", script, cache_dir],
                              capture_output=True, text=True, env=env,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("EVENT ")][0]
        return json.loads(line[len("EVENT "):])

    ev1 = run_once()
    ev2 = run_once()
    assert ev1["source"] == "miss" and ev1["hit"] is False
    assert ev2["source"] == "executable" and ev2["hit"] is True
    assert ev1["key"] == ev2["key"]          # cross-process determinism
    assert ev1["out"] == ev2["out"]          # identical numerics
