"""Serving fleet (fleet/): router pick/evict/re-route, the autoscaler
decision table, sidecar-gated checkpoint publishing, the engine's
hot-swap seam — and the ISSUE-6 acceptance smoke: a 2-worker fleet
survives a mid-load worker kill with zero failed client requests, then
hot-swaps to a newly published checkpoint with zero failed requests, a
per-replica monotone version flip, and outputs pinned EXACTLY equal to
the single-process ``--mode serve`` path."""

import copy
import dataclasses
import json
import os
import socket
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

from dml_cnn_cifar10_tpu.config import TrainConfig
from dml_cnn_cifar10_tpu.fleet import autoscaler as autoscaler_lib
from dml_cnn_cifar10_tpu.fleet import publisher as publisher_lib
from dml_cnn_cifar10_tpu.fleet import router as router_lib
from dml_cnn_cifar10_tpu.parallel import cluster as cluster_lib


class FakeLogger:
    def __init__(self):
        self.records = []

    def log(self, kind, **fields):
        self.records.append({"kind": kind, **fields})

    def flush(self):
        pass

    def kinds(self):
        return [r["kind"] for r in self.records]


def _view(rid, port=1000, version="1", depth=0, phase="serve",
          age=0.1):
    return router_lib.ReplicaView(replica_id=rid, port=port,
                                  version=version, queue_depth=depth,
                                  phase=phase, age_s=age)


# ---------------------------------------------------------------------------
# router placement/eviction logic (pure)
# ---------------------------------------------------------------------------

def test_live_views_filters_stale_warmup_portless_and_excluded():
    views = [_view(0),
             _view(1, age=9.9),            # stale heartbeat
             _view(2, phase="warmup"),     # not ready
             _view(3, port=None),          # never advertised a port
             _view(4),
             _view(5, phase="drain")]      # retiring
    live = router_lib.live_views(views, dead_after_s=3.0, exclude={4})
    assert [v.replica_id for v in live] == [0]


def test_pick_replica_least_depth_then_round_robin():
    views = [_view(0, depth=3), _view(1, depth=0), _view(2, depth=0)]
    assert router_lib.pick_replica(views, rr=0).replica_id == 1
    assert router_lib.pick_replica(views, rr=1).replica_id == 2
    assert router_lib.pick_replica(views, rr=2).replica_id == 1
    # Loaded replica only picked once the idle ones are excluded.
    only = [_view(0, depth=3)]
    assert router_lib.pick_replica(only, rr=7).replica_id == 0
    assert router_lib.pick_replica([], rr=0) is None


def test_router_evicts_stale_replica_and_reroutes_membership(tmp_path):
    log = FakeLogger()
    store0 = cluster_lib.HeartbeatStore(str(tmp_path), 0)
    store1 = cluster_lib.HeartbeatStore(str(tmp_path), 1)
    r = router_lib.Router(str(tmp_path), dead_after_s=0.5, logger=log)
    store0.publish(0, "serve", extra={"port": 1111, "version": "1",
                                      "queue_depth": 0})
    store1.publish(0, "serve", extra={"port": 2222, "version": "1",
                                      "queue_depth": 0})
    assert sorted(v.replica_id for v in r.live()) == [0, 1]
    time.sleep(0.6)
    store0.publish(1, "serve", extra={"port": 1111, "version": "1",
                                      "queue_depth": 0})  # 0 stays fresh
    live = r.live()
    assert [v.replica_id for v in live] == [0]
    lost = [rec for rec in log.records if rec["kind"] == "peer_lost"]
    assert lost and lost[0]["process_id"] == 1
    assert lost[0]["reason"] == "replica_evicted_stale_heartbeat"
    # Eviction is sticky: a late beat does not silently rejoin.
    store1.publish(5, "serve", extra={"port": 2222, "version": "1",
                                      "queue_depth": 0})
    assert [v.replica_id for v in r.live()] == [0]
    # healthz reflects the membership view.
    hz = r.healthz()
    assert hz["live"] == 1 and hz["replicas"]["1"]["live"] is False


def test_router_drain_excludes_from_routing_until_forgotten(tmp_path):
    """Retirement half-step: a draining replica takes no NEW requests
    (it finishes what it has via its own SIGTERM drain), and forget()
    clears the bookkeeping once the process is gone."""
    store = cluster_lib.HeartbeatStore(str(tmp_path), 0)
    r = router_lib.Router(str(tmp_path), dead_after_s=5.0)
    store.publish(0, "serve", extra={"port": 1111, "version": "1",
                                     "queue_depth": 0})
    assert [v.replica_id for v in r.live()] == [0]
    r.drain_replica(0)
    assert r.live() == []
    r.forget(0)
    assert [v.replica_id for v in r.live()] == [0]


def test_beat_extra_payload_roundtrip(tmp_path):
    store = cluster_lib.HeartbeatStore(str(tmp_path), 3)
    store.publish(17, "serve", extra={"port": 9000, "version": "12",
                                      "queue_depth": 4})
    beats = cluster_lib.HeartbeatStore(str(tmp_path), 0).read_all()
    assert set(beats) == {3}           # only 3 published
    beat = beats[3]
    assert beat.step == 17 and beat.phase == "serve"
    assert beat.extra == {"port": 9000, "version": "12",
                          "queue_depth": 4}
    view = router_lib.view_from_beat(beat)
    assert view.port == 9000 and view.version == "12" \
        and view.queue_depth == 4


# ---------------------------------------------------------------------------
# autoscaler decision table (pure)
# ---------------------------------------------------------------------------

def _sig(live=2, starting=0, depth=0.0, shed=0.0, p99=None):
    return autoscaler_lib.FleetSignals(
        live=live, starting=starting, mean_queue_depth=depth,
        shed_fraction=shed, p99_ms=p99)


def test_autoscaler_decision_table():
    d = autoscaler_lib.decide
    # Below the floor: always up — the self-healing path.
    assert d(_sig(live=1), 2, 4).action == "up"
    assert d(_sig(live=1), 2, 4).reason == "below_min"
    # A spawn in flight counts: no second spawn for the same gap.
    assert d(_sig(live=1, starting=1), 2, 4).action == "hold"
    # Shedding scales up...
    assert d(_sig(shed=0.05), 2, 4).reason == "shedding"
    # ...but never past the ceiling.
    assert d(_sig(live=4, shed=0.5), 2, 4).action == "hold"
    # SLO violation scales up; no SLO configured means no signal.
    assert d(_sig(p99=80.0), 2, 4, slo_ms=50.0).reason == \
        "slo_violation"
    assert d(_sig(p99=80.0), 2, 4, slo_ms=None).action == "hold"
    # Queue backpressure scales up.
    assert d(_sig(depth=9.0), 2, 4).reason == "queue_depth"
    # All quiet above the floor: retire one.
    assert d(_sig(live=3), 2, 4).action == "down"
    assert d(_sig(live=3), 2, 4).reason == "idle"
    # Quiet-but-at-floor holds; barely-inside-SLO holds (down needs
    # comfortably inside).
    assert d(_sig(live=2), 2, 4).action == "hold"
    assert d(_sig(live=3, p99=40.0), 2, 4, slo_ms=50.0).action == \
        "hold"
    assert d(_sig(live=3, p99=10.0), 2, 4, slo_ms=50.0).action == \
        "down"


def test_aggregate_signals_reads_serve_windows(tmp_path):
    tele = tmp_path / "telemetry"
    tele.mkdir()
    (tele / "replica_0.jsonl").write_text(
        json.dumps({"kind": "serve", "t": 1.0, "task": 0,
                    "requests": 90, "completed": 80, "shed_queue": 10,
                    "shed_deadline": 0, "qps": 8.0, "p50_ms": 5.0,
                    "p95_ms": 9.0, "p99_ms": 40.0, "batch_fill": 0.5,
                    "window_s": 10.0}) + "\n")
    views = [_view(0, depth=4), _view(1, depth=2)]
    sig = autoscaler_lib.aggregate_signals(views, starting=1,
                                           telemetry_dir=str(tele))
    assert sig.live == 2 and sig.starting == 1
    assert sig.mean_queue_depth == 3.0
    assert sig.shed_fraction == pytest.approx(10 / 90)
    assert sig.p99_ms == 40.0


# ---------------------------------------------------------------------------
# checkpoint publishing: the integrity-sidecar gate
# ---------------------------------------------------------------------------

def _toy_state(scale=1.0):
    return {"w": (np.arange(8, dtype=np.float32) * scale),
            "b": np.float32(scale)}


def test_publish_gate_requires_verifiable_sidecar(tmp_path):
    from dml_cnn_cifar10_tpu.ckpt import checkpoint as ckpt_lib

    ckpt_dir = str(tmp_path / "ckpts")
    fleet_dir = str(tmp_path / "fleet")
    path1 = ckpt_lib.save_checkpoint(ckpt_dir, _toy_state(), 10)
    # Committed save → sidecar exists → publishable.
    rec = publisher_lib.publish_checkpoint(fleet_dir, path1, 10)
    assert rec is not None and rec.seq == 1 and rec.version == "10"
    got = publisher_lib.read_published(fleet_dir)
    assert got == rec
    # Older-or-equal steps never roll the published version back.
    assert publisher_lib.publish_checkpoint(fleet_dir, path1, 10) is None
    # No sidecar → not publishable (stricter than restore).
    bare = os.path.join(ckpt_dir, "ckpt_20.msgpack")
    with open(path1, "rb") as f:
        payload = f.read()
    with open(bare, "wb") as f:
        f.write(payload)
    assert publisher_lib.publish_checkpoint(fleet_dir, bare, 20) is None
    # Corrupt bytes under a valid-looking sidecar → not publishable.
    path3 = ckpt_lib.save_checkpoint(ckpt_dir, _toy_state(2.0), 30)
    with open(path3, "r+b") as f:
        f.truncate(os.path.getsize(path3) // 2)
    assert publisher_lib.publish_checkpoint(fleet_dir, path3, 30) is None
    assert publisher_lib.read_published(fleet_dir).step == 10


def test_directory_publisher_skips_bad_latest(tmp_path):
    from dml_cnn_cifar10_tpu.ckpt import checkpoint as ckpt_lib

    ckpt_dir = str(tmp_path / "ckpts")
    fleet_dir = str(tmp_path / "fleet")
    ckpt_lib.save_checkpoint(ckpt_dir, _toy_state(), 10, keep=10)
    path2 = ckpt_lib.save_checkpoint(ckpt_dir, _toy_state(2.0), 20,
                                     keep=10)
    with open(path2, "r+b") as f:
        f.truncate(os.path.getsize(path2) // 2)   # corrupt the newest
    pub = publisher_lib.DirectoryPublisher(ckpt_dir, fleet_dir)
    rec = pub.scan_once()
    # The corrupt newest is skipped (and remembered); the older
    # verifiable checkpoint is published instead.
    assert rec is not None and rec.step == 10
    assert pub.scan_once() is None                # nothing new
    ckpt_lib.save_checkpoint(ckpt_dir, _toy_state(3.0), 30, keep=10)
    rec = pub.scan_once()
    assert rec.step == 30 and rec.seq == 2


# ---------------------------------------------------------------------------
# the engine hot-swap seam
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def swap_setup():
    from dml_cnn_cifar10_tpu.config import DataConfig, ModelConfig
    from dml_cnn_cifar10_tpu.models.registry import get_model

    model_def = get_model("cnn")
    model_cfg = ModelConfig(logit_relu=False)
    data_cfg = DataConfig(normalize="scale")
    p1 = model_def.init(jax.random.key(0), model_cfg, data_cfg)
    p2 = jax.tree.map(lambda x: x * 1.25, p1)
    return model_def, model_cfg, data_cfg, p1, p2


def test_try_swap_installs_matching_params(swap_setup, rng):
    from dml_cnn_cifar10_tpu.serve.engine import ServingEngine

    model_def, model_cfg, data_cfg, p1, p2 = swap_setup
    log = FakeLogger()
    eng = ServingEngine.from_params(model_def, model_cfg, data_cfg, p1,
                                    logger=log, version="1")
    ref2 = ServingEngine.from_params(model_def, model_cfg, data_cfg, p2,
                                     version="2")
    img = rng.integers(0, 256, (1, 32, 32, 3)).astype(np.uint8)
    out1, _, v1 = eng.forward_timed_versioned(img)
    assert v1 == "1"
    ok, reason = eng.try_swap(p2, version="2")
    assert ok, reason
    out2, _, v2 = eng.forward_timed_versioned(img)
    assert v2 == "2" and eng.version == "2" and eng.swap_count == 1
    want2, _ = ref2.forward_timed(img)
    assert np.array_equal(out2, want2)       # the NEW weights, exactly
    assert not np.array_equal(out1, out2)    # and they actually differ
    swaps = [r for r in log.records if r["kind"] == "swap"]
    assert swaps and swaps[0]["version"] == "2" \
        and swaps[0]["from_version"] == "1" \
        and swaps[0]["swap_ms"] >= 0


def test_try_swap_rejects_mismatched_candidate(swap_setup, rng):
    from dml_cnn_cifar10_tpu.serve.engine import ServingEngine

    model_def, model_cfg, data_cfg, p1, _ = swap_setup
    log = FakeLogger()
    eng = ServingEngine.from_params(model_def, model_cfg, data_cfg, p1,
                                    logger=log, version="1")
    img = rng.integers(0, 256, (1, 32, 32, 3)).astype(np.uint8)
    want, _ = eng.forward_timed(img)

    # Wrong leaf shape (a differently-sized model's checkpoint).
    leaves, treedef = jax.tree.flatten(p1)
    leaves[0] = np.zeros((3, 3), np.float32)
    bad_shape = jax.tree.unflatten(treedef, leaves)
    ok, reason = eng.try_swap(bad_shape, version="9")
    assert not ok and "leaf" in reason
    # Wrong dtype with right shapes.
    bad_dtype = jax.tree.map(lambda x: np.asarray(x, np.float64), p1)
    ok, reason = eng.try_swap(bad_dtype, version="9")
    assert not ok
    # Wrong tree structure entirely.
    ok, reason = eng.try_swap({"nope": np.zeros(3, np.float32)},
                              version="9")
    assert not ok and "structure" in reason

    rejects = [r for r in log.records if r["kind"] == "swap_rejected"]
    assert len(rejects) == 3 and all(r["version"] == "9"
                                     for r in rejects)
    assert not [r for r in log.records if r["kind"] == "swap"]
    # The old version never stopped serving, bit-identically.
    got, _, v = eng.forward_timed_versioned(img)
    assert v == "1" and eng.swap_count == 0
    assert np.array_equal(got, want)


def test_artifact_engine_refuses_swap(swap_setup):
    from dml_cnn_cifar10_tpu import export as export_lib
    from dml_cnn_cifar10_tpu.serve.engine import ServingEngine

    model_def, model_cfg, data_cfg, p1, _ = swap_setup
    blob = export_lib.export_forward(model_def, model_cfg, data_cfg, p1,
                                     platforms=["cpu"])
    log = FakeLogger()
    eng = ServingEngine.from_artifact(blob=blob, logger=log)
    ok, reason = eng.try_swap(p1, version="2")
    assert not ok and "artifact" in reason
    assert [r["kind"] for r in log.records] == ["swap_rejected"]


def test_batcher_tags_rows_with_version(swap_setup, rng):
    from dml_cnn_cifar10_tpu.serve import MicroBatcher, VersionedLogits
    from dml_cnn_cifar10_tpu.serve.engine import ServingEngine

    model_def, model_cfg, data_cfg, p1, p2 = swap_setup
    eng = ServingEngine.from_params(model_def, model_cfg, data_cfg, p1,
                                    version="1")
    img = rng.integers(0, 256, (32, 32, 3)).astype(np.uint8)
    with MicroBatcher(eng, buckets=(1,)) as b:
        row = b.submit(img).result(timeout=60)
        assert isinstance(row, VersionedLogits) and row.version == "1"
        assert eng.try_swap(p2, version="2")[0]
        row2 = b.submit(img).result(timeout=60)
        assert row2.version == "2"
        assert not np.array_equal(np.asarray(row), np.asarray(row2))


# ---------------------------------------------------------------------------
# satellites: JSONL kinds, report section, loadgen mixes, CLI plumb
# ---------------------------------------------------------------------------

def test_fleet_jsonl_kinds_pass_schema_lint(tmp_path):
    from tools import check_jsonl_schema

    from dml_cnn_cifar10_tpu.utils.logging import MetricsLogger

    path = str(tmp_path / "fleet.jsonl")
    logger = MetricsLogger(jsonl_path=path)
    logger.log("fleet", replicas=2, live=2, routed=100, rerouted=1,
               evictions=1, shed=0, version_mix={"1": 60, "2": 40},
               window_s=5.0)
    logger.log("fleet_done", replicas=2, live=2, routed=100,
               rerouted=1, evictions=1, shed=0, version_mix={},
               window_s=9.0)
    logger.log("swap", replica_id=0, version="2", from_version="1",
               swap_ms=3.2)
    logger.log("swap_rejected", replica_id=1, version="3",
               reason="leaf 0: have (3,)/float32, candidate "
                      "(4,)/float32")
    logger.log("scale", action="up", reason="below_min", replicas=2)
    logger.log("fleet_publish", seq=2, version="20", step=20,
               path="/x/ckpt_20.msgpack")
    logger.close()
    assert check_jsonl_schema.check_file(path, strict=True) == []


def test_telemetry_report_prints_fleet_section(tmp_path):
    from tools import telemetry_report

    path = str(tmp_path / "fleet.jsonl")
    recs = [
        {"kind": "fleet", "t": 1.0, "task": 0, "replicas": 2, "live": 2,
         "routed": 50, "rerouted": 0, "evictions": 0, "shed": 0,
         "version_mix": {"1": 50}, "window_s": 2.0},
        {"kind": "fleet", "t": 3.0, "task": 0, "replicas": 3, "live": 1,
         "routed": 40, "rerouted": 2, "evictions": 1, "shed": 0,
         "version_mix": {"1": 10, "2": 30}, "window_s": 2.0},
        {"kind": "swap", "t": 2.5, "task": 0, "replica_id": 0,
         "version": "2", "from_version": "1", "swap_ms": 4.0},
        {"kind": "scale", "t": 2.6, "task": 0, "action": "up",
         "reason": "below_min", "replicas": 2},
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    out = telemetry_report.summarize(path)
    assert "fleet health" in out
    assert "1 hot-swap(s)" in out
    assert "autoscale up (below_min)" in out
    assert "re-routed" in out and "eviction" in out


def test_loadgen_mix_rows(tmp_path):
    """Mixes produce one BENCH-style row each; the adversarial mix
    rejects oversize requests without failing well-formed ones; every
    row carries a version_mix."""
    import tools.loadgen as loadgen

    report_path = str(tmp_path / "mix_report.json")
    assert loadgen.main([
        "--mix", "steady,diurnal,adversarial", "--qps", "60",
        "--duration_s", "0.7", "--buckets", "1,8",
        "--report", report_path]) == 0
    with open(report_path) as f:
        report = json.load(f)
    rows = {r["mix"]: r for r in report["mixes"]}
    assert set(rows) == {"steady", "diurnal", "adversarial"}
    for row in rows.values():
        assert row["completed"] > 0
        assert row["requests"] == row["completed"] + row["shed"]
        assert row["latency_ms"]["p50"] > 0
        assert row["latency_ms"]["p99"] >= row["latency_ms"]["p50"]
        assert row["version_mix"]    # every completion tagged
    assert rows["adversarial"]["rejected"] > 0
    assert rows["steady"]["rejected"] == 0


def test_cli_fleet_flags_plumb_into_config():
    from dml_cnn_cifar10_tpu.cli.main import (build_parser,
                                              config_from_args)

    args, _ = build_parser().parse_known_args([
        "--mode", "fleet", "--fleet_min_replicas", "3",
        "--fleet_max_replicas", "5", "--fleet_port", "0",
        "--fleet_dir", "/x/fleet", "--fleet_autoscale", "false",
        "--fleet_replica_dead_after_s", "7.5", "--fleet_publish",
        "true", "--serve_slo_ms", "25"])
    cfg = config_from_args(args)
    assert cfg.fleet.min_replicas == 3
    assert cfg.fleet.max_replicas == 5
    assert cfg.fleet.port == 0
    assert cfg.fleet.dir == "/x/fleet"
    assert cfg.fleet.autoscale is False
    assert cfg.fleet.replica_dead_after_s == 7.5
    assert cfg.fleet.publish is True
    assert cfg.serve.slo_ms == 25
    with pytest.raises(SystemExit, match="min <= max"):
        config_from_args(build_parser().parse_known_args(
            ["--fleet_min_replicas", "4",
             "--fleet_max_replicas", "2"])[0])


def test_controller_router_and_http_loadgen_never_start_a_backend(tmp_path):
    """A chip belongs to one process at a time, so the fleet's parent
    (controller + router threads) and the HTTP load generator must stay
    off JAX's backends: the workers are the only processes that may open
    the chip. Proven by running them under a platform that does not
    exist — any backend initialization would raise."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = f"""
import os, sys
from dml_cnn_cifar10_tpu.config import TrainConfig
from dml_cnn_cifar10_tpu.fleet.controller import FleetController
from tools import loadgen
cfg = TrainConfig(log_dir=r"{tmp_path}/logs")
cfg.fleet.dir = r"{tmp_path}/fleet"
cfg.fleet.min_replicas = 1
cfg.fleet.port = 0
c = FleetController(cfg)
port = c.start()
try:
    c.tick()
    loadgen.main(["--target", f"http://127.0.0.1:{{port}}",
                  "--duration_s", "0.3", "--concurrency", "2",
                  "--report", r"{tmp_path}/report.json"])
finally:
    c.shutdown()
import jax
try:
    jax.devices()
except RuntimeError:
    print("NO_BACKEND_WAS_EVER_STARTED")
"""
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform",
               PYTHONPATH=repo)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "NO_BACKEND_WAS_EVER_STARTED" in out.stdout


def test_cli_refuses_several_replicas_on_one_accelerator_host(monkeypatch):
    """One process for each chip: every fleet worker is a process that
    opens the accelerator, so on a TPU host a second replica could only
    die or hang at backend start-up. The CLI says so at flag parse
    (the CPU simulation above keeps its many workers)."""
    from dml_cnn_cifar10_tpu.cli.main import (build_parser,
                                              config_from_args)
    from dml_cnn_cifar10_tpu.utils import platform as platform_lib

    monkeypatch.setattr(platform_lib, "accelerator_expected", lambda: True)

    def parse(*extra):
        return config_from_args(build_parser().parse_known_args(
            ["--mode", "fleet", *extra])[0])

    with pytest.raises(SystemExit, match=r"one\s+process at a time"):
        parse("--fleet_max_replicas", "2")
    assert parse("--fleet_min_replicas", "1", "--fleet_max_replicas",
                 "1").fleet.max_replicas == 1


# ---------------------------------------------------------------------------
# acceptance smoke: 2 workers + router; worker kill, then hot-swap —
# zero failed client requests throughout, outputs pinned to --mode serve
# ---------------------------------------------------------------------------

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _fleet_cfg(tmp_path, data_cfg) -> TrainConfig:
    cfg = TrainConfig(
        log_dir=str(tmp_path / "logs"),
        metrics_jsonl=str(tmp_path / "router.jsonl"),
        data=dataclasses.replace(data_cfg, normalize="scale"),
    )
    cfg.model.logit_relu = False
    cfg.serve.buckets = (1, 4)
    cfg.serve.batch_window_ms = 1.0
    cfg.serve.metrics_every_s = 0.5
    cfg.serve.drain_deadline_s = 5.0
    cfg.fleet.dir = str(tmp_path / "fleet")
    cfg.fleet.port = _free_port()
    cfg.fleet.min_replicas = 2
    cfg.fleet.max_replicas = 3
    cfg.fleet.heartbeat_interval_s = 0.1
    cfg.fleet.replica_dead_after_s = 1.5
    cfg.fleet.swap_poll_s = 0.1
    cfg.fleet.publish_poll_s = 0.2
    cfg.fleet.autoscale_every_s = 0.5
    cfg.fleet.scale_cooldown_s = 2.0
    cfg.fleet.metrics_every_s = 0.5
    return cfg


def _save_ckpt(cfg, host_state, step, scale=1.0):
    """Commit a checkpoint at ``step`` (params scaled so versions are
    numerically distinguishable), sidecar included."""
    from dml_cnn_cifar10_tpu.ckpt import checkpoint as ckpt_lib

    opt = dict(host_state.opt)
    opt["step"] = np.asarray(opt["step"]) * 0 + step
    params = jax.tree.map(lambda x: np.asarray(x * scale, x.dtype),
                          host_state.params)
    return ckpt_lib.save_checkpoint(
        cfg.log_dir, host_state._replace(opt=opt, params=params), step,
        keep=10)


#: The single-process ``--mode serve`` reference path, run in a FRESH
#: subprocess with the workers' environment: resolve_engine over the
#: latest checkpoint, one bucket-1 forward per image. In-process
#: reference computation would be polluted by whatever jax state the
#: rest of the suite left behind (device count, config leaks) — the
#: acceptance pin is fleet-vs-serve, both as real deployments.
_REF_SCRIPT = """
import json, sys
from dml_cnn_cifar10_tpu.utils.platform import force_cpu
force_cpu()
import numpy as np
from dml_cnn_cifar10_tpu.config import config_from_dict
with open(sys.argv[1]) as f:
    cfg = config_from_dict(json.load(f))
cfg.metrics_jsonl = None
from dml_cnn_cifar10_tpu.serve.server import resolve_engine
eng = resolve_engine(cfg)
imgs = np.load(sys.argv[2])
out = {}
for i in range(imgs.shape[0]):
    logits, _ = eng.forward_timed(imgs[i:i + 1])
    out[i] = [float(v) for v in logits[0]]
print("RESULT " + json.dumps({"version": eng.version, "logits": out}))
"""


def _serve_path_logits(cfg, tmp_path, images):
    import subprocess
    import sys as _sys

    from dml_cnn_cifar10_tpu.config import config_to_dict

    script = tmp_path / "serve_ref.py"
    script.write_text(_REF_SCRIPT)
    cfg_path = tmp_path / "serve_ref_cfg.json"
    cfg_path.write_text(json.dumps(config_to_dict(cfg)))
    imgs_path = tmp_path / "serve_ref_imgs.npy"
    np.save(imgs_path, images)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, XLA_FLAGS="")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [_sys.executable, str(script), str(cfg_path), str(imgs_path)],
        capture_output=True, text=True, timeout=240, env=env, cwd=repo)
    assert proc.returncode == 0, \
        f"serve reference run failed:\n{proc.stdout}\n{proc.stderr}"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    res = json.loads(lines[-1][len("RESULT "):])
    return res["version"], {int(k): v for k, v in res["logits"].items()}


def _predict(port: int, img: np.ndarray) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict", data=img.tobytes(),
        headers={"Content-Type": "application/octet-stream"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def _healthz(port: int) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
        return json.loads(resp.read())


def _worker_log_tails(fleet_dir: str) -> str:
    tele = os.path.join(fleet_dir, "telemetry")
    out = []
    if os.path.isdir(tele):
        for name in sorted(os.listdir(tele)):
            if name.endswith(".log"):
                with open(os.path.join(tele, name), errors="replace") as f:
                    out.append(f"--- {name} ---\n" + f.read()[-3000:])
    return "\n".join(out)


def test_fleet_survives_kill_and_hot_swaps_zero_failures(
        tmp_path, data_cfg, monkeypatch, rng):
    from dml_cnn_cifar10_tpu.ckpt import checkpoint as ckpt_lib
    from dml_cnn_cifar10_tpu.fleet.controller import main_fleet
    from dml_cnn_cifar10_tpu.train.loop import Trainer

    # Workers are fresh processes: single CPU device (the 8-virtual-
    # device XLA flag is this test process's mesh, not theirs).
    monkeypatch.setenv("XLA_FLAGS", "")
    cfg = _fleet_cfg(tmp_path, data_cfg)
    # Replica 1 dies abruptly (host_lost: os._exit, no cleanup, no
    # drain) at its 15th traffic dispatch.
    cfg.fleet.worker_fault = "1:host_lost@15"

    # Seed checkpoint: version "1".
    seed_cfg = copy.deepcopy(cfg)
    seed_cfg.metrics_jsonl = None
    trainer = Trainer(seed_cfg)
    host_state = ckpt_lib.fetch_to_host(trainer.init_or_restore())
    _save_ckpt(cfg, host_state, 1)

    images = rng.integers(0, 256, (4, 32, 32, 3)).astype(np.uint8)
    v1, direct1 = _serve_path_logits(cfg, tmp_path, images)
    assert v1 == "1"

    ready, stop = threading.Event(), threading.Event()
    rc = {}
    t = threading.Thread(
        target=lambda: rc.setdefault("rc", main_fleet(
            cfg, ready_event=ready, stop_event=stop)),
        daemon=True)
    t.start()
    port = cfg.fleet.port
    responses = []   # (replica_id, version) in client order
    try:
        assert ready.wait(60), "router never became ready"
        deadline = time.time() + 240
        while time.time() < deadline:
            if _healthz(port)["live"] >= 2:
                break
            time.sleep(0.5)
        else:
            pytest.fail("fleet never reached 2 live replicas\n"
                        + _worker_log_tails(cfg.fleet.dir))

        # Phase A: sustained load across both replicas; replica 1 dies
        # mid-way; every request must succeed on version "1" with
        # logits EXACTLY the single-process serve path's.
        for i in range(80):
            resp = _predict(port, images[i % 4])
            assert "class" in resp, f"request {i} failed: {resp}"
            assert resp["version"] == "1"
            assert resp["logits"] == direct1[i % 4], \
                f"fleet output diverged from --mode serve at req {i}"
            responses.append((resp["replica_id"], resp["version"]))
            time.sleep(0.01)
        assert len({rid for rid, _ in responses}) >= 2, \
            "load never reached the second replica"
        # The kill actually happened and was re-routed, not surfaced.
        hz = _healthz(port)
        assert hz["replicas"]["1"]["live"] is False, \
            "replica 1 was never killed/evicted\n" \
            + _worker_log_tails(cfg.fleet.dir)

        # Phase B: publish version "2" (the directory publisher picks
        # it up; workers hot-swap between micro-batches). Zero request
        # errors during the swap; versions flip monotonically
        # per-replica; outputs pin to the new serve path.
        _save_ckpt(cfg, host_state, 2, scale=1.25)
        v2, direct2 = _serve_path_logits(cfg, tmp_path, images)
        assert v2 == "2"
        flip_deadline = time.time() + 90
        consecutive_new = 0
        i = 0
        while consecutive_new < 20:
            assert time.time() < flip_deadline, \
                "fleet never converged to version 2\n" \
                + _worker_log_tails(cfg.fleet.dir)
            resp = _predict(port, images[i % 4])
            assert "class" in resp, f"request failed mid-swap: {resp}"
            assert resp["version"] in ("1", "2")
            if resp["version"] == "2":
                consecutive_new += 1
                assert resp["logits"] == direct2[i % 4], \
                    "post-swap fleet output diverged from --mode serve"
            else:
                consecutive_new = 0
            responses.append((resp["replica_id"], resp["version"]))
            i += 1
            time.sleep(0.01)

        # Per-replica monotone flip: once a replica answers "2" it
        # never answers "1" again.
        seen_new = set()
        for rid, version in responses:
            if version == "2":
                seen_new.add(rid)
            else:
                assert rid not in seen_new, \
                    f"replica {rid} answered version 1 after 2"
    finally:
        stop.set()
        t.join(120)
    assert not t.is_alive(), "fleet loop did not exit on stop"
    assert rc.get("rc") == 0

    # Stream checks: the router's JSONL passes the schema lint and
    # records the eviction + the self-healing scale-up; the report CLI
    # prints the fleet-health section; replica streams lint too.
    from tools import check_jsonl_schema, telemetry_report
    assert check_jsonl_schema.check_file(cfg.metrics_jsonl, strict=True) == []
    with open(cfg.metrics_jsonl) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    kinds = {r["kind"] for r in recs}
    assert "fleet" in kinds and "fleet_done" in kinds
    lost = [r for r in recs if r["kind"] == "peer_lost"]
    assert any(r["process_id"] == 1 for r in lost)
    scale_ups = [r for r in recs if r["kind"] == "scale"
                 and r["action"] == "up" and r["reason"] == "below_min"]
    assert scale_ups, "the dead replica was never replaced"
    report = telemetry_report.summarize(cfg.metrics_jsonl)
    assert "fleet health" in report
    tele = os.path.join(cfg.fleet.dir, "telemetry")
    replica0 = os.path.join(tele, "replica_0.jsonl")
    assert check_jsonl_schema.check_file(replica0, strict=True) == []
    with open(replica0) as f:
        r0 = [json.loads(ln) for ln in f if ln.strip()]
    swaps = [r for r in r0 if r["kind"] == "swap"]
    assert swaps and swaps[0]["version"] == "2" \
        and swaps[0]["from_version"] == "1"
