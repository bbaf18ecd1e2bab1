"""Run-health telemetry layer (utils/telemetry.py + parallel/step.py
health metrics + the JSONL schema lint): span nesting/export, goodput
accounting, health-metric fusion into the fused boundary fetch, and the
zero-extra-device-fetches contract when telemetry is off."""

import json
import os
import time

import numpy as np
import pytest

from dml_cnn_cifar10_tpu.utils.telemetry import (GOODPUT_CATEGORIES,
                                                 SpanTracer,
                                                 flush_boundary, hbm_stats)
from tests.conftest import tiny_train_cfg


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_span_nesting_and_drain(monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(time, "perf_counter", clock)
    tr = SpanTracer(enabled=True)
    tr.start()
    with tr.span("outer", cat="eval"):
        clock.t += 1.0
        with tr.span("inner"):
            clock.t += 0.5
        clock.t += 0.5
    spans = tr.drain()
    # Inner finishes first; depth recorded at entry.
    assert [(s[0], s[4]) for s in spans] == [("inner", 1), ("outer", 0)]
    name, cat, start, dur, depth, thread = spans[1]
    assert cat == "eval" and start == 0.0 and dur == pytest.approx(2.0)
    assert thread is None      # the tracer's own thread is not named
    # drain() forgets — a second drain is empty.
    assert tr.drain() == []


def test_disabled_tracer_is_noop():
    tr = SpanTracer(enabled=False)
    # The fast path returns one shared no-op context manager: no
    # allocation, no clock read, nothing recorded.
    cm = tr.span("anything", cat="eval")
    assert tr.span("other") is cm
    with cm:
        pass
    assert tr.drain() == []


def test_goodput_fractions_sum_to_one(monkeypatch):
    """Synthetic timeline: categorized spans attribute their seconds,
    productive training is the remainder, fractions sum to 1.0."""
    clock = _FakeClock()
    monkeypatch.setattr(time, "perf_counter", clock)
    tr = SpanTracer(enabled=True)
    tr.start()
    for cat, dur in (("compile", 2.0), ("data", 1.0), ("eval", 0.5),
                     ("checkpoint", 0.4), ("sync", 0.1)):
        with tr.span(cat, cat=cat):
            clock.t += dur
    clock.t = 10.0
    gp = tr.goodput()
    assert gp["total_s"] == pytest.approx(10.0)
    assert gp["compile_frac"] == pytest.approx(0.2)
    assert gp["data_frac"] == pytest.approx(0.1)
    assert gp["eval_frac"] == pytest.approx(0.05)
    assert gp["checkpoint_frac"] == pytest.approx(0.04)
    assert gp["sync_frac"] == pytest.approx(0.01)
    assert gp["train_frac"] == pytest.approx(0.6)
    total_frac = gp["train_frac"] + sum(
        gp[f"{c}_frac"] for c in GOODPUT_CATEGORIES)
    assert total_frac == pytest.approx(1.0, abs=1e-5)
    # Nested spans with a category must NOT double-count their parent.
    with tr.span("eval", cat="eval"):
        with tr.span("inner", cat="eval"):
            clock.t += 1.0
    assert tr._cat_secs["eval"] == pytest.approx(0.5 + 1.0)


def test_span_ring_overflow_counts_dropped_and_drains_newest():
    tr = SpanTracer(enabled=True, max_spans=4)
    for i in range(6):
        with tr.span(f"s{i}", cat="data"):
            pass
    assert tr.dropped == 2
    assert [s[0] for s in tr.drain()] == ["s2", "s3", "s4", "s5"]
    # A drained ring has room again: nothing more is dropped.
    with tr.span("s6", cat="data"):
        pass
    assert tr.dropped == 2 and [s[0] for s in tr.drain()] == ["s6"]


def test_hbm_stats_shape():
    """Emitted unconditionally: on backends without memory stats (CPU)
    the record still carries the full schema with available=False."""
    s = hbm_stats()
    assert set(s) == {"available", "devices", "bytes_in_use",
                      "peak_bytes", "bytes_limit"}
    assert isinstance(s["available"], bool)


def test_flush_boundary_logs_span_goodput_hbm(tmp_path):
    from dml_cnn_cifar10_tpu.utils.logging import MetricsLogger

    path = str(tmp_path / "m.jsonl")
    logger = MetricsLogger(path)
    tr = SpanTracer(enabled=True)
    with tr.span("eval", cat="eval"):
        pass
    flush_boundary(tr, logger, step=7, final=True)
    logger.close()
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    kinds = [r["kind"] for r in recs]
    assert kinds == ["span", "goodput", "hbm"]
    assert recs[0]["name"] == "eval" and recs[0]["step"] == 7
    assert recs[1]["final"] == 1
    # Disabled tracer: flush is a no-op (no records, no fetches).
    flush_boundary(SpanTracer(enabled=False), logger, step=8)


def test_health_stats_in_step_metrics(data_cfg):
    """health_metrics=True compiles the scalars into the step's metrics
    dict (the fused-fetch payload); off means the keys don't exist."""
    import jax

    from dml_cnn_cifar10_tpu.config import ModelConfig, OptimConfig
    from dml_cnn_cifar10_tpu.models.registry import get_model
    from dml_cnn_cifar10_tpu.parallel import step as step_lib

    model_cfg = ModelConfig(logit_relu=False)
    optim_cfg = OptimConfig(learning_rate=0.05)
    model_def = get_model("cnn")
    state = step_lib.init_train_state(
        jax.random.key(0), model_def, model_cfg, data_cfg, optim_cfg)
    images = np.random.default_rng(0).normal(
        size=(8, data_cfg.crop_height, data_cfg.crop_width, 3)
    ).astype(np.float32)
    labels = np.arange(8, dtype=np.int32) % 10

    plain = step_lib.make_train_step(model_def, model_cfg, optim_cfg)
    _, metrics = plain(state, images, labels)
    assert not any(k.startswith("health_") for k in metrics)

    state2 = step_lib.init_train_state(
        jax.random.key(0), model_def, model_cfg, data_cfg, optim_cfg)
    healthy = step_lib.make_train_step(model_def, model_cfg, optim_cfg,
                                       health_metrics=True)
    _, metrics = healthy(state2, images, labels)
    gn = float(metrics["health_grad_norm"])
    pn = float(metrics["health_param_norm"])
    ur = float(metrics["health_update_ratio"])
    assert gn > 0 and pn > 0 and 0 < ur < 1
    # SGD: ||Δθ|| = lr·||g|| exactly, so the ratio is checkable.
    assert ur == pytest.approx(optim_cfg.learning_rate * gn / pn,
                               rel=1e-4)


def test_telemetry_run_and_fetch_parity(data_cfg, tmp_path, monkeypatch):
    """One telemetry-off and one telemetry+health-on run of the real
    Trainer: (a) telemetry must add ZERO jax.device_get calls (spans,
    goodput, and hbm are host-side; health rides the fused fetch);
    (b) the on-run emits span/goodput/hbm records whose goodput
    categories sum to within 2% of the recorded wall-clock, a valid
    Chrome trace, health keys in the train records, a schema-clean JSONL
    stream, and a telemetry_report summary."""
    import jax

    from dml_cnn_cifar10_tpu.train.loop import Trainer

    counts = {"n": 0}
    real_get = jax.device_get

    def counting_get(x):
        counts["n"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)

    def run(sub, telemetry, health):
        cfg = tiny_train_cfg(data_cfg, str(tmp_path / sub), total_steps=20,
                             output_every=5, eval_every=10,
                             checkpoint_every=10)
        cfg.telemetry = telemetry
        cfg.health_metrics = health
        cfg.metrics_jsonl = os.path.join(str(tmp_path / sub), "m.jsonl")
        counts["n"] = 0
        t0 = time.perf_counter()
        result = Trainer(cfg).fit()
        wall = time.perf_counter() - t0
        assert result.final_step == 20
        return counts["n"], cfg, wall

    fetches_off, _, _ = run("off", telemetry=False, health=False)
    fetches_on, cfg, wall = run("on", telemetry=True, health=True)
    assert fetches_on == fetches_off, \
        "telemetry/health must not add device fetches"

    with open(cfg.metrics_jsonl) as f:
        recs = [json.loads(line) for line in f]
    by_kind = {}
    for r in recs:
        by_kind.setdefault(r["kind"], []).append(r)
    assert {"train", "eval", "span", "goodput", "hbm"} <= set(by_kind)

    # Health scalars fused into every train record.
    for r in by_kind["train"]:
        assert {"health_grad_norm", "health_param_norm",
                "health_update_ratio"} <= set(r)
        assert np.isfinite(r["health_grad_norm"])

    # The always-on device step-time estimator (utils/devprof.py)
    # rides the same fused fetch: every train row carries the keys,
    # real numbers once the first window completes — and it added zero
    # fetches (the assertion above already proved it).
    for r in by_kind["train"]:
        assert {"device_step_ms", "drain_wait_ms"} <= set(r)
    assert any(isinstance(r["device_step_ms"], (int, float)) and
               r["device_step_ms"] > 0 for r in by_kind["train"])

    # Span phases cover the loop; depth-0 categories feed goodput.
    names = {r["name"] for r in by_kind["span"]}
    assert {"data_wait", "compile_first_dispatch", "dispatch",
            "boundary_drain", "eval", "checkpoint"} <= names

    # Final goodput record: categories + train remainder sum to the
    # wall-clock total (within 2%), and the tracer's total is within
    # the fit() call's measured wall time.
    final = [r for r in by_kind["goodput"] if r.get("final")]
    assert final, "run end must flush a final goodput record"
    gp = final[-1]
    cat_s = sum(gp[f"{c}_frac"] for c in GOODPUT_CATEGORIES) \
        + gp["train_frac"]
    assert cat_s == pytest.approx(1.0, abs=0.02)
    assert 0 < gp["total_s"] <= wall * 1.02
    assert gp["compile_frac"] > 0      # first dispatch compiled

    # hbm records carry the full schema even on CPU.
    assert by_kind["hbm"][-1]["available"] in (True, False)

    # WELL-FORMED spans: within one lane (thread, depth) the records
    # are non-negative, monotone and non-overlapping (a thread's
    # same-depth spans are sequential context managers).
    lanes = {}
    for r in by_kind["span"]:
        assert r["dur_s"] >= 0 and r["start_s"] >= 0
        lanes.setdefault((r.get("thread"), r["depth"]), []).append(r)
    for lane in lanes.values():
        lane.sort(key=lambda r: r["start_s"])
        for a, b in zip(lane, lane[1:]):
            # 2 us slack: start_s/dur_s round to 1 us in the record.
            assert b["start_s"] >= a["start_s"] + a["dur_s"] - 2e-6, \
                (a, b, "same-depth spans must not overlap")

    # The stream passes the documented-schema lint (wired into tier 1).
    from tools import check_jsonl_schema
    assert check_jsonl_schema.check_file(cfg.metrics_jsonl, strict=True) == []

    # And the report CLI summarizes it.
    from tools import telemetry_report
    out = telemetry_report.summarize(cfg.metrics_jsonl)
    assert "goodput over" in out and "train" in out
    assert "grad norm" in out
    assert telemetry_report.main([cfg.metrics_jsonl]) == 0


def test_schema_kinds_match_observability_doc():
    """Doc-drift gate: every kind the lint knows appears in the
    docs/OBSERVABILITY.md kinds table, and vice versa — the exact drift
    KIND_KEYS' comment says the lint exists to catch, now enforced in
    BOTH directions (--list-kinds is the machine-readable side)."""
    import re

    from tools import check_jsonl_schema as lint

    doc_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "OBSERVABILITY.md")
    with open(doc_path) as f:
        doc = f.read()
    # Table rows look like: | `kind` | `required keys` | emitted |
    doc_kinds = set(re.findall(r"^\| `(\w+)` \|", doc, re.MULTILINE))
    lint_kinds = set(lint.list_kinds())
    assert lint_kinds - doc_kinds == set(), \
        "kinds missing from the docs/OBSERVABILITY.md table"
    assert doc_kinds - lint_kinds == set(), \
        "documented kinds missing from tools/check_jsonl_schema.py"


def test_list_kinds_cli(capsys):
    from tools import check_jsonl_schema as lint

    assert lint.main(["--list-kinds"]) == 0
    out = capsys.readouterr().out.split()
    assert out == sorted(lint.KIND_KEYS)
    assert "devtime" in out and "train" in out


def test_check_jsonl_schema_catches_violations(tmp_path):
    from tools import check_jsonl_schema as lint

    good = {"kind": "eval", "t": 1.0, "task": 0, "step": 10,
            "test_accuracy": 0.5}
    assert lint.check_lines([json.dumps(good)]) == []
    # NaN token → non-strict JSON.
    errs = lint.check_lines(['{"kind": "eval", "t": NaN, "task": 0, '
                             '"step": 1, "test_accuracy": 0.1}'])
    assert errs and "strict JSON" in errs[0]
    # Missing required key for the kind.
    errs = lint.check_lines(['{"kind": "eval", "t": 1.0, "task": 0, '
                             '"step": 1}'])
    assert errs and "test_accuracy" in errs[0]
    # Unknown kind: tolerated by default (an old checkout reading a
    # newer stream), rejected under strict — the drift guard the repo's
    # own tests run with.
    mystery = '{"kind": "mystery", "t": 1.0, "task": 0}'
    assert lint.check_lines([mystery]) == []
    errs = lint.check_lines([mystery], strict=True)
    assert errs and "unknown kind" in errs[0]
    # Garbage line.
    assert lint.check_lines(["not json"])
    # File-level entry point.
    p = tmp_path / "m.jsonl"
    p.write_text(json.dumps(good) + "\n")
    assert lint.check_file(str(p), strict=True) == []
    assert lint.main(["--strict", str(p)]) == 0


# ---------------------------------------------------------------------------
# One span stream over the whole of fit (threads, the collector, set-up)
# ---------------------------------------------------------------------------

def test_a_second_threads_spans_leave_the_loops_depths_alone():
    import threading

    tr = SpanTracer(enabled=True)
    inside = threading.Event()
    done = threading.Event()

    def other():
        with tr.span("flops_probe"):
            with tr.span("probe_compile_dispatch"):
                inside.set()
                done.wait(5)

    t = threading.Thread(target=other, name="flops-probe")
    with tr.span("outer"):
        t.start()
        assert inside.wait(5)
        # two spans are open on the other thread: this thread's depth is
        # still its own
        with tr.span("inner"):
            assert tr._depth == 2
        done.set()
        t.join(5)
    assert tr._depth == 0
    recs = {r[0]: r for r in tr.drain()}
    assert (recs["inner"][4], recs["outer"][4]) == (1, 0)
    assert (recs["flops_probe"][4], recs["probe_compile_dispatch"][4]) \
        == (0, 1)
    assert recs["flops_probe"][5] == "flops-probe"
    assert recs["outer"][5] is None and recs["inner"][5] is None


def test_span_records_keep_microseconds_and_name_their_thread(monkeypatch):
    import threading

    clock = _FakeClock()
    monkeypatch.setattr(time, "perf_counter", clock)
    seen = []

    class Log:
        def log(self, kind, **fields):
            seen.append((kind, fields))

    tr = SpanTracer(enabled=True)
    clock.t = 0.001234
    with tr.span("boundary_drain"):
        clock.t += 0.000085
    t = threading.Thread(target=lambda: tr.span("late").__enter__()
                         .__exit__(None, None, None), name="worker-7")
    t.start()
    t.join()
    flush_boundary(tr, Log(), step=3)
    spans = [f for k, f in seen if k == "span"]
    assert spans[0]["start_s"] == 0.001234 and spans[0]["dur_s"] == 0.000085
    assert "thread" not in spans[0] and spans[1]["thread"] == "worker-7"
    assert tr.flushed_step == 3


def test_goodput_of_a_fixed_run_is_what_it_was(monkeypatch):
    """The goodput clock starts at ``start()`` (where set-up ends), not
    at the tracer's creation: set-up spans, uncategorized loop spans, the
    probe's and the collector's change no fraction."""
    clock = _FakeClock()
    monkeypatch.setattr(time, "perf_counter", clock)
    tr = SpanTracer(enabled=True)
    with tr.span("fit_setup"):
        with tr.span("build_iterators"):
            clock.t += 7.0
    tr.add_secs("compile", 3.0)      # attributed before the loop: dropped
    tr.start()
    with tr.span("compile_first_dispatch", cat="compile"):
        clock.t += 2.0
    with tr.span("data_wait", cat="data"):
        clock.t += 1.0
    with tr.span("boundary_acc_dispatch"):
        clock.t += 0.5
    with tr.span("boundary_drain"):
        tr._on_gc("start", {"generation": 2})
        clock.t += 5.0
        tr._on_gc("stop", {"generation": 2})
    with tr.span("boundary_log"):
        clock.t += 0.5
    with tr.span("checkpoint", cat="checkpoint"):
        clock.t += 1.0
    assert tr.goodput() == {
        "total_s": 10.0, "checkpoint_frac": 0.1, "compile_frac": 0.2,
        "data_frac": 0.1, "eval_frac": 0.0, "sync_frac": 0.0,
        "train_frac": 0.6}
    recs = {r[0]: r for r in tr.drain()}
    # span starts stay on the tracer's own clock, set-up included
    assert recs["fit_setup"][2] == 0.0
    assert recs["compile_first_dispatch"][2] == pytest.approx(7.0)
    # the collection is a span inside the drain, one level down
    assert recs["gc_gen2"][3] == pytest.approx(5.0)
    assert recs["gc_gen2"][4] == 1 and recs["boundary_drain"][4] == 0


def test_gc_hook_is_registered_only_while_an_enabled_tracer_watches():
    import gc

    before = list(gc.callbacks)
    off = SpanTracer(enabled=False)
    off.watch_gc()
    assert gc.callbacks == before
    on = SpanTracer(enabled=True)
    on.watch_gc()
    on.watch_gc()
    assert len(gc.callbacks) == len(before) + 1
    gc.collect()
    assert any(r[0] == "gc_gen2" for r in on.drain())
    on.close()
    assert gc.callbacks == before


def test_a_reopened_tracer_keeps_spans_for_the_next_flush_again():
    """Between a fit's close and the next one's start whoever finishes a
    span logs it; the next fit's spans wait for its boundaries."""
    import gc

    logged = []

    class Log:
        def log(self, kind, **fields):
            logged.append(fields["name"])

    before = list(gc.callbacks)
    tr = SpanTracer(enabled=True)
    with tr.span("trainer_init"):
        pass
    tr.reopen()
    assert len(gc.callbacks) == len(before) + 1
    with tr.span("dispatch"):
        pass
    assert logged == []
    tr.close(Log())
    assert logged == ["trainer_init", "dispatch"] and gc.callbacks == before
    with tr.span("init_or_restore"):       # between two fits
        pass
    assert logged[-1] == "init_or_restore" and tr.drain() == []
    tr.reopen()
    with tr.span("dispatch"):
        pass
    assert len(logged) == 3 and [r[0] for r in tr.drain()] == ["dispatch"]
    tr.close()


def test_short_young_collections_leave_no_span(monkeypatch):
    """A fit makes a thousand collections of generation 0 in its set-up;
    only those long enough to stall a loop, and every full one, are
    spans."""
    clock = _FakeClock()
    monkeypatch.setattr(time, "perf_counter", clock)
    tr = SpanTracer(enabled=True)
    for gen, dur in ((0, 9e-5), (1, 5e-4), (0, 2e-3), (1, 1e-3), (2, 1e-5)):
        tr._on_gc("start", {"generation": gen})
        clock.t += dur
        tr._on_gc("stop", {"generation": gen})
    assert [(r[0], round(r[3], 6)) for r in tr.drain()] == [
        ("gc_gen0", 2e-3), ("gc_gen1", 1e-3), ("gc_gen2", 1e-5)]


def test_registry_counts_span_seconds_and_spans_by_name():
    from dml_cnn_cifar10_tpu.utils import metrics_registry as mr

    reg = mr.MetricsRegistry()
    for dur in (0.25, 0.5):
        mr.observe_record("span", {"step": 1, "name": "fit_setup",
                                   "start_s": 0.0, "dur_s": dur,
                                   "depth": 0}, registry=reg)
    mr.observe_record("span", {"step": 1, "name": "checkpoint",
                               "start_s": 1.0, "dur_s": 2.0, "depth": 0,
                               "cat": "checkpoint"}, registry=reg)
    secs = reg.get("dml_span_seconds_total").values()
    count = reg.get("dml_spans_total").values()
    assert secs == {("fit_setup",): 0.75, ("checkpoint",): 2.0}
    assert count == {("fit_setup",): 2.0, ("checkpoint",): 1.0}
    text = reg.render()
    assert 'dml_span_seconds_total{name="fit_setup"} 0.75' in text
    assert mr.parse_prometheus_text(text)["dml_spans_total"]["samples"][
        (("name", "checkpoint"),)] == 1.0


def _resident_fit(data_cfg, tmp_path, sub, telemetry, total_steps=8,
                  profile_dir=None):
    import threading

    from dml_cnn_cifar10_tpu.train.loop import Trainer

    cfg = tiny_train_cfg(data_cfg, str(tmp_path / sub),
                         total_steps=total_steps, output_every=4,
                         eval_every=8, checkpoint_every=8)
    cfg.steps_per_dispatch = 2
    cfg.telemetry = telemetry
    cfg.profile_dir = profile_dir
    cfg.metrics_jsonl = os.path.join(str(tmp_path / sub), "m.jsonl")
    before = set(threading.enumerate())
    trainer = Trainer(cfg)
    result = trainer.fit()
    for t in set(threading.enumerate()) - before:
        t.join(timeout=300)
    trainer.logger.close()
    with open(cfg.metrics_jsonl) as f:
        return trainer, result, [json.loads(line) for line in f]


def test_fit_spans_cover_setup_probe_boundary_teardown_and_gc(
        data_cfg, tmp_path, monkeypatch):
    import gc

    from dml_cnn_cifar10_tpu.utils import devprof
    from dml_cnn_cifar10_tpu.utils import telemetry as telemetry_lib

    devprof.clear_scope_maps()
    real_record = SpanTracer._record
    collected = []

    def record_and_collect(self, name, cat, t0, dur, depth):
        # a full collection inside the loop, while its spans are open
        if name == "dispatch" and not collected:
            collected.append(gc.collect())
        return real_record(self, name, cat, t0, dur, depth)

    monkeypatch.setattr(SpanTracer, "_record", record_and_collect)
    callbacks_before = list(gc.callbacks)
    trainer, result, recs = _resident_fit(data_cfg, tmp_path, "on", True)
    assert result.final_step == 8
    assert gc.callbacks == callbacks_before       # the hook is gone again
    spans = [r for r in recs if r["kind"] == "span"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert {"fit_setup", "build_iterators", "place_resident", "build_step",
            "compile_first_dispatch", "dispatch", "data_wait",
            "boundary_acc_dispatch", "boundary_drain", "boundary_log",
            "eval", "checkpoint", "fit_teardown", "flops_probe",
            "probe_compile_dispatch", "probe_scope_map"} <= set(by_name)
    assert any(n.startswith("gc_gen") for n in by_name)
    # set-up's children lie inside it, one level down; the boundary's
    # three are siblings at the loop's depth, in order
    # the tracer is the Trainer's: its making comes first on its clock,
    # and the fit's own initialisation lies inside the fit's set-up
    (made,) = by_name["trainer_init"]
    (setup,) = by_name["fit_setup"]
    (init,) = by_name["init_or_restore"]
    assert made["depth"] == 0 and made["start_s"] < 0.01
    assert setup["depth"] == 0 \
        and setup["start_s"] >= made["start_s"] + made["dur_s"]
    assert init["depth"] == 1 and setup["start_s"] <= init["start_s"]
    for child in ("build_iterators", "place_resident", "build_step"):
        (c,) = by_name[child]
        assert c["depth"] == 1 and c["start_s"] >= setup["start_s"]
        assert c["start_s"] + c["dur_s"] \
            <= setup["start_s"] + setup["dur_s"] + 1e-5
    acc, drain, log = (by_name[n][0] for n in (
        "boundary_acc_dispatch", "boundary_drain", "boundary_log"))
    assert acc["depth"] == drain["depth"] == log["depth"] == 0
    assert acc["start_s"] + acc["dur_s"] <= drain["start_s"] + 1e-5
    assert drain["start_s"] + drain["dur_s"] <= log["start_s"] + 1e-5
    # the probe's spans name their thread and keep their own depths
    (probe,) = by_name["flops_probe"]
    assert probe["thread"] == "flops-probe" and probe["depth"] == 0
    assert by_name["probe_scope_map"][0]["depth"] == 1
    assert "thread" not in drain
    # every start is on the tracer's clock: epoch + start_s is the
    # span's perf_counter start (what benchmark/lib/driver.py reads)
    assert all(isinstance(s["start_s"], float) and s["start_s"] >= 0
               for s in spans)
    # goodput keeps its keys, and its clock starts after set-up
    final = [r for r in recs if r["kind"] == "goodput" and r.get("final")]
    assert set(final[-1]) == {
        "kind", "t", "task", "step", "total_s", "train_frac", "final",
        *(f"{c}_frac" for c in GOODPUT_CATEGORIES)}
    end = max(s["start_s"] + s["dur_s"] for s in spans
              if s["name"] == "checkpoint")
    assert final[-1]["total_s"] \
        <= end - setup["start_s"] - setup["dur_s"] + 0.5
    # the scope maps: one record a program, the maps kept by module
    maps = [r for r in recs if r["kind"] == "scopemap"]
    assert {m["module"] for m in maps} == set(devprof.scope_maps())
    assert len(maps) == 2 and all(m["path"] is None for m in maps)
    assert all(0 < m["mapped"] <= m["instructions"] for m in maps)
    from tools import check_jsonl_schema
    assert check_jsonl_schema.check_lines(
        [json.dumps(r) for r in recs], strict=True) == []


def test_a_trainers_making_and_its_restore_are_spans_of_one_tracer(
        data_cfg, tmp_path, monkeypatch):
    """``trainer_init`` and ``init_or_restore`` are posted (records and the
    registry's counters) by a fit that initialises, and by a ``Trainer``
    that restores before it goes on; one tracer serves both of an
    object's fits."""
    import threading

    from dml_cnn_cifar10_tpu.train.loop import Trainer
    from dml_cnn_cifar10_tpu.utils import metrics_registry as mr

    reg = mr.MetricsRegistry()
    monkeypatch.setattr(mr, "_DEFAULT", reg)

    def counted(name):
        family = reg.get("dml_spans_total")
        return 0 if family is None else family.values().get((name,), 0)

    trainer, result, recs = _resident_fit(data_cfg, tmp_path, "on", True,
                                          total_steps=4)
    assert result.final_step == 4
    assert counted("trainer_init") == counted("init_or_restore") == 1
    assert reg.get("dml_span_seconds_total").values()[
        ("trainer_init",)] > 0
    # a second Trainer on the same directory restores step 4, outside any
    # fit; the spans wait for its fit's first flush, and a second fit of
    # the same object keeps the tracer
    cfg = trainer.cfg
    cfg.total_steps = 8
    before = set(threading.enumerate())
    again = Trainer(cfg)
    tracer = again._tracer
    state = again.init_or_restore()
    assert int(state.step) == 4 and counted("init_or_restore") == 1
    first = again.fit(total_steps=6, state=state)
    assert counted("trainer_init") == counted("init_or_restore") == 2
    second = again.fit(total_steps=8, state=first.state)
    for t in set(threading.enumerate()) - before:
        t.join(timeout=300)
    again.logger.close()
    assert second.final_step == 8 and again._tracer is tracer
    assert counted("fit_setup") == 3 and counted("trainer_init") == 2
    with open(cfg.metrics_jsonl) as f:
        spans = [r for r in map(json.loads, f) if r["kind"] == "span"]
    restored = [s for s in spans if s["name"] == "init_or_restore"][-1]
    assert restored["depth"] == 0


def test_telemetry_off_builds_and_registers_nothing(data_cfg, tmp_path,
                                                    monkeypatch):
    import gc

    from dml_cnn_cifar10_tpu.utils import devprof
    from dml_cnn_cifar10_tpu.utils import telemetry as telemetry_lib

    devprof.clear_scope_maps()
    annotations = []
    monkeypatch.setattr(telemetry_lib, "_trace_annotation",
                        lambda name: annotations.append(name))
    appended = []
    real_callbacks = gc.callbacks

    class Watched(list):
        def append(self, cb):
            appended.append(cb)
            super().append(cb)

    monkeypatch.setattr(gc, "callbacks", Watched(real_callbacks))
    trainer, result, recs = _resident_fit(data_cfg, tmp_path, "off", False)
    assert result.final_step == 8
    assert not appended, "no gc callback without telemetry"
    assert not annotations, "no TraceAnnotation without telemetry"
    assert devprof.scope_maps() == {}, "no scope map without telemetry"
    kinds = {r["kind"] for r in recs}
    assert not kinds & {"span", "scopemap", "goodput", "hbm"}
    assert {"train", "eval", "done"} <= kinds
    assert trainer._tracer.span("x") is trainer._tracer.span("y")


def test_a_one_dispatch_fit_still_logs_its_probe_span(data_cfg, tmp_path):
    """The warm-up fit of one dispatch makes its last flush before its
    probe thread ends: the span is logged by whoever finishes it."""
    trainer, result, recs = _resident_fit(data_cfg, tmp_path, "one", True,
                                          total_steps=2)
    assert result.final_step == 2
    names = [r["name"] for r in recs if r["kind"] == "span"]
    assert names.count("flops_probe") == 1
    assert "fit_teardown" in names and "fit_setup" in names
    # fit_teardown ends after the final flush by construction: it is in
    # the stream all the same, after the final goodput record
    final_at = max(i for i, r in enumerate(recs)
                   if r["kind"] == "goodput" and r.get("final"))
    teardown_at = next(i for i, r in enumerate(recs) if r["kind"] == "span"
                       and r["name"] == "fit_teardown")
    assert teardown_at > final_at


def test_report_names_the_span_that_holds_a_slow_intervals_excess(tmp_path):
    """tools/telemetry_report.py, "slowest boundary interval": the
    interval's length against the median and the spans inside it."""
    from tools import telemetry_report

    recs = []

    def span(name, start, dur, step, depth=0, **kw):
        recs.append({"kind": "span", "t": start + dur, "task": 0,
                     "step": step, "name": name, "start_s": start,
                     "dur_s": dur, "depth": depth, **kw})

    # a warm-up fit with one boundary, then a fit of six intervals of
    # 3 s; in the fifth a full collection inside the drain adds 1.5 s
    span("boundary_drain", 5.0, 1.0, 6)
    t = 0.0
    span("fit_setup", 0.0, 2.0, 0)
    t = 2.0
    for i in range(7):
        stall = 1.5 if i == 5 else 0.0
        span("boundary_log", t, 0.01, 30 * i)
        span("dispatch", t + 0.01, 0.002, 30 * i)
        span("boundary_acc_dispatch", t + 0.02, 0.003, 30 * (i + 1))
        if stall:
            span("gc_gen2", t + 1.0, stall, 30 * (i + 1), depth=1)
        span("boundary_drain", t + 0.023, 2.977 + stall, 30 * (i + 1))
        t += 3.0 + stall
    path = tmp_path / "m.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    slow = telemetry_report.summarize_json(str(path))["slowest_boundary"]
    assert slow["intervals"] == 6 and slow["step"] == 180
    assert slow["secs"] == pytest.approx(4.5)
    assert slow["median_secs"] == pytest.approx(3.0)
    assert slow["ratio"] == pytest.approx(1.5)
    top = slow["spans"][0]
    assert top["name"] in ("gc_gen2", "boundary_drain")
    by_name = {r["name"]: r for r in slow["spans"]}
    assert by_name["gc_gen2"]["excess_secs"] == pytest.approx(1.5)
    assert by_name["boundary_drain"]["excess_secs"] == pytest.approx(1.5)
    assert by_name["dispatch"]["excess_secs"] == pytest.approx(0.0)
    text = telemetry_report.summarize(str(path))
    assert "slowest boundary interval: 4.5000 s ending at step 180" in text
    assert "gc_gen2" in text and "x1.5" in text
    # too few intervals: no section
    few = tmp_path / "few.jsonl"
    few.write_text("".join(json.dumps(r) + "\n" for r in recs[:8]))
    assert "slowest_boundary" not in telemetry_report.summarize_json(
        str(few))
