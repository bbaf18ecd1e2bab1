"""The readers that join the trace with the program's instruction-to-layer
maps, and the host's phases with its span counters, on a small recorded
plane (the planes of ``test_benchmark_xplane.py``, extended by copy) with
a hand-made map: a name that two modules share, leaves inside a ``while``,
four planes that read as one, and a reader that finds nothing."""

import json
import os

import pytest
from jax.profiler import ProfileData

from benchmark.lib import cells, driver, scopes, xplane
from benchmark.tools import held
from dml_cnn_cifar10_tpu.utils import devprof, metrics_registry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NEXT_ID = [0]


def _line(name, events):
    """``events``: (metadata id, offset ns, duration ns)."""
    evs = "".join(
        f"events {{ metadata_id: {m} offset_ps: {int(o * 1000)} "
        f"duration_ps: {int(d * 1000)} }} " for m, o, d in events)
    _NEXT_ID[0] += 1
    return f'lines {{ id: {_NEXT_ID[0]} name: "{name}" timestamp_ns: 0 {evs}}} '


def _plane(name, lines, names):
    meta = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }} '
        for k, v in names.items())
    return f'planes {{ name: "{name}" {"".join(lines)}{meta}}} '


NAMES = {
    1: "%while.1 = (f32[8]) while(%tuple.1), body=%body",
    2: "%fusion.7 = f32[8] fusion(%p.0), kind=kLoop",
    3: "%optimizer.129 = f32[16,128]{1,0:T(8,128)S(1)} custom-call(f32[1] "
       "%bitcast.253), custom_call_target=\\\"tpu_custom_call\\\"",
    4: "%select-and-scatter.3 = f32[8] select-and-scatter(%fusion.7)",
    5: "%fusion.9 = f32[8] fusion(%select-and-scatter.3), kind=kLoop",
    6: "%fusion.2 = f32[8] fusion(%fusion.9), kind=kOutput",
    7: "%copy.5 = f32[8] copy(%while.1)",
    8: "%fusion.4 = u8[8] fusion(%p.1), kind=kLoop",
    9: "%fusion.8 = f32[8] fusion(%p.2), kind=kLoop",
    10: "%copy-start.2 = f32[8] copy-start(%fusion.2)",
}


def device_plane(i, shift=0.0, pool=True):
    """One chip, two steps. A dispatch's while of 100 ns: conv 10-40, the
    update 30-50 over it, pool's backward 50-70, a batch norm's backward
    70-80, a dense fusion 80-90, a copy the map does not hold 90-95. Then
    the accuracy pass, whose ``fusion.7`` is another module's (110-120),
    the next dispatch's decode (120-130), 20 ns idle, and a ``fusion.8``
    that both modules hold outside any loop (150-160). An async copy
    overlaps the loop's end."""
    body = [(2, 10, 30), (3, 30, 20), (5, 70, 10), (6, 80, 10), (7, 90, 5)]
    if pool:
        body.append((4, 50, 20))
    ops = _line("XLA Ops", [(m, o + shift, d) for m, o, d in
                            [(1, 0, 100)] + body + [(2, 110, 10),
                                                    (8, 120, 10),
                                                    (9, 150, 10)]])
    asy = _line("Async XLA Ops", [(10, 85 + shift, 10)])
    other = _line("Steps", [(1, 0, 1000)])
    return _plane(f"/device:TPU:{i}", [other, ops, asy], NAMES)


def profile(*planes):
    host = _plane("/host:CPU", [_line("python3", [(99, 5, 1)])],
                  {99: "bench_window_start"})
    return ProfileData.from_text_proto("".join(planes) + host)


E = devprof.ScopeEntry
MAPS = {
    "jit_chunk_dev": {
        "while.1": E("", "none", "other", False, False),
        "fusion.7": E("fwd_bwd/conv1", "conv", "forward", True, True),
        "optimizer.129": E("optimizer", "optimizer", "update", False, True),
        "select-and-scatter.3": E("fwd_bwd/pool1", "pool", "backward",
                                  False, True),
        "fusion.9": E("fwd_bwd/stem/bn", "norm_act", "backward", False,
                      True),
        "fusion.2": E("fwd_bwd/loss", "dense", "forward", False, True),
        # a copy the map named after its consumer
        "fusion.4": E("gather", "decode", "other", False, False, True),
        "fusion.8": E("index", "decode", "other", False, False),
    },
    "jit_ev": {
        "fusion.7": E("train_acc/logits", "dense", "other", False, False),
        "fusion.8": E("train_acc/conv1", "conv", "other", False, False),
    },
}
SPANS = [driver.Span("boundary_acc_dispatch", 1.0, 0.002, 0),
         driver.Span("boundary_drain", 1.002, 0.9, 0),
         driver.Span("boundary_log", 1.902, 0.010, 0),
         driver.Span("boundary_acc_dispatch", 2.0, 0.004, 0),
         driver.Span("checkpoint", 2.1, 0.5, 0)]
COUNTED = {"fit_setup": (4.0, 5.5), "compile_first_dispatch": (15.0, 1.2),
           "flops_probe": (5.0, 7.0), "checkpoint": (2.0, 0.5),
           "boundary_log": (0.008, 0.010, 0.012, 0.8)}

# metric -> what it reads on one plane (steps 2, busy 130 ns)
WANT = {
    "model.conv_device_ms": 1e3 * 30e-9 / 2,
    "model.pool_device_ms": 1e3 * 20e-9 / 2,
    "model.norm_act_device_ms": 1e3 * 10e-9 / 2,
    "model.dense_device_ms": 1e3 * 20e-9 / 2,      # in the loop + jit_ev's
    "data.decode_device_ms": 1e3 * 10e-9 / 2,
    "step.backward_pct": 100 * 30 / 130,
    # named: 10-90 in the loop, 110-130 after it; the while's own time,
    # the copy and the ambiguous fusion.8 are what is left
    "step.unattributed_pct": 100 * 30 / 130,
    "step.mixed_pct": 100 * 30 / 130,      # the loop's fusion.7
    "loop.boundary_host_ms": 3.0 + 10.0,
    "setup.fit_setup_s": 9.5,
    "setup.compile_s": 16.2,
    "setup.probe_s": 12.0,
    "setup.checkpoint_s": 2.0,       # 2.5 counted less the window's 0.5
}


def reader(name):
    return cells.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py")).read


@pytest.fixture
def program(monkeypatch):
    """The program's side as a run with telemetry leaves it: the maps of
    two modules, and a registry fed by the logger's ``span`` records."""
    monkeypatch.setattr(devprof, "_SCOPE_MAPS", dict(MAPS))
    reg = metrics_registry.MetricsRegistry()
    monkeypatch.setattr(metrics_registry, "_DEFAULT", reg)
    for name, durs in COUNTED.items():
        for dur in durs:
            metrics_registry.observe_record(
                "span", {"step": 0, "name": name, "start_s": 0.0,
                         "dur_s": dur, "depth": 0})
    return reg


def ctx_of(*planes, spans=SPANS):
    trace = xplane.from_profile(profile(*planes)) if planes else None
    return {"trace": trace, "steps": 2, "window_s": 1e-6, "spans": spans}


IN_BENCHMARK = {n for n in WANT if n.startswith("setup.")}


def test_the_entries_are_listed_or_held_with_their_readers():
    """The four that every rehearsal can read are in ``BENCHMARK.json``;
    those that join the trace with the map, and the one that needs two
    traced intervals, wait under a key the tests' roots do not merge."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    waiting = {m["name"]: m for m in held.held_entries()}
    assert IN_BENCHMARK <= set(listed) and not set(waiting) & set(listed)
    assert set(waiting) == set(WANT) - IN_BENCHMARK
    ends = {m["name"] for m in bench["end_to_end"]}
    for name in WANT:
        m = {**listed, **waiting}[name]
        assert callable(reader(name))
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["better"] == "lower" and m["moves"] in ends
        assert m["moves"] == ("setup_s" if name.startswith("setup.")
                              else "img_per_s_per_chip")
        assert m["source"] == (
            "program_counter" if name.startswith("setup.") else
            "program_span" if name.startswith("loop.") else "device_trace")
        assert set(m["workloads"]) <= {w["name"] for w in bench["workloads"]}
    # the CNN has no layer of kind norm_act: nothing to read there
    assert waiting["model.norm_act_device_ms"]["workloads"] \
        == ["resnet50_b256_resident"]


def test_the_held_entries_join_a_copy_that_traces_more_intervals(tmp_path):
    root = held.make_root(str(tmp_path / "root"), "cnn_b16k_resident", 3)
    cell = cells.load_cell(root, "cnn_b16k_resident")
    names = [m["name"] for m in cell.per_layer]
    assert set(WANT) - {"model.norm_act_device_ms"} <= set(names)
    assert "model.norm_act_device_ms" not in names
    assert cell.traffic["trace_boundaries"] == 3
    committed = cells.load_cell(ROOT, "cnn_b16k_resident")
    assert committed.traffic["trace_boundaries"] == 1
    before = [m["name"] for m in committed.per_layer]
    assert names == before + [n for n in names if n not in before]
    for name in names:
        assert callable(cells.load_reader(cell, name))


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_reader_on_the_recorded_plane(program, name):
    assert reader(name)(ctx_of(device_plane(0))) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(n for n in WANT
                                        if not n.startswith(("setup.",
                                                             "loop."))))
def test_four_planes_read_as_one_device(program, name):
    four = ctx_of(*[device_plane(i, shift=3.0 * i) for i in range(4)])
    assert reader(name)(four) == pytest.approx(WANT[name])


def test_the_kinds_and_what_is_left_add_up_to_the_busy_time(program):
    ctx = ctx_of(device_plane(0))
    busy_ms = 1e3 * ctx["trace"].busy_s() / ctx["steps"]
    parts = sum(reader(n)(ctx) for n in WANT if n.endswith("_device_ms"))
    update_ms = reader("optimizer.device_us")(ctx) / 1e3
    left_ms = reader("step.unattributed_pct")(ctx) / 100 * busy_ms
    # over by what the update's kernel overlaps the convolution (10 ns)
    assert parts + update_ms + left_ms == pytest.approx(
        busy_ms + 1e3 * 10e-9 / 2)


def test_a_shared_name_goes_by_the_enclosing_while(program):
    maps = scopes.program_maps()
    assert scopes.resolve("fusion.7", True, maps).kind == "conv"
    assert scopes.resolve("fusion.7", False, maps).kind == "dense"
    # held by both outside any loop, and they disagree: no entry
    assert scopes.resolve("fusion.8", False, maps) is None
    assert scopes.resolve("fusion.4", False, maps).kind == "decode"
    assert scopes.resolve("copy.5", True, maps) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_program_without_maps_or_counters_gives_nothing(
        monkeypatch, name):
    """The parent of the PR that added them, or a run without telemetry:
    every new reader returns None and none raises."""
    monkeypatch.setattr(devprof, "_SCOPE_MAPS", {})
    monkeypatch.setattr(metrics_registry, "_DEFAULT",
                        metrics_registry.MetricsRegistry())
    assert reader(name)(ctx_of(device_plane(0), spans=[])) is None
    monkeypatch.delattr(devprof, "scope_maps")
    assert reader(name)(ctx_of(device_plane(0), spans=[])) is None


def test_a_layer_off_the_path_is_nothing_to_read_not_zero(program):
    ctx = ctx_of(device_plane(0, pool=False))
    assert reader("model.pool_device_ms")(ctx) is None
    assert reader("model.conv_device_ms")(ctx) == pytest.approx(
        WANT["model.conv_device_ms"])
    assert reader("step.backward_pct")(ctx) == pytest.approx(
        100 * 10 / 130)


def test_a_window_of_one_interval_holds_no_boundary_host_time(program):
    """Such a window holds no whole ``boundary_log``; the counters, which
    hold the boundaries before the window (beside the probe thread, and
    the one in which the profiler starts), are not read in its place."""
    spans = [s for s in SPANS if s.name != "boundary_log"]
    assert program.get("dml_spans_total").values()[("boundary_log",)] == 4
    assert reader("loop.boundary_host_ms")(ctx_of(device_plane(0),
                                                  spans=spans)) is None


def test_the_report_counts_mixed_and_inherited_time_apart(program, capfd):
    scopes._LAST[:] = [None, None]
    assert reader("step.mixed_pct")(ctx_of(device_plane(0))) is not None
    err = capfd.readouterr().err
    # left: leaves only (the ambiguous fusion.8, the copy no map holds)
    assert err == ("scopes mixed_pct=23.08 inherited_pct=7.69 left "
                   "fusion.8=7.69% copy.5=3.85%\n")


def test_kinds_that_cover_more_than_the_busy_time_read_below_zero(
        program, monkeypatch):
    """No clamp: an overcount of the join shows."""
    ctx = ctx_of(device_plane(0))
    busy = ctx["trace"].busy_s()
    monkeypatch.setattr(xplane.Trace, "busy_s", lambda self: busy / 2)
    assert reader("step.unattributed_pct")(ctx) == pytest.approx(
        100 * (1 - 2 * 100 / 130))
