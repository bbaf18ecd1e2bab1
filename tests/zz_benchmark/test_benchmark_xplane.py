"""The trace reducer on small recorded planes: busy time is a union on
one device, a figure of the trace is one device's (the mean over device
planes, never their sum), scopes and collectives are found by name, and
what is not found fails loudly."""

import pytest
from jax.profiler import ProfileData

from benchmark.lib import xplane

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
    2: "%fusion.7 = f32[8] fusion(%p.0), kind=kLoop, "
       "metadata={op_name=\\\"jit(chunk)/while/body/fwd_bwd/conv\\\"}",
    # as the v5e's trace prints the fused update: a kernel called under
    # jax.named_scope("optimizer") is an instruction of that name
    3: "%optimizer.129 = f32[16,128]{1,0:T(8,128)S(1)} custom-call(f32[1] "
       "%bitcast.253, f32[16,128] %copy-done.14), "
       "custom_call_target=\\\"tpu_custom_call\\\"",
    6: "%slice_fusion.27 = f32[10] fusion(f32[16,128] %optimizer.129), "
       "kind=kLoop",
    4: "%all-reduce-start.2 = f32[8] all-reduce-start(%fusion.7)",
    5: "%all-reduce-done.2 = f32[8] all-reduce-done(%all-reduce-start.2)",
}


def device_plane(i, shift=0.0):
    """One chip: a while of 100 ns holding a 30 ns fusion and a 20 ns
    update that overlaps it by 10 ns; then 50 ns idle; then a 10 ns
    fusion. Async line: a 40 ns all-reduce of which 15 ns lie under no
    other leaf operation."""
    ops = _line("XLA Ops", [(1, 0 + shift, 100), (2, 10 + shift, 30),
                            (3, 30 + shift, 20), (6, 150 + shift, 10)])
    asy = _line("Async XLA Ops", [(4, 45 + shift, 40)])
    other = _line("Steps", [(1, 0, 1000)])
    return _plane(f"/device:TPU:{i}", [other, ops, asy], NAMES)


def profile(*planes):
    host = _plane("/host:CPU", [_line("python3", [(9, 5, 1)])],
                  {9: "bench_window_start"})
    return ProfileData.from_text_proto("".join(planes) + host)


def test_busy_is_a_union_of_overlapping_and_nested_events():
    trace = xplane.from_profile(profile(device_plane(0)))
    # while 0..100 covers its body; the all-reduce ends at 85 inside it;
    # the late fusion adds 10: 110 ns, not the 200 ns the durations sum to
    assert trace.busy_s() == pytest.approx(110e-9)


def test_four_device_planes_give_one_devices_figure():
    one = xplane.from_profile(profile(device_plane(0)))
    four = xplane.from_profile(profile(*[device_plane(i, shift=3.0 * i)
                                         for i in range(4)]))
    assert len(four.planes) == 4
    assert four.busy_s() == pytest.approx(one.busy_s())
    assert four.scope_s("optimizer") == pytest.approx(one.scope_s("optimizer"))
    assert four.exposed_collective_s() == pytest.approx(
        one.exposed_collective_s())


def test_scope_time_counts_the_leaves_under_the_scope():
    trace = xplane.from_profile(profile(device_plane(0)))
    # by the instruction's own name; one that only reads the update's
    # result (slice_fusion.27) is not the update
    assert trace.scope_s("optimizer") == pytest.approx(20e-9)
    # by a name-scope path in the event's text
    assert trace.scope_s("fwd_bwd") == pytest.approx(30e-9)


def test_exposed_collective_time_is_what_no_other_leaf_covers():
    trace = xplane.from_profile(profile(device_plane(0)))
    # all-reduce 45..85; leaves: fusion 10..40, update 30..50 -> 50..85,
    # less nothing else: 35 ns (the while is no leaf)
    assert trace.exposed_collective_s() == pytest.approx(35e-9)


def test_self_times_take_the_body_out_of_the_while():
    trace = xplane.from_profile(profile(device_plane(0)))
    times = xplane.self_times(
        [o for o in trace.planes[0].ops if o.line == "XLA Ops"])
    assert times["while.1"] == pytest.approx(60e-9)   # 100 - (10..50)
    # the update that starts inside the first fusion counts as inside it
    assert times["fusion.7"] == pytest.approx(20e-9)
    assert times["slice_fusion.27"] == pytest.approx(10e-9)
    assert sum(times.values()) == pytest.approx(110e-9)
    top = xplane.breakdown(trace)
    assert top["device_ops"][0][0] == "while.1"
    assert top["idle_gaps"] == [["unattributed", pytest.approx(50e-9)]]


def test_marker_gives_the_host_clock_of_the_trace():
    assert xplane.from_profile(profile(device_plane(0)),
                               "bench_window_start").marker_ns == 5.0


@pytest.mark.parametrize("what", ["scope", "collective", "plane", "line"])
def test_what_is_not_found_fails_loudly(what):
    if what == "plane":
        with pytest.raises(xplane.TraceError, match="no device plane"):
            xplane.from_profile(profile())
        return
    if what == "line":
        bare = _plane("/device:TPU:0", [_line("Steps", [(1, 0, 10)])], NAMES)
        with pytest.raises(xplane.TraceError, match="no event"):
            xplane.from_profile(profile(bare))
        return
    quiet = _plane("/device:TPU:0",
                   [_line("XLA Ops", [(2, 0, 10)])], NAMES)
    trace = xplane.from_profile(profile(quiet))
    with pytest.raises(xplane.NotInTrace):
        if what == "scope":
            trace.scope_s("optimizer")
        else:
            trace.exposed_collective_s()


@pytest.mark.parametrize("intervals,want", [
    ([(0, 10), (2, 3)], 10), ([(0, 10), (5, 15)], 15),
    ([(0, 1), (1, 2)], 2), ([(0, 1), (5, 6)], 2), ([], 0),
])
def test_length_of_a_union(intervals, want):
    assert xplane.length(intervals) == want


def test_a_reader_returns_nothing_where_its_kernel_is_off_the_path():
    """The reducer is loud; the metric's reader turns "no such kernel in a
    sound trace" into nothing to read, never into 0."""
    from benchmark.lib import cells
    import os
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    quiet = _plane("/device:TPU:0", [_line("XLA Ops", [(2, 0, 10)])], NAMES)
    ctx = {"trace": xplane.from_profile(profile(quiet)), "steps": 2,
           "window_s": 1.0}
    for name in ("optimizer.device_us", "collective.exposed_pct"):
        read = cells.load_module(os.path.join(
            here, "benchmark", "metrics", name + ".py")).read
        assert read(ctx) is None
    full = {"trace": xplane.from_profile(profile(device_plane(0))),
            "steps": 2, "window_s": 1e-6}
    read = cells.load_module(os.path.join(
        here, "benchmark", "metrics", "optimizer.device_us.py")).read
    assert read(full) == pytest.approx(1e6 * 20e-9 / 2)
