"""The readers of ``benchmark/lib/scope_parts.py`` on a small recorded
plane (built as ``test_benchmark_scopes.py`` builds its own) with a
hand-made map of today's entries: a ``while`` that its leaves tile, the
``qkv`` projection in all three passes, two flash kernels with the tiles
around them, a kernel the compiler renamed, a copy counted with its
consumer, and an operation after the loop."""

import collections
import json
import os

import pytest

import test_benchmark_scopes as recorded
from benchmark.lib import scope_parts, scopes, xplane
from dml_cnn_cifar10_tpu.utils import devprof, metrics_registry

ROOT = recorded.ROOT
CELLS = ["ouro_l6_b2_s4096_resident", "lfm2_l5_e8_s8192_resident"]
# five entries that joined the benchmark together, in their order
FIVE = ["step.recompute_pct", "model.attention_proj_device_ms",
        "model.attention_glue_device_ms", "step.inherited_pct",
        "setup.init_s"]

E = devprof.ScopeEntry
# name: (start ns, duration ns, entry); the loop runs 0-200
LOOP = {
    "fusion.10": (0, 20, E("fwd_bwd/layer1/attn/qkv", "attention",
                           "forward", False, True, False, "qkv")),
    "flash_fwd.3": (20, 20, E("fwd_bwd/layer1/attn/flash/flash_fwd",
                              "attention", "forward", False, True, False,
                              "flash")),
    "fusion.11": (40, 10, E("fwd_bwd/layer1/attn/rotary", "attention",
                            "forward", True, True, False, "rotary")),
    "fusion.12": (50, 15, E("fwd_bwd/layer1/attn/qkv", "attention",
                            "recompute", False, True, False, "qkv")),
    "fusion.13": (65, 25, E("fwd_bwd/layer1/attn/qkv", "attention",
                            "backward", False, True, False, "qkv")),
    "flash_bwd_dkv.4": (90, 30, E(
        "fwd_bwd/layer1/attn/flash/flash_bwd_dkv", "attention", "backward",
        False, True, False, "flash")),
    # the log-sum-exp tiles: under `flash`, and no kernel
    "fusion.14": (120, 6, E("fwd_bwd/layer1/attn/flash", "attention",
                            "backward", False, True, False, "flash")),
    # the experts' product, renamed by the compiler: its own kind, no guess
    "ragged-dot-none.3": (126, 24, E("", "expert", "backward", False, True)),
    "copy.9": (150, 4, E("fwd_bwd/layer1/attn/out", "attention", "backward",
                         False, True, True, "out")),
    "fusion.15": (154, 10, E("fwd_bwd/layer0/mlp", "mlp", "recompute",
                             False, True)),
    "fusion.16": (164, 10, E("fwd_bwd/layer2/attn_window/out",
                             "window_attention", "backward", False, True,
                             False, "out")),
    "fusion.18": (174, 20, E("fwd_bwd/layer1/moe/combine", "route",
                             "backward", False, True)),
    "fusion.17": (194, 6, E("optimizer", "optimizer", "update", False,
                            True)),
}
AFTER = {"fusion.20": (210, 10, E("embed", "embed", "other", False, False))}
MAPS = {"jit_chunk_dev": {
    "while.1": E("", "none", "other", False, False),
    **{name: e for name, (_, _, e) in {**LOOP, **AFTER}.items()}}}
BUSY = 210.0
STEPS = 2

# metric -> what it reads on the plane
WANT = {
    "step.recompute_pct": 100 * 25 / BUSY,
    "model.attention_proj_device_ms": 1e3 * 74e-9 / STEPS,
    "model.attention_glue_device_ms": 1e3 * 16e-9 / STEPS,
    "step.inherited_pct": 100 * 4 / BUSY,
}


def device_plane(i, shift=0.0, extra=()):
    texts = {1: "%while.1 = (f32[8]) while(%tuple.1), body=%body"}
    events = [(1, shift, 200)]
    for k, (name, (start, dur, _)) in enumerate(
            list({**LOOP, **AFTER}.items()) + list(extra), 2):
        texts[k] = f"%{name} = f32[8] fusion(%p.0), kind=kLoop"
        events.append((k, start + shift, dur))
    return recorded._plane(f"/device:TPU:{i}",
                           [recorded._line("XLA Ops", events)], texts)


def ctx_of(*planes):
    return {"trace": xplane.from_profile(recorded.profile(*planes)),
            "steps": STEPS, "window_s": 1e-6, "spans": []}


reader = recorded.reader


@pytest.fixture
def program(monkeypatch):
    monkeypatch.setattr(devprof, "_SCOPE_MAPS", dict(MAPS))
    monkeypatch.setattr(scope_parts, "_LAST", [None])


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_reader_on_the_recorded_plane(program, name):
    assert reader(name)(ctx_of(device_plane(0))) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_four_planes_read_as_one_device(program, name):
    four = ctx_of(*[device_plane(i, shift=3.0 * i) for i in range(4)])
    assert reader(name)(four) == pytest.approx(WANT[name])


def test_projections_glue_and_kernels_are_the_attention_kinds_time(program):
    ctx = ctx_of(device_plane(0))
    kinds = sum(scopes.kind_ms_per_step(ctx, k)
                for k in scope_parts.ATTENTION)
    kernels = scope_parts.ms_per_step(
        ctx, lambda name, e: name.startswith("flash_"))
    assert kernels == pytest.approx(1e3 * 50e-9 / STEPS)
    assert reader("model.attention_proj_device_ms")(ctx) \
        + reader("model.attention_glue_device_ms")(ctx) + kernels \
        == pytest.approx(kinds) == pytest.approx(1e3 * 140e-9 / STEPS)
    # the renamed kernel is the experts', all of it, and not the router's
    assert reader("model.expert_device_ms")(ctx) \
        == pytest.approx(1e3 * 24e-9 / STEPS)
    assert reader("model.route_device_ms")(ctx) \
        == pytest.approx(1e3 * 20e-9 / STEPS)


def test_the_five_passes_add_up_to_the_busy_time(program):
    ctx = ctx_of(device_plane(0))
    shares = {p: scope_parts.pct_of_busy(
        ctx, lambda name, e, p=p: e.pass_ == p) for p in devprof.PASSES}
    assert shares == pytest.approx({
        "forward": 100 * 50 / BUSY, "recompute": 100 * 25 / BUSY,
        "backward": 100 * 119 / BUSY, "update": 100 * 6 / BUSY,
        "other": 100 * 10 / BUSY})
    assert sum(shares.values()) == pytest.approx(100.0)
    # the readers that were there agree on what is left of `backward`
    assert reader("step.backward_pct")(ctx) \
        == pytest.approx(shares["backward"])


def test_a_leaf_without_an_entry_is_in_no_share(program):
    """A name the map does not hold is busy time of no pass: the shares
    sum to less, they are not spread over it."""
    ctx = ctx_of(device_plane(0, extra=[("copy.77", (220, 10, None))]))
    assert ctx["trace"].busy_s() == pytest.approx(220e-9)
    total = sum(scope_parts.pct_of_busy(
        ctx, lambda name, e, p=p: e.pass_ == p) for p in devprof.PASSES)
    assert total == pytest.approx(100 * 210 / 220)


Old = collections.namedtuple(
    "ScopeEntry", "scope kind pass_ mixed in_loop inherited",
    defaults=(False,))


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_map_without_parts_or_no_map_gives_nothing(monkeypatch, name):
    """The parent of the PR that added ``part`` (its ``inherited`` flag
    also covered the renamed kernels), a run without telemetry, a program
    without ``scope_maps``: ``None``, never 0, and none raises."""
    old = {"jit_chunk_dev": {n: Old(*e[:6]) for n, e in
                             MAPS["jit_chunk_dev"].items()}}
    monkeypatch.setattr(devprof, "_SCOPE_MAPS", old)
    ctx = ctx_of(device_plane(0))
    assert scopes.kind_ms_per_step(ctx, "attention") is not None
    assert reader(name)(ctx) is None
    monkeypatch.setattr(devprof, "_SCOPE_MAPS", {})
    assert reader(name)(ctx) is None
    monkeypatch.delattr(devprof, "scope_maps")
    assert reader(name)(ctx) is None
    assert reader(name)({**ctx, "trace": None}) is None


def test_what_is_off_the_path_is_nothing_to_read_not_zero(program,
                                                          monkeypatch):
    plain = {"jit_chunk_dev": {
        n: e._replace(pass_="backward" if e.pass_ == "recompute"
                      else e.pass_, inherited=False)
        for n, e in MAPS["jit_chunk_dev"].items()}}
    monkeypatch.setattr(devprof, "_SCOPE_MAPS", plain)
    ctx = ctx_of(device_plane(0))
    assert reader("step.recompute_pct")(ctx) is None
    assert reader("step.inherited_pct")(ctx) is None
    assert reader("model.attention_proj_device_ms")(ctx) \
        == pytest.approx(WANT["model.attention_proj_device_ms"])


def test_a_name_two_modules_hold_still_goes_by_the_enclosing_while(
        program, monkeypatch):
    """Relabelled maps keep ``split_plane``'s rule: the accuracy pass's
    ``fusion.10``, outside any loop, is not the dispatch's projection."""
    both = dict(MAPS, jit_ev={"fusion.10": E(
        "train_acc/embed", "embed", "other", False, False)})
    monkeypatch.setattr(devprof, "_SCOPE_MAPS", both)
    extra = [("fusion.10", (230, 40, None))]
    ctx = ctx_of(device_plane(0, extra=extra))
    assert reader("model.attention_proj_device_ms")(ctx) \
        == pytest.approx(WANT["model.attention_proj_device_ms"])


def test_the_table_is_printed_once_a_trace(program, capfd):
    ctx = ctx_of(device_plane(0, extra=[("copy.77", (220, 10, None))]))
    for name in sorted(WANT):
        assert reader(name)(ctx) is not None
    err = capfd.readouterr().err
    assert err.count("scope_parts ms a step by kind, pass, part") == 1
    lines = [line.split() for line in err.splitlines()]
    # 1e6 ns a ms: the fixture's nanoseconds are far below the rows' floor
    assert not any(line[1:4] == ["attention", "forward", "qkv"]
                   for line in lines if len(line) > 3)
    assert ["0.000", "flash_bwd_dkv.4", "attention", "backward", "flash",
            "fwd_bwd/layer1/attn/flash/flash_bwd_dkv"] in lines
    assert "leaves without an entry: copy.77=0.000" in err


def test_the_tables_rows_are_kind_pass_and_part(program, capfd,
                                                monkeypatch):
    monkeypatch.setattr(scope_parts, "ROW_MS", 0.0)
    reader("step.recompute_pct")(ctx_of(device_plane(0)))
    rows = [line.split() for line in capfd.readouterr().err.splitlines()
            if line.startswith("  ")]
    table = rows[:rows.index(next(r for r in rows if len(r) > 4))]
    assert ["attention", "backward", "flash"] in [r[1:] for r in table]
    assert ["expert", "backward"] in [r[1:] for r in table]
    assert ["mlp", "recompute"] in [r[1:] for r in table]
    # largest first: the backward pass's two flash rows are 36 ns together
    assert table[0][1:] == ["attention", "backward", "flash"]
    assert len(table) == len({(e.kind, e.pass_, e.part)
                              for _, _, e in {**LOOP, **AFTER}.values()})


def check_the_entries(bench: dict):
    """In ``bench`` (``BENCHMARK.json``, or one to which a later
    configuration has been appended), found by name: the four entries with
    the contract's keys, each listing the two cells (a later cell may join
    the list), and the five of ``FIVE`` in their order."""
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in WANT:
        m = listed[name]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["better"], m["source"], m["moves"]) == (
            "lower", "device_trace", "img_per_s_per_chip")
        assert set(CELLS) <= set(m["workloads"])
        assert m["unit"] == ("%" if name.endswith("_pct") else "ms")
        assert m["layer"] == ("whole step" if name.startswith("step.")
                              else "models and kernels")
    assert [m["name"] for m in bench["per_layer"] if m["name"] in FIVE] \
        == FIVE


def test_the_entries_have_the_contracts_keys_and_list_the_two_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        check_the_entries(json.load(f))
    for name in WANT:
        assert callable(reader(name))


def test_setup_init_s_is_listed_for_every_cell_and_reads_both_spans(
        monkeypatch):
    from benchmark.lib import cells
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {e["name"]: e for e in bench["per_layer"]}["setup.init_s"]
    assert m == {"name": "setup.init_s", "unit": "s", "better": "lower",
                 "source": "program_counter", "layer": "entry and compile",
                 "moves": "setup_s"}
    for w in bench["workloads"]:
        assert "setup.init_s" in [
            e["name"] for e in cells.load_cell(ROOT, w["name"]).per_layer]
    reg = metrics_registry.MetricsRegistry()
    monkeypatch.setattr(metrics_registry, "_DEFAULT", reg)
    read = reader("setup.init_s")
    assert read({}) is None

    def post(name, dur):
        metrics_registry.observe_record(
            "span", {"step": 0, "name": name, "start_s": 0.0, "dur_s": dur,
                     "depth": 0})

    post("init_or_restore", 1.5)
    assert read({}) == pytest.approx(1.5)
    post("trainer_init", 2.0)
    post("fit_setup", 9.0)
    assert read({}) == pytest.approx(3.5)
