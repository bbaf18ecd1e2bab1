"""A configuration joins the benchmark at the end: new files, and its
configuration, its cell and its per-layer entries appended to their lists
in ``BENCHMARK.json``, with nothing that is there edited. The checks that
hold accepted configurations, cells and entries by name pass on
``BENCHMARK.json`` and on a root with a seventh configuration appended,
the harness loads the added cell, and a reader takes its sizes from the
cell's own configuration."""

import json
import os

import pytest

import bench_roots
import test_benchmark_dsv2lite as dsv2lite
import test_benchmark_mellum2 as mellum2
import test_benchmark_parts as parts
from benchmark.lib import cells, window_costs, xplane

ROOT = bench_roots.ROOT
PINS = {"dsv2lite": dsv2lite.check_the_cells_entries,
        "mellum2": mellum2.check_the_cells_entries,
        "parts": parts.check_the_entries}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def added_root(tmp_path_factory):
    return bench_roots.make_root_with_one_appended(
        str(tmp_path_factory.mktemp("added") / "root"))


def _committed() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("which", ["committed", "appended"])
@pytest.mark.parametrize("pin", sorted(PINS))
def test_the_pins_hold_before_and_after_a_configuration_joins(pin, which):
    PINS[pin](_committed() if which == "committed"
              else bench_roots.benchmark_with_one_appended())


def test_what_joins_is_appended_and_nothing_before_it_changes():
    before, after = _committed(), bench_roots.benchmark_with_one_appended()
    for key, added in (("configs", bench_roots.ADDED_CONFIG),
                       ("workloads", bench_roots.ADDED_CELL),
                       ("per_layer", bench_roots.ADDED_METRIC)):
        assert [e["name"] for e in after[key]] \
            == [e["name"] for e in before[key]] + [added]
        assert [e for e in after[key] if e["name"] != added] == before[key]
    assert after["end_to_end"] == before["end_to_end"]
    entry = {m["name"]: m for m in after["per_layer"]}[
        bench_roots.ADDED_METRIC]
    assert entry["workloads"] == [bench_roots.ADDED_CELL]


def test_the_added_cell_loads_with_its_own_configuration(added_root):
    cell = cells.load_cell(added_root, bench_roots.ADDED_CELL)
    like = cells.load_cell(added_root, "mellum2_l4_e8_s8192_resident")
    assert cell.config["sliding_window"] == 512
    assert like.config["sliding_window"] == 1024
    assert cell.traffic == like.traffic and cell.limits == like.limits
    names = [m["name"] for m in cell.per_layer]
    # the entries every cell reports, then its own; none of another cell's
    assert names == [n for n in names if n != bench_roots.ADDED_METRIC] \
        + [bench_roots.ADDED_METRIC]
    assert "setup.init_s" in names and "step.device_ms" in names
    assert not set(mellum2.OWN) & set(names)
    assert {m["name"] for m in cell.end_to_end} \
        == {"img_per_s_per_chip", "setup_s"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.load_reader(cell, m["name"]))
    assert cell.reference.param_count(cell.config) \
        == cell.config["parameters"]


@pytest.mark.parametrize("name,names,passes", [
    ("flash_window_fwd_roofline", ("flash_window_fwd.3",), 1.0),
    ("flash_window_bwd_roofline", ("flash_window_bwd_dq.4",
                                   "flash_window_bwd_dkv.5"), 2.5)])
def test_a_window_reader_costs_the_added_cells_band(added_root, name, names,
                                                    passes):
    """Given the added cell's context, the window's readers cost the band
    of 512, and given Mellum2's, the band of 1,024, on the same kernels."""
    cell = cells.load_cell(added_root, bench_roots.ADDED_CELL)
    like = cells.load_cell(added_root, "mellum2_l4_e8_s8192_resident")
    read = cells.load_reader(cell, name)
    text = "%k = bf16[128,8192,128]{2,1,0} custom-call(...)"
    ops = [xplane.Op(i * 1e7, i * 1e7 + 4e6, n, text, "XLA Ops")
           for i, n in enumerate(names)]
    trace = xplane.Trace([xplane.DevicePlane("/device:TPU:0", ops)])
    for c, window in ((cell, 512), (like, 1024)):
        pairs = window_costs.band_pairs(8192, window)
        least = passes * 4 * 128 * 128 * pairs / 197e12
        got = read({"trace": trace, "peak": PEAK, "config": c.config})
        assert got == pytest.approx(100 * least / (4e-3 * len(names)))
