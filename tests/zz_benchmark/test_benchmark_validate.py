"""The validator of the result line, on recorded lines: a sound untraced
and a sound traced line pass, and each way a line has gone wrong is named."""

import copy
import json

import pytest

from benchmark.lib import validate

E2E = [{"name": "img_per_s_per_chip", "unit": "img/s/chip"},
       {"name": "setup_s", "unit": "s"}]
LAYER = [{"name": "step.mfu_pct", "unit": "%"},
         {"name": "step.device_ms", "unit": "ms"},
         {"name": "device.idle_pct", "unit": "%"}]

UNTRACED = {
    "correct": True, "attempted": 400, "failed": 0,
    "metrics": {"img_per_s_per_chip": {"value": 320166.4, "unit": "img/s/chip"},
                "setup_s": {"value": 41.5, "unit": "s"}},
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
               "memory_peak_bytes": 9847934976},
    "compared": {"loss": [1e-4, 1e-2]}}
TRACED = {
    "correct": True, "attempted": 60, "failed": 0,
    "metrics": {"step.mfu_pct": {"value": 14.2, "unit": "%"},
                "step.device_ms": {"value": 50.2, "unit": "ms"},
                "device.idle_pct": {"value": 1.1, "unit": "%"}},
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
               "memory_peak_bytes": 9847934976, "busy_s": 2.01,
               "window_s": 2.04},
    "breakdown": {"device_ops": [["fusion.1", 1.2]],
                  "idle_gaps": [["dispatch", 0.01]]},
    "compared": {"loss": [1e-4, 1e-2]}}


def _problems(obj, traced):
    return validate.problems(json.dumps(obj), LAYER if traced else E2E,
                             4 if traced else 1, traced)


def test_sound_lines_pass():
    assert _problems(UNTRACED, False) == []
    assert _problems(TRACED, True) == []


def _set(path, value):
    def edit(obj):
        for k in path[:-1]:
            obj = obj[k]
        if value is _DROP:
            del obj[path[-1]]
        else:
            obj[path[-1]] = value
    return edit


_DROP = object()

BROKEN = {
    "no_correct": (False, _set(["correct"], _DROP), "correct"),
    "correct_not_bool": (False, _set(["correct"], "yes"), "correct"),
    "no_device": (False, _set(["device"], _DROP), "device"),
    "metric_missing": (False, _set(["metrics", "setup_s"], _DROP), "setup_s"),
    "metric_bare_number": (False, _set(["metrics", "setup_s"], 41.5),
                           "value and a unit"),
    "metric_nan": (False, _set(["metrics", "setup_s", "value"],
                               float("nan")), "finite"),
    "metric_foreign": (False, _set(["metrics", "extra"],
                                   {"value": 1, "unit": "s"}), "not one of"),
    "unit_with_space": (False, _set(["metrics", "setup_s", "unit"],
                                    "s per"), "unit"),
    "unit_differs": (False, _set(["metrics", "setup_s", "unit"], "ms"),
                     "not 's'"),
    "count_differs": (False, _set(["device", "count"], 4), "count"),
    "no_memory": (False, _set(["device", "memory_peak_bytes"], 0),
                  "memory_peak_bytes"),
    "nothing_attempted": (False, _set(["attempted"], 0), "attempted"),
    "busy_over_window": (True, _set(["device", "busy_s"], 8.1), "busy_s"),
    "busy_zero": (True, _set(["device", "busy_s"], 0.0), "busy_s"),
    "busy_missing": (True, _set(["device", "busy_s"], _DROP), "busy_s"),
    "share_over_100": (True, _set(["metrics", "step.mfu_pct", "value"],
                                  104.0), "> 100"),
    "share_negative": (True, _set(["metrics", "device.idle_pct", "value"],
                                  -0.5), "negative"),
    "breakdown_too_long": (True, _set(["breakdown", "device_ops"],
                                      [["x", 1.0]] * 11), "breakdown"),
}


@pytest.mark.parametrize("case", sorted(BROKEN))
def test_each_fault_of_a_line_is_named(case):
    traced, edit, says = BROKEN[case]
    obj = copy.deepcopy(TRACED if traced else UNTRACED)
    edit(obj)
    found = validate.problems(json.dumps(obj), LAYER if traced else E2E,
                              4 if traced else 1, traced)
    assert found and any(says in f for f in found), found


@pytest.mark.parametrize("line", ["", "not json", "[1, 2]",
                                  '{"a": 1}\n{"b": 2}'])
def test_what_is_not_one_json_object_is_refused(line):
    assert validate.problems(line, E2E, 1, False)


def test_compared_comes_last():
    obj = {"compared": {}, **{k: v for k, v in UNTRACED.items()
                              if k != "compared"}}
    assert any("last" in f for f in _problems(obj, False))


def test_a_metric_with_nothing_to_read_is_left_out_not_zeroed():
    """A reader that finds nothing returns nothing: the line then lacks
    the metric, and says so to the validator; it may not carry it."""
    line = copy.deepcopy(TRACED)
    del line["metrics"]["step.device_ms"]
    text = json.dumps(line)
    assert "is missing" in validate.problems(text, LAYER, 4, True)[0]
    assert validate.problems(text, LAYER, 4, True,
                             nothing_to_read=["step.device_ms"]) == []
    assert validate.problems(json.dumps(TRACED), LAYER, 4, True,
                             nothing_to_read=["step.device_ms"]) \
        == ["metric 'step.device_ms' is not one of this run's"]
    bare = copy.deepcopy(TRACED)
    bare["metrics"] = {}
    assert "no metric found anything to read" in validate.problems(
        json.dumps(bare), LAYER, 4, True,
        nothing_to_read=[m["name"] for m in LAYER])
