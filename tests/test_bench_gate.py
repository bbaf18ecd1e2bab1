"""Perf-regression gate (tools/bench_gate.py): the synthetic decision
table, a recorded trajectory on disk (its last round must pass against
all of them), and the regressions the gate exists to flag (10%
throughput, 3x compile_s, tail blowup)."""

import copy
import json

from tools import bench_gate



def _checks_by(checks, name):
    return [c for c in checks if c["check"] == name]


# ---------------------------------------------------------------------------
# decision table on synthetic reports (the tier-1 self-check wire)
# ---------------------------------------------------------------------------

def test_self_check_decision_table(capsys):
    assert bench_gate.self_check() == 0
    out = capsys.readouterr().out
    assert "0 wrong verdict(s)" in out
    # And via the CLI entry point (the CI wire).
    assert bench_gate.main(["--self-check"]) == 0


def test_gate_flags_throughput_and_compile_regressions():
    baselines = [bench_gate._synth(990.0), bench_gate._synth(1000.0),
                 bench_gate._synth(1010.0)]
    # 10% throughput regression → the throughput checks fail.
    checks = bench_gate.gate(bench_gate._synth(ips=900.0), baselines)
    bad = [c for c in checks if not c["ok"]]
    assert bad and all(c["check"] == "throughput" for c in bad)
    # 3x compile_s → only the compile check fails.
    checks = bench_gate.gate(bench_gate._synth(compile_s=60.0),
                             baselines)
    bad = [c for c in checks if not c["ok"]]
    assert [c["check"] for c in bad] == ["compile_s"]
    # Tail regression the mean hides: p99 alone blows up.
    checks = bench_gate.gate(bench_gate._synth(p99=2.4), baselines)
    bad = [c for c in checks if not c["ok"]]
    assert [c["check"] for c in bad] == ["step_tail_p99"]
    # Tolerances are honored: a wide-open throughput tolerance passes
    # the same 10% regression.
    checks = bench_gate.gate(bench_gate._synth(ips=900.0), baselines,
                             tol_throughput=0.5)
    assert all(c["ok"] for c in _checks_by(checks, "throughput"))
    # Metrics absent from the baselines are skipped, never failed.
    bare = [{"metric": "train_throughput", "value": 1000.0}]
    checks = bench_gate.gate(bench_gate._synth(), bare)
    assert all(c["ok"] for c in checks)
    assert not _checks_by(checks, "compile_s")


# ---------------------------------------------------------------------------
# a recorded trajectory on disk (files, wrappers, the CLI entry point)
# ---------------------------------------------------------------------------

def _write_trajectory(tmp_path):
    """Five rounds as the driver wraps them, built with ``_synth``."""
    for n, ips in enumerate((990.0, 1010.0, 1000.0, 985.0, 1005.0), 1):
        (tmp_path / f"round_{n:02d}.json").write_text(json.dumps(
            {"n": n, "rc": 0, "parsed": bench_gate._synth(ips)}))
    return str(tmp_path / "round_*.json"), str(tmp_path / "round_05.json")


def test_last_round_passes_the_recorded_trajectory(tmp_path, capsys):
    baselines, last = _write_trajectory(tmp_path)
    rc = bench_gate.main([last, "--baselines", baselines])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "PASS" in out and "REGRESSION" not in out


def test_synthetic_10pct_regression_of_last_round_fails(tmp_path, capsys):
    baselines, last = _write_trajectory(tmp_path)
    report = bench_gate.load_report(last)
    slow = copy.deepcopy(report)
    slow["value"] *= 0.9
    for row in bench_gate.ROW_KEYS:
        if isinstance(slow.get(row), dict):
            slow[row]["images_per_sec_per_chip"] *= 0.9
            if "mfu" in slow[row]:
                slow[row]["mfu"] *= 0.9
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps(slow))
    rc = bench_gate.main([str(cand), "--baselines", baselines,
                          "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["pass"] is False
    bad = [c for c in doc["checks"] if not c["ok"]]
    assert any(c["check"] == "throughput" for c in bad)


def test_load_report_shapes(tmp_path):
    # Driver wrapper and raw bench stdout both load to the same doc.
    _, last = _write_trajectory(tmp_path)
    wrapped = bench_gate.load_report(last)
    assert wrapped == bench_gate._synth(1005.0)
    raw = tmp_path / "raw.json"
    raw.write_text(json.dumps(wrapped))
    assert bench_gate.load_report(str(raw)) == wrapped
    bogus = tmp_path / "bogus.json"
    bogus.write_text(json.dumps({"metric": "other"}))
    try:
        bench_gate.load_report(str(bogus))
        assert False, "non-bench report must be rejected"
    except ValueError:
        pass
