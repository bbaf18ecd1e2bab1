"""Run-wide trace aggregation (tools/trace_aggregate.py): heartbeat-
wallclock clock alignment, the (task, step) timeline, the step-skew /
straggler table, event merging, and the merged Perfetto document. The
2-process cluster-sim integration run lives in tests/test_cluster.py
(the sim already produces real streams there)."""

import json

import pytest

from tools import trace_aggregate as agg_lib


def _rec(kind, t, task, **fields):
    return {"kind": kind, "t": round(t, 4), "task": task, **fields}


def _stream(task, unix0, steps, lag_s=0.0, events=()):
    """A schema-shaped stream for one host whose logger started at unix
    time ``unix0``: heartbeats (with wallclock), train rows, spans."""
    recs = []
    for i, step in enumerate(steps):
        t = 1.0 + i * 2.0 + lag_s
        recs.append(_rec("heartbeat", t, task, step=step,
                         process_id=task, phase="train",
                         wallclock=round(unix0 + t, 3)))
        recs.append(_rec("train", t + 0.5, task, step=step, loss=1.0,
                         train_accuracy=0.5, images_per_sec=100.0,
                         lr=0.1, device_step_ms=12.0,
                         drain_wait_ms=5.0))
        recs.append(_rec("span", t + 0.6, task, step=step,
                         name="dispatch", start_s=t + 0.1, dur_s=0.3,
                         depth=0))
    for kind, t, fields in events:
        recs.append(_rec(kind, t, task, **fields))
    return recs


@pytest.fixture
def two_streams(tmp_path):
    """Host 0's logger started at unix 1000.0; host 1's started 5 s
    EARLIER (995.0) but it reaches each step 0.25 s behind host 0 in
    aligned wall terms — exactly the case raw ``t`` comparison gets
    backwards and wallclock alignment gets right."""
    a = _stream(0, 1000.0, [10, 20, 30],
                events=[("peer_lost", 7.0,
                         {"step": 30, "process_id": 1,
                          "reason": "stale_heartbeat"})])
    # Host 1 wall for step s = 995.0 + t; lag chosen so aligned wall is
    # host0's + 0.25 (t_h1 = t_h0 + 5.0 + 0.25).
    b = _stream(1, 995.0, [10, 20], lag_s=5.25)
    pa, pb = tmp_path / "m0.jsonl", tmp_path / "m1.jsonl"
    for path, recs in ((pa, a), (pb, b)):
        path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return str(pa), str(pb)


def test_clock_offset_from_heartbeats(two_streams):
    pa, pb = two_streams
    assert agg_lib.clock_offset(agg_lib.load_stream(pa)) == \
        pytest.approx(1000.0, abs=1e-3)
    assert agg_lib.clock_offset(agg_lib.load_stream(pb)) == \
        pytest.approx(995.0, abs=1e-3)
    assert agg_lib.clock_offset([]) is None


def test_aggregate_timeline_counts_and_skew(two_streams):
    pa, pb = two_streams
    agg = agg_lib.aggregate([pa, pb])
    assert agg["aligned_hosts"] == 2

    # Per-host step counts must match the individual streams EXACTLY.
    for host in agg["hosts"]:
        direct = [r["step"] for r in agg_lib.load_stream(host["path"])
                  if r["kind"] == "train"]
        assert host["train_steps"] == direct
        assert host["train_rows"] == len(direct)
    by_task = {h["task"]: h for h in agg["hosts"]}
    assert by_task[0]["train_steps"] == [10, 20, 30]
    assert by_task[1]["train_steps"] == [10, 20]

    # Timeline keyed (task, step): every step each host reported, and
    # only those.
    assert sorted(agg["timeline"][0]) == [10, 20, 30]
    assert sorted(agg["timeline"][1]) == [10, 20]
    assert "train" in agg["timeline"][1][20]["kinds"]

    # Skew: steps 10 and 20 are shared; host 1 arrives 0.25 s later in
    # ALIGNED wall time (its raw t is smaller — alignment is what makes
    # the comparison meaningful).
    skew = agg["skew"]
    assert skew["steps_compared"] == 2
    assert skew["max_spread_s"] == pytest.approx(0.25, abs=1e-3)
    assert skew["laggard_counts"] == {1: 2}

    # The peer_lost event surfaced on the merged event list.
    kinds = [e["kind"] for e in agg["events"]]
    assert "peer_lost" in kinds
    ev = agg["events"][kinds.index("peer_lost")]
    assert ev["task"] == 0 and ev["reason"] == "stale_heartbeat"

    # Text report renders the host table and skew section.
    out = agg_lib.render(agg)
    assert "task 0" in out and "step skew" in out \
        and "peer_lost" in out


def test_aggregate_unaligned_stream_flagged(tmp_path, two_streams):
    pa, _ = two_streams
    # A stream with no heartbeats (single-process run) stays unaligned.
    pc = tmp_path / "m2.jsonl"
    pc.write_text(json.dumps(
        {"kind": "train", "t": 1.0, "task": 2, "step": 10, "loss": 1.0,
         "train_accuracy": 0.5, "images_per_sec": 50.0, "lr": 0.1,
         "device_step_ms": None, "drain_wait_ms": None}) + "\n")
    agg = agg_lib.aggregate([pa, str(pc)])
    by_task = {h["task"]: h for h in agg["hosts"]}
    assert by_task[2]["offset_unix"] is None
    assert agg["aligned_hosts"] == 1
    # Unaligned hosts never enter the skew comparison.
    assert agg["skew"]["steps_compared"] == 0
    assert "UNALIGNED" in agg_lib.render(agg)


def test_merged_trace_document(two_streams):
    pa, pb = two_streams
    doc = agg_lib.build_merged_trace([pa, pb])
    evs = doc["traceEvents"]
    assert evs
    pids = {e.get("pid") for e in evs}
    assert {0, 1} <= pids
    span_x = [e for e in evs if e.get("ph") == "X"]
    counters = [e for e in evs if e.get("ph") == "C"]
    instants = [e for e in evs if e.get("ph") == "i"]
    assert span_x and counters and instants
    # Span lanes land on the SHARED clock: host 1's step-10 dispatch
    # sits ~0.25 s after host 0's, not 5.25 s before.
    def span_ts(pid):
        return min(e["ts"] for e in span_x if e["pid"] == pid)
    assert span_ts(1) - span_ts(0) == pytest.approx(0.25e6, rel=0.05)


def test_cli_main(two_streams, tmp_path, capsys):
    pa, pb = two_streams
    out_path = str(tmp_path / "merged.json")
    assert agg_lib.main([pa, pb, "--out", out_path]) == 0
    with open(out_path) as f:
        doc = json.load(f)
    assert doc["traceEvents"]
    captured = capsys.readouterr()
    assert "step skew" in captured.out
    # JSON mode emits the aggregation for tooling.
    assert agg_lib.main([pa, pb, "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["skew"]["steps_compared"] == 2
