"""Device-time attribution (utils/devprof.py): capture-spec parsing, op
bucketing, trace parsing, the boundary step-time estimator — and the
ISSUE-8 acceptance smoke: a CPU run with --profile_at_steps whose
stream carries schema-clean `devtime` records and train rows with
`device_step_ms`, rendered by telemetry_report in both formats."""

import json
import os
import subprocess
import sys

import pytest

from dml_cnn_cifar10_tpu.utils import devprof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# spec parsing and op bucketing
# ---------------------------------------------------------------------------

def test_parse_profile_at_steps():
    assert devprof.parse_profile_at_steps(None) is None
    assert devprof.parse_profile_at_steps("") is None
    assert devprof.parse_profile_at_steps("100:20") == (100, 20)
    assert devprof.parse_profile_at_steps("0:1") == (0, 1)
    for bad in ("100", "a:b", "5:0", "-1:5", "1:2:3"):
        with pytest.raises(ValueError, match="profile_at_steps"):
            devprof.parse_profile_at_steps(bad)


def test_classify_op_buckets():
    for name in ("all-reduce.1", "all-gather-start",
                 "reduce-scatter.3", "all-to-all",
                 "collective-permute-done", "fusion.all_reduce"):
        assert devprof.classify_op(name) == "collective", name
    for name in ("infeed.2", "outfeed", "copy-start.1", "copy.3",
                 "MemcpyD2H", "transfer"):
        assert devprof.classify_op(name) == "infeed", name
    for name in ("fusion.123", "convolution.2", "dot_general",
                 "fwd_bwd/conv2d", "optimizer/add.4"):
        assert devprof.classify_op(name) == "compute", name


# ---------------------------------------------------------------------------
# trace parsing (synthetic Chrome docs — no profiler involved)
# ---------------------------------------------------------------------------

def _doc(lane_name, pid=7):
    """One device lane: 2 compute ops, 1 collective, 1 infeed."""
    return {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": pid,
         "args": {"name": lane_name}},
        {"ph": "X", "name": "fusion.1", "pid": pid, "tid": 0,
         "ts": 0.0, "dur": 600.0},
        {"ph": "X", "name": "fusion.1", "pid": pid, "tid": 0,
         "ts": 700.0, "dur": 400.0},
        {"ph": "X", "name": "all-reduce.2", "pid": pid, "tid": 0,
         "ts": 1200.0, "dur": 300.0},
        {"ph": "X", "name": "infeed.3", "pid": pid, "tid": 0,
         "ts": 1600.0, "dur": 100.0},
    ]}


def test_parse_trace_doc_buckets_and_topk():
    lanes = devprof.parse_trace_doc(_doc("/device:TPU:0"), top_k=2)
    assert len(lanes) == 1
    lane = lanes[0]
    assert lane["device"] == "/device:TPU:0"
    assert lane["compute_ms"] == pytest.approx(1.0)
    assert lane["collective_ms"] == pytest.approx(0.3)
    assert lane["infeed_ms"] == pytest.approx(0.1)
    assert lane["total_ms"] == pytest.approx(1.4)
    assert lane["window_ms"] == pytest.approx(1.7)   # 0 .. 1700 us
    # top_k=2 keeps the two largest ops, fracs against the lane total.
    assert [op["name"] for op in lane["top_ops"]] == ["fusion.1",
                                                      "all-reduce.2"]
    assert lane["top_ops"][0]["calls"] == 2
    assert lane["top_ops"][0]["frac"] == pytest.approx(1.0 / 1.4,
                                                       abs=1e-3)
    assert lane["top_ops"][1]["bucket"] == "collective"


def test_parse_trace_doc_prefers_device_lanes_with_host_fallback():
    # Device + host lanes present: host lane excluded.
    doc = _doc("/device:TPU:0", pid=7)
    doc["traceEvents"] += _doc("/host:CPU", pid=9)["traceEvents"]
    lanes = devprof.parse_trace_doc(doc)
    assert [ln["device"] for ln in lanes] == ["/device:TPU:0"]
    # Host lanes only (the CPU backend): fall back so the record shape
    # survives on every platform.
    lanes = devprof.parse_trace_doc(_doc("/host:CPU", pid=9))
    assert [ln["device"] for ln in lanes] == ["/host:CPU"]
    assert devprof.parse_trace_doc({"traceEvents": []}) == []


# ---------------------------------------------------------------------------
# boundary step-time estimator
# ---------------------------------------------------------------------------

def test_device_step_estimator_math():
    est = devprof.DeviceStepEstimator()
    # No mark yet: device_step unknown, drain wait still reported.
    dev, drain = est.boundary(10, drain_start=1.0, drain_end=1.25)
    assert dev is None and drain == pytest.approx(250.0)
    est.mark(10, now=100.0)
    # 10 steps between mark and boundary; drain ends 2 s after mark.
    dev, drain = est.boundary(20, drain_start=101.5, drain_end=102.0)
    assert dev == pytest.approx(200.0)       # 2 s / 10 steps
    assert drain == pytest.approx(500.0)
    # Zero-step window (mark at the boundary step) degrades to None.
    est.mark(20, now=200.0)
    dev, _ = est.boundary(20, drain_start=200.1, drain_end=200.2)
    assert dev is None


# ---------------------------------------------------------------------------
# acceptance smoke: real Trainer run on CPU with a capture window
# ---------------------------------------------------------------------------

def test_profile_at_steps_trainer_run(tmp_path):
    """Acceptance smoke, via the real CLI in a SINGLE-device
    subprocess: the in-process test mesh simulates 8 CPU devices whose
    executor threads busy-wait — profiling that floods the trace with
    millions of spin events and the profiler's stop/export takes
    minutes. One real CPU device keeps the same code path (window arm →
    drained-boundary stop → parse → devtime emit) at test speed, and
    covers the --profile_at_steps flag end-to-end."""
    log_dir = str(tmp_path / "logs")
    jsonl = str(tmp_path / "m.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "dml_cnn_cifar10_tpu",
         "--dataset", "synthetic", "--data_dir", str(tmp_path / "d"),
         "--synthetic_train_records", "256",
         "--log_dir", log_dir, "--metrics_jsonl", jsonl,
         "--batch_size", "32", "--total_steps", "10",
         "--output_every", "2", "--eval_every", "10",
         "--checkpoint_every", "10", "--learning_rate", "0.01",
         "--use_native_loader", "false",
         "--profile_at_steps", "4:2"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "[devprof]" in proc.stdout     # the attribution narrator line

    with open(jsonl) as f:
        recs = [json.loads(line) for line in f]
    devs = [r for r in recs if r["kind"] == "devtime"]
    assert devs, "capture window must emit devtime records"
    for r in devs:
        assert r["step"] >= 6                # stopped at/after 4 + 2
        assert isinstance(r["top_ops"], list) and r["top_ops"]
        total = (r["compute_ms"] + r["collective_ms"]
                 + r["infeed_ms"])
        assert total == pytest.approx(r["total_ms"], abs=0.01)
    # The trace itself landed under the default <log_dir>/devprof.
    assert os.path.isdir(os.path.join(log_dir, "devprof"))

    # Always-on estimator: every train row carries the keys; after the
    # first window they are real numbers.
    trains = [r for r in recs if r["kind"] == "train"]
    assert trains
    for r in trains:
        assert "device_step_ms" in r and "drain_wait_ms" in r
    assert any(isinstance(r["device_step_ms"], (int, float))
               for r in trains)

    # Schema-clean (devtime + the new train keys are registered kinds).
    from tools import check_jsonl_schema
    assert check_jsonl_schema.check_file(jsonl, strict=True) == []

    # Both report renderers cover the new sections.
    from tools import telemetry_report
    out = telemetry_report.summarize(jsonl)
    assert "device-time attribution" in out
    assert "device step time" in out
    doc = telemetry_report.summarize_json(jsonl)
    assert doc["devtime"] and doc["device_split"]["boundaries"] > 0
    assert doc["device_split"]["device_step_ms_p50"] > 0


def test_profile_window_fail_open(tmp_path, capsys, monkeypatch):
    """Attribution must never kill a training run: a profiler that
    fails to start, and a capture that leaves no parseable trace, both
    degrade to a warning."""
    import jax

    # Start failure → window done, loop continues.
    def boom(_dir):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    win = devprof.ProfileWindow(0, 1, str(tmp_path / "a"))
    win.maybe_start(0)
    assert win.state == "done"
    assert "start failed" in capsys.readouterr().err

    # Clean start/stop but nothing written → "no parseable trace".
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    win = devprof.ProfileWindow(0, 1, str(tmp_path / "b"))
    win.maybe_start(0)
    assert win.state == "active"
    # Not drained / before the stop step: no-op.
    win.maybe_stop(5, drained=False)
    win.maybe_stop(0, drained=True)
    assert win.state == "active"
    win.maybe_stop(5, drained=True)
    assert win.state == "done"
    assert "no parseable trace" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# instruction -> layer: scope_map
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name,want", [
    ("jit(chunk)/while/body/closed_call/fwd_bwd/jvp(conv1)/"
     "conv_general_dilated", ("fwd_bwd/conv1", "conv", "forward", "")),
    ("jit(chunk)/while/body/closed_call/fwd_bwd/transpose(jvp(conv1))/"
     "conv_general_dilated", ("fwd_bwd/conv1", "conv", "backward", "")),
    ("jit(chunk)/while/body/closed_call/fwd_bwd/transpose(jvp(pool1))/"
     "select_and_scatter", ("fwd_bwd/pool1", "pool", "backward", "")),
    ("jit(chunk)/while/body/closed_call/optimizer/sub",
     ("optimizer", "optimizer", "update", "")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(stage3)/"
     "jvp(block2)/jvp(conv2)/conv_general_dilated",
     ("fwd_bwd/stage3/block2/conv2", "conv", "forward", "")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/"
     "transpose(jvp(stage1/block0))/transpose(jvp(shortcut))/"
     "transpose(jvp(bn))/mul",
     ("fwd_bwd/stage1/block0/shortcut/bn", "norm_act", "backward", "")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(stage1/block0)/"
     "jvp(shortcut)/add_any", ("fwd_bwd/stage1/block0/shortcut", "conv",
                               "forward", "")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(stem)/jvp(bn)/"
     "jit(relu)/max", ("fwd_bwd/stem/bn", "norm_act", "forward", "")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(head)/jvp(fc)/"
     "dot_general", ("fwd_bwd/head/fc", "dense", "forward", "")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(head)/jvp(pool)/"
     "reduce_sum", ("fwd_bwd/head/pool", "pool", "forward", "")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(loss)/"
     "jit(log_softmax)/reduce_max", ("fwd_bwd/loss", "dense", "forward", "")),
    ("jit(chunk_dev)/while/body/decode/_random_crop/nkw,nrwc->nrkc/"
     "dot_general", ("decode/_random_crop/nkw,nrwc->nrkc", "decode",
                     "other", "_random_crop")),
    ("jit(chunk_dev)/index/while/body/xor", ("index", "decode", "other", "")),
    ("jit(chunk_dev)/gather/gather", ("gather", "decode", "other", "")),
    ("jit(ev)/train_acc/conv1/conv_general_dilated",
     ("train_acc/conv1", "conv", "other", "")),
    # a ReLU under no layer's scope; an addition that is a primitive,
    # not the `add` scope; plumbing; nothing at all
    ("jit(chunk)/while/body/closed_call/fwd_bwd/jvp(jit(relu))/max",
     ("fwd_bwd", "norm_act", "forward", "")),
    ("jit(chunk)/while/body/closed_call/fwd_bwd/add",
     ("fwd_bwd", "none", "forward", "")),
    ("jit(chunk)/while/body/dynamic_slice", ("", "none", "other", "")),
    ("", ("", "none", "other", "")),
    # XLA joins the names of merged instructions with ";": the first
    ("jit(chunk)/while/body/closed_call/fwd_bwd/transpose(jvp(loss))/mul;"
     "fwd_bwd/transpose(jvp(loss))/broadcast_in_dim",
     ("fwd_bwd/loss", "dense", "backward", "")),
    # recorded from the three decoders' dispatches, compiled for a v5e: a
    # sublayer's part; a forward formed again under `jax.checkpoint` is its
    # own pass and the marker is no scope; the transposed product beside it
    # sits under `checkpoint` alone and stays backward
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(layer0)/while/body/"
     "closed_call/attn_window/rotary/mul",
     ("fwd_bwd/layer0/attn_window/rotary", "window_attention", "forward",
      "rotary")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(jvp())/while/"
     "body/closed_call/pass/layer3/pass/layer3/checkpoint/"
     "rematted_computation/attn/qkv/dot_general",
     ("fwd_bwd/pass/layer3/pass/layer3/attn/qkv", "attention", "recompute",
      "qkv")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(jvp())/while/"
     "body/closed_call/pass/layer3/pass/layer3/checkpoint/attn/qkv/"
     "dot_general",
     ("fwd_bwd/pass/layer3/pass/layer3/attn/qkv", "attention", "backward",
      "qkv")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(jvp(layer2))/"
     "while/body/closed_call/checkpoint/rematted_computation/short_conv/in/"
     "dot_general",
     ("fwd_bwd/layer2/short_conv/in", "short_conv", "recompute", "in")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(jvp(layer1))/"
     "moe/fwd_bwd/jvp(layer1)/moe/checkpoint/rematted_computation/route/"
     "top_k",
     ("fwd_bwd/layer1/moe/fwd_bwd/layer1/moe/route", "route", "recompute",
      "")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(jvp())/while/"
     "body/closed_call/pass/layer0/pass/layer0/checkpoint/attn/flash/"
     "jit(flash_attention)/flash_bwd_dkv/pallas_call",
     ("fwd_bwd/pass/layer0/pass/layer0/attn/flash/flash_bwd_dkv",
      "attention", "backward", "flash")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(layer0)/moe/combine/"
     "jit(sum_rows_pallas)/sum_rows_by_token/pallas_call",
     ("fwd_bwd/layer0/moe/combine/sum_rows_by_token", "route", "forward",
      "sum_rows_by_token")),
    # what a `custom_vjp` forms again by its own backward rule (the
    # experts' products in their written-out backward loop, the blockwise
    # loss's logits) is under no `jax.checkpoint`: backward
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(fwd_bwd)/"
     "jvp(layer4)/moe/while/body/experts/jvp()/convert_element_type",
     ("fwd_bwd/fwd_bwd/layer4/moe/experts", "expert", "backward", "")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(fwd_bwd)/"
     "jvp(head)/loss/while/body/closed_call/dot_general",
     ("fwd_bwd/fwd_bwd/head/loss", "dense", "backward", "")),
    # the marker outside any backward pass is plumbing and no more
    ("jit(f)/fwd_bwd/jvp(layer0)/checkpoint/rematted_computation/mlp/tanh",
     ("fwd_bwd/layer0/mlp", "mlp", "forward", "")),
])
def test_parse_op_name(op_name, want):
    assert devprof.parse_op_name(op_name) == want


_HLO = """HloModule jit_chunk, is_scheduled=true

%fused_relu (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %c0 = f32[] constant(0), metadata={op_name="jit(chunk)/while/body/closed_call/fwd_bwd/transpose(jvp(loss))/jit(log_softmax)"}
  %b0 = f32[8]{0} broadcast(%c0), dimensions={}
  ROOT %max.1 = f32[8]{0} maximum(%p0, %b0), metadata={op_name="jit(chunk)/while/body/closed_call/fwd_bwd/jvp(conv1)/jit(relu)/max"}
}

%fused_pool (p0.1: f32[8]) -> f32[4] {
  %p0.1 = f32[8]{0} parameter(0)
  %relu_fusion.1 = f32[8]{0} fusion(%p0.1), kind=kLoop, calls=%fused_relu, metadata={op_name="jit(chunk)/while/body/closed_call/fwd_bwd/jvp(conv1)/jit(relu)/max"}
  ROOT %rw.1 = f32[4]{0} reduce-window(%relu_fusion.1), to_apply=%region_max, metadata={op_name="jit(chunk)/while/body/closed_call/fwd_bwd/jvp(pool1)/reduce_window_max"}
}

%region_max (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %m = f32[] maximum(%a, %b), metadata={op_name="reduce_window_max"}
}

%fused_mask (p0.2: f32[8]) -> u32[1] {
  %p0.2 = f32[8]{0} parameter(0)
  %gt.1 = pred[8]{0} compare(%p0.2, %p0.2), direction=GT, metadata={op_name="jit(chunk)/while/body/closed_call/fwd_bwd/jvp(conv1)/gt"}
  %cv.1 = u32[8]{0} convert(%gt.1)
  ROOT %red.1 = u32[1]{0} reduce(%cv.1), to_apply=%region_max
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%t), index=1
  %copy-start.9 = (f32[8]{0}, f32[8]{0:S(1)}, u32[]) copy-start(%gte.1)
  %copy-done.9 = f32[8]{0:S(1)} copy-done(%copy-start.9)
  %copy.3 = f32[8]{0} copy(%gte.1)
  %conv.1 = f32[8]{0} convolution(%copy-done.9, %gte.1), dim_labels=b0f_0io->b0f, metadata={op_name="jit(chunk)/while/body/closed_call/fwd_bwd/jvp(conv1)/conv_general_dilated" stack_frame_id=8}
  %fusion.7 = f32[4]{0} fusion(%conv.1), kind=kOutput, calls=%fused_pool, metadata={op_name="jit(chunk)/while/body/closed_call/fwd_bwd/jvp(pool1)/reduce_window_max"}
  %mask_fusion.2 = u32[1]{0} fusion(%conv.1), kind=kLoop, calls=%fused_mask
  %sas.3 = f32[8]{0} select-and-scatter(%conv.1, %fusion.7), select=%region_max, scatter=%region_max, metadata={op_name="jit(chunk)/while/body/closed_call/fwd_bwd/transpose(jvp(pool1))/select_and_scatter"}
  %optimizer.5 = f32[8]{0} custom-call(%sas.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk)/while/body/closed_call/optimizer/pallas_call"}
  %call.1 = f32[8]{0} call(%optimizer.5), to_apply=%called
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%copy.3, %call.1)
}

%called (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%x), metadata={op_name="jit(chunk)/while/body/closed_call/fwd_bwd/jvp(loss)/neg"}
}

%cond (t.1: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

%never_called (y: f32[8]) -> f32[8] {
  ROOT %y = f32[8]{0} parameter(0)
}

ENTRY %main (arg: f32[8]) -> f32[8] {
  %arg = f32[8]{0} parameter(0)
  %fusion.7.entry = f32[8]{0} copy(%arg), metadata={op_name="jit(chunk)/index/xor"}
  %tuple.0 = (s32[], f32[8]{0}) tuple(%arg, %fusion.7.entry)
  %while.1 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(chunk)/while"}
  ROOT %gte.2 = f32[8]{0} get-tuple-element(%while.1), index=1
}
"""


def test_scope_map_of_text_walks_what_runs_and_flags_mixed_fusions():
    module, m = devprof.scope_map_of_text(_HLO)
    assert module == "jit_chunk"
    # every instruction of the entry, the while's body and condition and
    # the called computation; none of a fused computation, a scalar
    # region or a computation nothing runs
    assert set(m) == {
        "arg", "fusion.7.entry", "tuple.0", "while.1", "gte.2",
        "t", "gte.1", "copy-start.9", "copy-done.9", "copy.3", "conv.1",
        "fusion.7", "mask_fusion.2", "sas.3",
        "optimizer.5", "call.1", "tuple.1", "x", "neg.1", "t.1", "lt.1"}
    def five(name):          # the entry without its `inherited` flag
        return m[name][:5]

    assert five("conv.1") == ("fwd_bwd/conv1", "conv", "forward", False, True)
    assert five("sas.3") == ("fwd_bwd/pool1", "pool", "backward", False, True)
    assert m["optimizer.5"][:3] == ("optimizer", "optimizer", "update")
    # conv1's ReLU (a nested fusion) under pool1's window: the root's
    # layer, flagged; the shared constant of another layer does not count
    assert five("fusion.7") == ("fwd_bwd/pool1", "pool", "forward", True, True)
    # no metadata on the fusion or its root: the working instruction
    assert five("mask_fusion.2") == ("fwd_bwd/conv1", "conv", "forward",
                                  False, True)
    # a copy the compiler put in carries no name: a prefetch is its
    # consumer's, one that only the loop's carry takes is nobody's
    assert m["copy-start.9"] == m["copy-done.9"] \
        == m["conv.1"]._replace(inherited=True)
    assert not m["conv.1"].inherited and not m["copy.3"].inherited
    assert five("copy.3") == ("", "none", "other", False, True)
    # a called computation inside the loop is in the loop
    assert five("neg.1") == ("fwd_bwd/loss", "dense", "forward", False, True)
    assert five("fusion.7.entry") == ("index", "decode", "other", False, False)
    assert m["while.1"].kind == "none" and not m["while.1"].in_loop
    assert m["lt.1"].in_loop


def _small_chunk():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def loss_fn(p, x, y):
        with jax.named_scope("conv1"):
            x = jax.nn.relu(lax.conv_general_dilated(
                x, p["c"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["cb"])
        with jax.named_scope("pool1"):
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
        with jax.named_scope("fc1"):
            logits = x.reshape(x.shape[0], -1) @ p["w"] + p["b"]
        with jax.named_scope("loss"):
            return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits)
                                     * jax.nn.one_hot(y, 10), -1))

    def step(p, batch):
        with jax.named_scope("fwd_bwd"):
            loss, g = jax.value_and_grad(loss_fn)(p, *batch)
        with jax.named_scope("optimizer"):
            p = jax.tree.map(lambda a, b: a - 0.1 * b, p, g)
        return p, loss

    def chunk(p, xs, ys):
        return lax.scan(step, p, (xs, ys))

    f32 = jnp.float32
    p = {"c": jax.ShapeDtypeStruct((3, 3, 3, 8), f32),
         "cb": jax.ShapeDtypeStruct((8,), f32),
         "w": jax.ShapeDtypeStruct((4 * 4 * 8, 10), f32),
         "b": jax.ShapeDtypeStruct((10,), f32)}
    xs = jax.ShapeDtypeStruct((3, 4, 8, 8, 3), f32)
    ys = jax.ShapeDtypeStruct((3, 4), jnp.int32)
    return jax.jit(chunk).lower(p, xs, ys).compile()


def test_scope_map_of_a_compiled_scan_of_steps():
    compiled = _small_chunk()
    m = devprof.scope_map(compiled)
    found = {(e.scope, e.kind, e.pass_) for e in m.values() if e.in_loop}
    assert ("fwd_bwd/conv1", "conv", "forward") in found
    assert ("fwd_bwd/conv1", "conv", "backward") in found
    assert ("fwd_bwd/pool1", "pool", "backward") in found
    assert ("optimizer", "optimizer", "update") in found
    assert any(kind == "dense" for _, kind, _ in found)
    # every instruction of the computations that run is accounted for:
    # the text's own count of them
    _, entry, comps = devprof._parse_hlo(compiled.as_text())
    runs, todo = set(), [entry]
    while todo:
        c = todo.pop()
        if c in runs:
            continue
        runs.add(c)
        for ins in comps[c]:
            todo += [t for a, t in ins.called
                     if a in devprof._RUNS.get(ins.opcode, ())]
    assert len(runs) >= 3         # entry, the scan's body and condition
    assert set(m) == {ins.name for c in runs for ins in comps[c]}
    assert sum(e.kind != "none" for e in m.values()) >= 8


def test_register_scope_map_keeps_writes_and_announces(tmp_path):
    devprof.clear_scope_maps()
    seen = []

    class Log:
        def log(self, kind, **fields):
            seen.append((kind, fields))

    class Compiled:
        def as_text(self):
            return _HLO

    out = str(tmp_path / "prof")
    assert devprof.register_scope_map(Compiled(), out, logger=Log(),
                                      step=40) == "jit_chunk"
    assert set(devprof.scope_maps()) == {"jit_chunk"}
    ((kind, rec),) = seen
    assert kind == "scopemap" and rec["step"] == 40
    assert (rec["module"], rec["instructions"], rec["mixed"]) \
        == ("jit_chunk", 21, 1)
    assert rec["mapped"] == 9 and rec["recompute"] == 0
    assert rec["path"] == os.path.join(out, "scopemap_jit_chunk.json")
    with open(rec["path"]) as f:
        doc = json.load(f)
    assert doc["instructions"]["sas.3"] == {
        "scope": "fwd_bwd/pool1", "kind": "pool", "pass": "backward",
        "part": "", "mixed": False, "in_loop": True, "inherited": False}
    # no capture directory: kept and announced, nothing written
    devprof.register_scope_map(Compiled(), None, logger=Log(), step=41)
    assert seen[-1][1]["path"] is None
    devprof.clear_scope_maps()
    assert devprof.scope_maps() == {}


# --- a sublayer's parts, the recomputed pass, a kernel named anew ----------

@pytest.mark.parametrize("scope,kind,part", [
    # the three decoders' kinds, each with the scope after the deciding one
    ("pass/layer0/attn/qkv", "attention", "qkv"),
    ("layer1/attn/qk_norm", "attention", "qk_norm"),
    ("layer1/attn/rotary", "attention", "rotary"),
    ("layer3/attn/flash/flash_fwd", "attention", "flash"),
    ("layer1/attn/out", "attention", "out"),
    ("layer2/attn_window/rotary", "window_attention", "rotary"),
    ("layer2/attn_window/flash/flash_window_bwd_dq", "window_attention",
     "flash"),
    ("layer3/short_conv/in", "short_conv", "in"),
    ("layer3/short_conv/gate_conv", "short_conv", "gate_conv"),
    ("layer3/short_conv/out", "short_conv", "out"),
    ("layer1/moe/combine/sum_rows_by_token", "route", "sum_rows_by_token"),
    ("layer1/moe/dispatch", "route", ""), ("layer1/moe/route", "route", ""),
    ("layer1/moe/experts", "expert", ""), ("layer0/mlp", "mlp", ""),
    ("layer1/moe/ffn_norm", "norm", ""), ("exit/norm", "norm", ""),
    ("embed", "embed", ""), ("exit/head", "exit_head", ""),
    ("exit/gate", "exit_head", ""), ("exit/head/loss", "dense", ""),
    # the image models' layers have no sublayer scopes
    ("conv1", "conv", ""), ("pool1", "pool", ""), ("fc1", "dense", ""),
    ("stage1/block0/shortcut/bn", "norm_act", ""),
    ("stage3/block2/conv2", "conv", ""), ("head/fc", "dense", ""),
])
def test_the_part_is_the_scope_after_the_one_that_decided_the_kind(
        scope, kind, part):
    for path in (f"jit(step)/fwd_bwd/jvp({scope})/dot_general",
                 f"jit(step)/fwd_bwd/transpose(jvp({scope}))/mul"):
        assert devprof.parse_op_name(path)[1::2] == (kind, part), path


@pytest.mark.parametrize("op_name,want", [
    # multi-head latent attention: kind `latent_attention`, each of its
    # parts (the latent's norm is the sublayer's, not kind `norm`)
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(layer2)/while/body/"
     "closed_call/mla/q/dot_general",
     ("fwd_bwd/layer2/mla/q", "latent_attention", "forward", "q")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(layer2)/while/body/"
     "closed_call/mla/kv_down/dot_general",
     ("fwd_bwd/layer2/mla/kv_down", "latent_attention", "forward",
      "kv_down")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(jvp(layer2))/"
     "while/body/closed_call/checkpoint/rematted_computation/mla/kv_norm/"
     "rsqrt",
     ("fwd_bwd/layer2/mla/kv_norm", "latent_attention", "recompute",
      "kv_norm")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(jvp(layer2))/"
     "while/body/closed_call/checkpoint/mla/kv_up/dot_general",
     ("fwd_bwd/layer2/mla/kv_up", "latent_attention", "backward", "kv_up")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(layer0)/while/body/"
     "closed_call/mla/rotary/concatenate",
     ("fwd_bwd/layer0/mla/rotary", "latent_attention", "forward",
      "rotary")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(jvp(layer4))/"
     "while/body/closed_call/checkpoint/mla/flash/jit(flash_attention)/"
     "flash_bwd_dkv/pallas_call",
     ("fwd_bwd/layer4/mla/flash/flash_bwd_dkv", "latent_attention",
      "backward", "flash")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(layer1)/while/body/"
     "closed_call/mla/out/dot_general",
     ("fwd_bwd/layer1/mla/out", "latent_attention", "forward", "out")),
    # the shared experts, chunk by chunk, each formed again under `remat`
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(layer1)/moe/shared/"
     "while/body/closed_call/checkpoint/dot_general",
     ("fwd_bwd/layer1/moe/shared", "shared_expert", "forward", "")),
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(jvp(layer1))/"
     "moe/shared/while/body/closed_call/checkpoint/rematted_computation/"
     "logistic",
     ("fwd_bwd/layer1/moe/shared", "shared_expert", "recompute", "")),
    # the balance loss is the router's
    ("jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(layer1)/moe/route/"
     "reduce_sum", ("fwd_bwd/layer1/moe/route", "route", "forward", "")),
])
def test_the_latent_sublayer_and_the_shared_experts_have_kinds_of_their_own(
        op_name, want):
    assert devprof.parse_op_name(op_name) == want


def _toy_step(remat: bool):
    import jax
    import jax.numpy as jnp

    def layer(p, x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ p["a"]) @ p["b"]

    def loss(p, x):
        f = jax.checkpoint(layer) if remat else layer
        for i in range(2):
            with jax.named_scope(f"layer{i}"):
                x = f(p, x)
        return jnp.sum(x * x)

    def step(p, x):
        with jax.named_scope("fwd_bwd"):
            return jax.grad(loss)(p, x)

    f32 = jnp.float32
    p = {"a": jax.ShapeDtypeStruct((16, 16), f32),
         "b": jax.ShapeDtypeStruct((16, 16), f32)}
    return jax.jit(step).lower(
        p, jax.ShapeDtypeStruct((4, 16), f32)).compile()


@pytest.mark.parametrize("remat", [True, False])
def test_only_a_checkpointed_step_has_recompute_instructions(remat):
    m = devprof.scope_map(_toy_step(remat))
    passes = {e.pass_ for e in m.values()}
    assert {"forward", "backward"} <= passes <= set(devprof.PASSES)
    again = [e for e in m.values() if e.pass_ == "recompute"]
    assert bool(again) == remat
    # the layer's own forward formed again: its kind, and no marker left
    assert all(e.kind == "mlp" and "rematted" not in e.scope
               and "checkpoint" not in e.scope for e in again)
    assert not any("rematted" in e.scope for e in m.values())


_HLO_EXPERTS = """HloModule jit_step, is_scheduled=true

%fused_weigh (p0: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  ROOT %mul.1 = f32[8,4]{1,0} multiply(%p0, %p0), metadata={op_name="jit(step)/fwd_bwd/transpose(fwd_bwd)/jvp(layer1)/moe/while/body/combine/mul"}
}

%fused_rows (p0.1: bf16[8,4]) -> bf16[8,4] {
  %p0.1 = bf16[8,4]{1,0} parameter(0)
  ROOT %gather.1 = bf16[8,4]{1,0} negate(%p0.1), metadata={op_name="jit(step)/fwd_bwd/transpose(fwd_bwd)/jvp(layer1)/moe/while/body/combine/gather"}
}

%fused_act (p0.2: f32[8,4]) -> bf16[8,4] {
  %p0.2 = f32[8,4]{1,0} parameter(0)
  ROOT %cv.1 = bf16[8,4]{1,0} convert(%p0.2), metadata={op_name="jit(step)/fwd_bwd/jvp(layer1)/moe/while/body/experts/convert_element_type"}
}

ENTRY %main (rows: bf16[8,4], w: bf16[2,4,4], sizes: s32[2]) -> (f32[8,4], bf16[8,4], f32[8,4]) {
  %rows = bf16[8,4]{1,0} parameter(0)
  %w = bf16[2,4,4]{2,1,0} parameter(1)
  %sizes = s32[2]{0} parameter(2)
  %ragged-dot-metadata = (s32[3]{0}, s32[1]{0}) custom-call(%sizes), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %gte.1 = s32[3]{0} get-tuple-element(%ragged-dot-metadata), index=0
  %ragged-dot-none.4 = f32[8,4]{1,0} custom-call(%gte.1, %rows, %w), custom_call_target="tpu_custom_call", frontend_attributes={mosaic_fusion_entry_point="true",ragged_dot_tiling="512,512,256"}, metadata={op_name="ragged-dot-none"}
  %act_fusion.1 = bf16[8,4]{1,0} fusion(%ragged-dot-none.4), kind=kLoop, calls=%fused_act, metadata={op_name="jit(step)/fwd_bwd/jvp(layer1)/moe/while/body/experts/convert_element_type"}
  %rows_fusion.2 = bf16[8,4]{1,0} fusion(%rows), kind=kLoop, calls=%fused_rows, metadata={op_name="jit(step)/fwd_bwd/transpose(fwd_bwd)/jvp(layer1)/moe/while/body/combine/gather"}
  %copy.7 = bf16[8,4]{1,0} copy(%rows_fusion.2)
  %ragged-dot-none.3 = f32[8,4]{1,0} custom-call(%gte.1, %copy.7, %w), custom_call_target="tpu_custom_call", frontend_attributes={mosaic_fusion_entry_point="true",ragged_dot_tiling="512,512,256"}, metadata={op_name="ragged-dot-none"}
  %copy.8 = f32[8,4]{1,0} copy(%ragged-dot-none.3)
  %custom-call.9 = f32[8,4]{1,0} custom-call(), custom_call_target="AllocateBuffer"
  %weigh_fusion.5 = f32[8,4]{1,0} fusion(%copy.8, %custom-call.9), kind=kLoop, calls=%fused_weigh, metadata={op_name="jit(step)/fwd_bwd/transpose(fwd_bwd)/jvp(layer1)/moe/while/body/combine/mul"}
  %ragged-dot-none.6 = f32[8,4]{1,0} custom-call(%sizes, %rows, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %tuple.2 = (f32[8,4]{1,0}, bf16[8,4]{1,0}, f32[8,4]{1,0}) tuple(%weigh_fusion.5, %act_fusion.1, %ragged-dot-none.6)
}
"""


_HLO_GMM = """HloModule jit_chunk_dev, is_scheduled=true

%fwd_body (p.1: bf16[8,4]) -> f32[8,4] {
  %p.1 = bf16[8,4]{1,0} parameter(0)
  %sizes.1 = s32[9]{0} constant({0, 0, 0, 0, 0, 0, 0, 0, 0})
  ROOT %gmm.3 = f32[8,4]{1,0} custom-call(%sizes.1, %p.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk_dev)/while/body/closed_call/fwd_bwd/jvp(layer1)/moe/while/body/experts/jit(gmm)/pallas_call"}
}

%fwd_cond (p.2: bf16[8,4]) -> pred[] {
  %p.2 = bf16[8,4]{1,0} parameter(0)
  ROOT %c.2 = pred[] constant(false)
}

%bwd_body (p.3: bf16[8,4]) -> f32[2,4,4] {
  %p.3 = bf16[8,4]{1,0} parameter(0)
  %sizes.3 = s32[9]{0} constant({0, 0, 0, 0, 0, 0, 0, 0, 0})
  %jvp_jit_gmm__.7 = f32[8,4]{1,0} custom-call(%sizes.3, %p.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(fwd_bwd)/jvp(layer1)/moe/while/body/experts/jvp(jit(gmm))/pallas_call"}
  %copy.4 = f32[8,4]{1,0} copy(%jvp_jit_gmm__.7)
  ROOT %jvp_jit_tgmm__.9 = f32[2,4,4]{2,1,0} custom-call(%sizes.3, %p.3, %copy.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk_dev)/while/body/closed_call/fwd_bwd/transpose(fwd_bwd)/jvp(layer1)/moe/while/body/experts/transpose(experts)/jvp(jit(tgmm))/pallas_call"}
}

ENTRY %main (rows: bf16[8,4]) -> (bf16[8,4], bf16[8,4]) {
  %rows = bf16[8,4]{1,0} parameter(0)
  %while.1 = bf16[8,4]{1,0} while(%rows), condition=%fwd_cond, body=%fwd_body
  %while.2 = bf16[8,4]{1,0} while(%rows), condition=%fwd_cond, body=%bwd_body
  ROOT %tuple.3 = (bf16[8,4]{1,0}, bf16[8,4]{1,0}) tuple(%while.1, %while.2)
}
"""


def test_the_grouped_kernels_are_the_experts_by_their_own_names():
    """megablox's ``gmm`` / ``tgmm`` keep the scope they were called under:
    kind ``expert`` by their own ``op_name``, pass ``forward`` in the
    experts' forward loop and ``backward`` in their written-out backward
    loop (the forward formed again there, the input gradients and the
    weight gradients alike), none of them a guess; the copy between two
    of them goes with its consumer."""
    _, m = devprof.scope_map_of_text(_HLO_GMM)
    fwd = m["gmm.3"]
    assert (fwd.scope, fwd.kind, fwd.pass_, fwd.part) \
        == ("fwd_bwd/layer1/moe/experts", "expert", "forward", "")
    assert fwd.in_loop and not fwd.inherited and not fwd.mixed
    again, dw = m["jvp_jit_gmm__.7"], m["jvp_jit_tgmm__.9"]
    assert again[:3] == ("fwd_bwd/fwd_bwd/layer1/moe/experts", "expert",
                         "backward")
    assert dw[:3] == ("fwd_bwd/fwd_bwd/layer1/moe/experts/experts",
                      "expert", "backward")
    assert not again.inherited and not dw.inherited
    assert m["copy.4"] == dw._replace(inherited=True)

def test_a_kernel_the_compiler_named_anew_keeps_its_own_kind():
    """The experts' grouped products become ``ragged-dot-none.<n>`` with
    no scope. One that feeds an instruction under ``moe/combine`` is an
    expert's product of the backward pass (its rows come from there), not
    the router's and no guess; an unnamed copy beside it still goes with
    its consumer."""
    _, m = devprof.scope_map_of_text(_HLO_EXPERTS)
    assert m["weigh_fusion.5"][:3] == (
        "fwd_bwd/fwd_bwd/layer1/moe/combine", "route", "backward")
    dot = m["ragged-dot-none.3"]
    assert (dot.scope, dot.kind, dot.pass_, dot.part) \
        == ("", "expert", "backward", "")
    assert not dot.inherited and not dot.mixed
    # a forward instruction reads this one; the metadata's kernel feeds both
    assert m["ragged-dot-none.4"][1:3] == ("expert", "forward")
    assert m["ragged-dot-metadata"][1:3] == ("expert", "forward")
    assert not m["ragged-dot-metadata"].inherited
    # nothing around it tells a pass: the kind stands, the pass is open
    assert m["ragged-dot-none.6"][1:3] == ("expert", "other")
    # copies are counted with what consumes them, and say so
    assert m["copy.8"] == m["weigh_fusion.5"]._replace(inherited=True)
    assert m["copy.7"] == dot._replace(inherited=True)
    # a custom call that is no kernel of the program's is nobody's
    assert m["custom-call.9"][1:3] == ("none", "other")
    assert not m["custom-call.9"].inherited
