"""Preemption handling: SIGTERM mid-training → clean exit with a
checkpoint; restart resumes from the saved step (SURVEY §5 failure
detection, upgraded from the reference's restart-only story)."""

import pytest
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The driver pins the CPU backend via utils.platform.force_cpu (so the
# test does not depend on the caller's environment), then runs the real
# CLI.
DRIVER = """
import sys
from dml_cnn_cifar10_tpu.utils.platform import force_cpu
force_cpu()
from dml_cnn_cifar10_tpu.cli.main import main
sys.exit(main(sys.argv[1:]))
"""


def _args(data_dir, log_dir, total_steps, jsonl=None):
    a = ["--dataset", "synthetic", "--data_dir", data_dir,
         "--log_dir", log_dir, "--total_steps", str(total_steps),
         "--batch_size", "16", "--output_every", "5",
         "--eval_every", "1000000"]
    if jsonl:
        a += ["--metrics_jsonl", jsonl]
    return a


@pytest.mark.slow
def test_sigterm_checkpoints_and_resumes(tmp_path, data_cfg):
    data_dir = data_cfg.data_dir
    log_dir = str(tmp_path / "logs")
    jsonl = str(tmp_path / "m.jsonl")
    script = tmp_path / "driver.py"
    script.write_text(DRIVER)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    p = subprocess.Popen(
        [sys.executable, str(script)] + _args(data_dir, log_dir, 100000,
                                              jsonl),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)
    try:
        # Wait until training demonstrably progresses (first metrics line),
        # then deliver the preemption signal.
        deadline = time.time() + 180
        while time.time() < deadline:
            if os.path.exists(jsonl) and os.path.getsize(jsonl) > 0:
                break
            if p.poll() is not None:
                break
            time.sleep(0.5)
        assert p.poll() is None, \
            f"trainer died early:\n{p.communicate()[0]}"
        p.send_signal(signal.SIGTERM)
        out, _ = p.communicate(timeout=120)
    finally:
        if p.poll() is None:
            p.kill()

    assert p.returncode == 0, f"non-zero exit after SIGTERM:\n{out}"
    assert "[preempt]" in out, f"no preempt line:\n{out}"
    ckpts = [f for f in os.listdir(log_dir) if f.startswith("ckpt_")]
    assert ckpts, f"no checkpoint written on SIGTERM: {os.listdir(log_dir)}"
    saved = max(int(f.split("_")[1].split(".")[0]) for f in ckpts)
    assert saved > 0

    # Restart with a slightly higher stop step: must RESUME (global_step
    # continues past `saved`), not start over.
    out2 = subprocess.run(
        [sys.executable, str(script)] + _args(data_dir, log_dir, saved + 3),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO, timeout=300).stdout
    assert f"done at step {saved + 3}" in out2, out2


# ---- multi-host: one preempted process must not strand its peer ----

MH_WORKER = """
import sys
from dml_cnn_cifar10_tpu.utils.platform import force_cpu
force_cpu()
task_index, n_procs, port, data_dir, log_dir, jsonl = (
    int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4],
    sys.argv[5], sys.argv[6])
import jax
from dml_cnn_cifar10_tpu.config import TrainConfig, DataConfig
from dml_cnn_cifar10_tpu.parallel import multihost
from dml_cnn_cifar10_tpu.train.loop import Trainer

multihost.initialize_from_hosts([f"localhost:{port}"] * n_procs, task_index)
cfg = TrainConfig(
    batch_size=16, total_steps=100000, output_every=5, eval_every=10**6,
    checkpoint_every=10**6, log_dir=log_dir, preempt_sync_every=2,
    metrics_jsonl=jsonl,
    data=DataConfig(dataset="synthetic", data_dir=data_dir,
                    synthetic_train_records=256, synthetic_test_records=64,
                    normalize="scale", use_native_loader=False),
)
cfg.model.logit_relu = False
res = Trainer(cfg, task_index=task_index).fit()
print(f"RESULT step={res.final_step} preempted={res.preempted}", flush=True)
"""


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_multihost_preemption_agrees(tmp_path, data_cfg):
    """SIGTERM delivered to ONE of two SPMD processes: the flag is
    allgathered at a sync boundary, BOTH processes checkpoint and exit
    cleanly at the same step (no peer stranded in a collective)."""
    import dataclasses as dc

    from dml_cnn_cifar10_tpu.data import ensure_dataset

    n = 2
    port = _free_port()
    data_dir = str(tmp_path / "data")
    log_dir = str(tmp_path / "logs")
    ensure_dataset(dc.replace(
        data_cfg, data_dir=data_dir, synthetic_train_records=256,
        synthetic_test_records=64))

    script = tmp_path / "mh_worker.py"
    script.write_text(MH_WORKER)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    jsonls = [str(tmp_path / f"m{i}.jsonl") for i in range(n)]
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(n), str(port),
             data_dir, log_dir, jsonls[i]],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=REPO)
        for i in range(n)
    ]
    try:
        # Wait until training demonstrably progresses (worker 0's metrics
        # line at step 5), then preempt ONLY process 0.
        deadline = time.time() + 240
        while time.time() < deadline:
            if any(p.poll() is not None for p in procs):
                break  # a worker died — fail below with its output
            if os.path.exists(jsonls[0]) and os.path.getsize(jsonls[0]) > 0:
                break
            time.sleep(0.5)
        assert all(p.poll() is None for p in procs), \
            "worker died before preemption:\n" + "\n".join(
                p.communicate()[0] for p in procs if p.poll() is not None)
        procs[0].send_signal(signal.SIGTERM)
        outs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    steps = []
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        lines = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
        assert lines, f"worker {i} produced no RESULT:\n{out}"
        assert "preempted=True" in lines[-1], lines[-1]
        steps.append(int(lines[-1].split("step=")[1].split()[0]))
    assert steps[0] == steps[1], f"processes exited at different steps {steps}"


@pytest.mark.slow
def test_check_numerics_halts_without_poisoned_checkpoint(tmp_path,
                                                          data_cfg):
    """The faithful LR-0.1-on-raw-pixels combo NaNs within a few steps (a
    reference property); with check_numerics the driver halts at the
    metrics boundary and the NaN state is NOT checkpointed."""
    import dataclasses


    from dml_cnn_cifar10_tpu.ckpt import checkpoint as ckpt_lib
    from dml_cnn_cifar10_tpu.train.loop import Trainer
    from tests.conftest import tiny_train_cfg

    cfg = tiny_train_cfg(data_cfg, str(tmp_path), total_steps=20)
    cfg.data = dataclasses.replace(cfg.data, normalize="none")  # raw 0-255
    cfg.optim.learning_rate = 0.1
    cfg.output_every = 10
    cfg.eval_every = 20
    # Checkpoint cadence FIRES BEFORE the first metrics boundary: the
    # guard must halt at the save itself, never persisting NaN weights.
    cfg.checkpoint_every = 5
    cfg.check_numerics = True
    with pytest.raises(FloatingPointError, match="non-finite"):
        Trainer(cfg).fit()
    assert ckpt_lib.all_checkpoint_steps(cfg.log_dir) == []
