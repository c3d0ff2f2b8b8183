"""End-to-end CLI smoke tests: `python -m distributed_sgd_tpu.main` driven
the way a user drives it (env-config only, Main.scala:122-159 role model).

Each case runs the real entry point in a subprocess on the virtual CPU
mesh with tiny synthetic data and asserts the scenario completed.  This
pins the wiring main.py owns — config parsing, topology selection, engine
construction, checkpoint plumbing — which unit tests don't reach.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_main(tmp_path, extra_env, timeout=240):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "DSGD_SYNTHETIC": "300",
        "DSGD_MAX_EPOCHS": "1",
        "DSGD_NODE_COUNT": "2",
        "DSGD_BATCH_SIZE": "16",
    })
    env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_sgd_tpu.main"],
        cwd=str(tmp_path), env=env, timeout=timeout,
        capture_output=True, text=True,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    return out


def test_dev_mesh_sync(tmp_path):
    out = run_main(tmp_path, {})
    assert "fit done" in out
    assert "engine=mesh" in out


def test_dev_mesh_sync_with_checkpoint(tmp_path):
    ck = str(tmp_path / "ck")
    out = run_main(tmp_path, {"DSGD_CHECKPOINT_DIR": ck})
    assert "checkpoint saved" in out
    # second run resumes instead of restarting
    out2 = run_main(tmp_path, {"DSGD_CHECKPOINT_DIR": ck, "DSGD_MAX_EPOCHS": "2"})
    assert "resumed from checkpoint" in out2


def test_dev_mesh_async_local_sgd(tmp_path):
    out = run_main(tmp_path, {
        "DSGD_ASYNC": "1", "DSGD_ASYNC_MODE": "local_sgd",
        "DSGD_CHECK_EVERY": "50",
    })
    assert "fit done" in out


def test_dev_rpc_sync(tmp_path):
    out = run_main(tmp_path, {"DSGD_ENGINE": "rpc"})
    assert "fit done" in out and "final test loss" in out


def test_invalid_config_fails_fast(tmp_path):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "DSGD_SYNTHETIC": "300",
        "DSGD_KERNEL": "pallas",  # no such family: rejected at config parse
    })
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_sgd_tpu.main"],
        cwd=str(tmp_path), env=env, timeout=120,
        capture_output=True, text=True,
    )
    assert proc.returncode != 0
    assert "kernel" in (proc.stdout + proc.stderr)


def test_dev_rpc_sync_checkpoint_resume(tmp_path):
    """DSGD_ENGINE=rpc sync saves at epoch cadence and a re-run resumes —
    symmetry with test_dev_mesh_sync_with_checkpoint (VERDICT r2 item 2)."""
    ck = str(tmp_path / "ck")
    out = run_main(tmp_path, {"DSGD_ENGINE": "rpc", "DSGD_CHECKPOINT_DIR": ck})
    assert "checkpoint saved" in out
    out2 = run_main(tmp_path, {
        "DSGD_ENGINE": "rpc", "DSGD_CHECKPOINT_DIR": ck, "DSGD_MAX_EPOCHS": "2",
    })
    assert "resumed sync fit from checkpoint" in out2
    # a third run already at max_epochs runs nothing but reports real state
    out3 = run_main(tmp_path, {
        "DSGD_ENGINE": "rpc", "DSGD_CHECKPOINT_DIR": ck, "DSGD_MAX_EPOCHS": "2",
    })
    assert "nothing to run" in out3


def test_serve_role_end_to_end(tmp_path):
    """DSGD_ROLE=serve through the real entry point: train+checkpoint via a
    dev run, start the serving role as a subprocess, wait for readiness
    via the health probe, round-trip a Predict, shut down cleanly."""
    import socket
    import time

    ck = str(tmp_path / "ck")
    run_main(tmp_path, {"DSGD_CHECKPOINT_DIR": ck})  # writes the snapshot

    with socket.socket() as s:  # free port for the serving subprocess
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "DSGD_ROLE": "serve",
        "DSGD_CHECKPOINT_DIR": ck,
        "DSGD_SERVE_PORT": str(port),
        "DSGD_SERVE_CKPT_POLL_S": "0.2",
    })
    proc = subprocess.Popen(
        [sys.executable, "-m", "distributed_sgd_tpu.main"],
        cwd=str(tmp_path), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        from distributed_sgd_tpu.serving.health_probe import probe

        deadline = time.time() + 120
        while time.time() < deadline and not probe(port):
            assert proc.poll() is None, proc.stdout.read()[-3000:]
            time.sleep(0.25)
        assert probe(port), "serve role never became ready"

        from distributed_sgd_tpu.rpc import dsgd_pb2 as pb
        from distributed_sgd_tpu.rpc.service import ServeStub, new_channel

        channel = new_channel("127.0.0.1", port)
        reply = ServeStub(channel).Predict(
            pb.PredictRequest(indices=[1], values=[1.0]), timeout=30)
        channel.close()
        assert reply.model_step >= 1
        assert reply.prediction in (-1.0, 0.0, 1.0)  # hinge label space
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=20)
