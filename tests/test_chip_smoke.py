"""chip_smoke.py stays runnable: tier-1 cannot reach a chip, so it holds the
script to its two CPU-checkable promises — the no-argument form never passes
without a TPU, and the explicit rehearsal drives every phase at tiny size
through the same parent/child code the chip run uses.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

# run the real parent in-process in a FRESH interpreter, then report whether
# it ever pulled jax (or the package, whose submodules import jax) in: a
# parent that did would hold the chip its children need
_PARENT = """
import sys
sys.path.insert(0, {repo!r})
import chip_smoke
rc = chip_smoke.main(["--rehearsal"])
held = [m for m in ("jax", "jaxlib", "distributed_sgd_tpu") if m in sys.modules]
print("PARENT_IMPORTED=" + ",".join(held), file=sys.stderr)
sys.exit(rc)
"""


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if not k.startswith("DSGD_")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_no_argument_form_refuses_without_a_tpu():
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, SMOKE], env=_env(), cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert time.monotonic() - t0 < 60
    # it names what jax found, and prints no result: stdout stays empty
    assert "platform=cpu" in out.stderr
    assert out.stdout == ""


def test_alone_in_a_directory_it_refuses_before_touching_jax(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    # no child is started, so this holds on a chip machine too
    out = subprocess.run([sys.executable, alone], env=_env(),
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=60)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "distributed_sgd_tpu/main.py" in out.stderr
    assert os.listdir(tmp_path) == ["chip_smoke.py"]


@pytest.fixture(scope="module")
def rehearsal():
    # two virtual devices: the smallest host on which the every-device
    # phase and its one-device reference differ
    out = subprocess.run(
        [sys.executable, "-c", _PARENT.format(repo=REPO)],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=2"),
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return out


def test_last_stdout_line_is_the_result_object_and_nothing_more(rehearsal):
    # the driver's contract: exactly {"ok", "device": {"platform", "kind",
    # "count"}} — one extra key and the chip check refuses the PR
    last = rehearsal.stdout.rstrip("\n").splitlines()[-1]
    assert json.loads(last) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 2}}


def test_rehearsal_passes_every_phase_and_says_it_is_one(rehearsal):
    lines = rehearsal.stdout.splitlines()
    assert lines[0].endswith("[REHEARSAL]")
    (summary,) = [ln for ln in lines if ln.startswith("summary: ")]
    assert lines.index(summary) == len(lines) - 2
    result = json.loads(summary[len("summary: "):])
    assert result["rehearsal"] is True
    assert list(result["phases"]) == [
        "mesh1", "meshN", "rpc", "gossip", "serve", "placement"]
    assert all(p["ok"] for p in result["phases"].values())
    # the every-device phase really split the rows, and ran its reference
    mesh_n = result["phases"]["meshN"]
    assert len(mesh_n["rows_per_device"]) == 2
    assert "one_device_same_workers" in mesh_n
    # off the chip the policy picks the scalar kernels
    assert result["kernels"] == {
        "mesh": "mxu (blocked one-hot, XLA)", "rpc": "scalar",
        "gossip": "scalar"}
    assert result["phases"]["serve"]["worst_abs_err"] < 1e-4


def test_parent_never_imports_jax(rehearsal):
    assert "PARENT_IMPORTED=\n" in rehearsal.stderr + "\n"
