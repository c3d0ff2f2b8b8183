#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py               # on a TPU machine; fails anywhere else
    python chip_smoke.py --rehearsal   # tiny sizes on the CPU, marked as such

Drives the flagship (hinge SparseSVM, D=47,236, 76 nnz/row, B=100, lr 0.5,
lambda 1e-5) through the entry point a user runs,
`python -m distributed_sgd_tpu.main`, on whatever chips the machine has:

- mesh1    one device x 3 virtual workers, 2 epochs at N=804,414, checkpointed
- meshN    every device, one worker each, 2 epochs at N=804,414; the rows must
           sit split over all devices (+ on a multi-chip host a one-device run
           at the same worker count, whose loss must agree to 0.01)
- rpc      DSGD_ENGINE=rpc: in-process gRPC cluster, a few hundred rounds
- gossip   DSGD_ASYNC=true DSGD_ASYNC_MODE=gossip: Hogwild workers
- serve    DSGD_ROLE=serve over mesh1's checkpoint; Predict margins must equal
           the direct dot product to 1e-4
- placement  SyncEngine.bind on dense 2,000-wide rows and on 76-wide ones:
           the wide rows sit row-major (padded to whole lanes) and the
           compiled epoch program holds no copy of them, the narrow ones
           stay as they come; on the chip both carry their label in a spare
           word of the stored row (lane 2,000; a 77th column)

One process per chip: this parent never imports jax (nor the package, whose
submodules do); it runs its children one after another, each with
JAX_PLATFORMS=tpu so that jax raises rather than carry on on the CPU, and
reads what they log.  Children's logs land in chiprun_out/chip_smoke/.

Output: a `device:` line, one `phase <name>: {...}` line per phase, one
`summary: {...}` line (phases, seconds each, losses, kernel path per engine,
compile-cache hits/misses, peak device bytes), and then — the LAST stdout
line, and the only one a driver reads — exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as jax reports it.  Any failed phase, a platform other than
tpu, or a directory that holds this file without the rest of the repo, exits
non-zero and prints neither the summary nor that line.
"""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "chiprun_out", "chip_smoke")
DEADLINE_S = 1150  # the whole run, compilation included
PHASE_TIMEOUT_S = 420

FULL = dict(rows=804_414, rpc_rows=100_000, gossip_rows=2_500,
            placement_rows=32_768)
TINY = dict(rows=3_000, rpc_rows=2_000, gossip_rows=600,
            placement_rows=512)
# sanity band for the full-width mesh runs (correctness, not speed): the
# last full-width ltc record is 0.364 / 0.826 after 2 epochs — at 3 workers.
# Every-device on a one-chip machine is ONE worker: a third of the samples
# per step at the same lr 0.5 lands, deterministically, at 0.492 / 0.824
# (chip run, PR 21), so fewer than 3 workers get the wider loss bound.
MAX_LOSS, MAX_LOSS_UNDER_3_WORKERS, MIN_ACC = 0.45, 0.55, 0.78

_live = []  # Popen objects this process started and has not reaped


# ---------------------------------------------------------------------------
# children (these import jax; the parent never calls them)
# ---------------------------------------------------------------------------

_PROBE = """
import json
from importlib import metadata
import jax
d = jax.devices()
print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d), "jax": jax.__version__,
                  "libtpu": metadata.version("libtpu")}))
"""


def child_placement(rows: int) -> None:
    """Where SyncEngine.bind puts resident rows (parallel/mesh.put_rows):
    2,000-wide dense rows and 76-wide sparse ones, and what the dense
    binding's compiled epoch program does with them at its entry."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_sgd_tpu import compile_cache
    from distributed_sgd_tpu.data.rcv1 import Dataset
    from distributed_sgd_tpu.data.synthetic import rcv1_like
    from distributed_sgd_tpu.models.linear import make_model
    from distributed_sgd_tpu.parallel.mesh import make_mesh
    from distributed_sgd_tpu.parallel.sync import SyncEngine
    from distributed_sgd_tpu.utils import metrics

    compile_cache.place()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((rows, 2000), dtype=np.float32)
    dense = Dataset.dense(x, np.where(rng.random(rows) < 0.5, 1, -1).astype(np.int32))
    sparse = rcv1_like(rows, n_features=47_236, nnz=76, seed=0)
    mesh = make_mesh(1)
    bound = {
        "dense": SyncEngine(make_model("logistic", 1e-6, 2000, regularizer="l2"),
                            mesh, batch_size=100, learning_rate=0.05,
                            virtual_workers=4).bind(dense),
        "sparse": SyncEngine(make_model("hinge", 1e-5, 47_236), mesh,
                             batch_size=100, learning_rate=0.5,
                             virtual_workers=4).bind(sparse)}

    b, d = bound["dense"], bound["dense"].data
    w0, key = jnp.zeros((2000,), jnp.float32), jax.random.PRNGKey(7)
    text = b._epoch.lower(w0, b._opt_state, d.indices, d.values, d.labels,
                          key).compile().as_text()
    resident = re.escape("f32[%d,%d]" % d.values.shape)
    w = np.asarray(b.epoch(w0, key))
    print(json.dumps({
        "platform": jax.devices()[0].platform,
        "dense_stored": [list(p[2]) for p in b.placement()],
        "dense_width": d.values.shape[1],
        "resident_copies": len(re.findall(rf"= {resident}\S* copy\(", text)),
        "sparse_widths": [bound["sparse"].data.indices.shape[1],
                          bound["sparse"].data.values.shape[1]],
        "label_slots": [b.data.label_slot for b in bound.values()],
        "row_major": metrics.counter("bind.rows.row_major").value,
        "default": metrics.counter("bind.rows.default").value,
        "finite": bool(np.isfinite(w).all()), "moved": float(np.max(np.abs(w))),
    }))


def child_client(ckpt_dir: str, port: int, n_requests: int = 8) -> None:
    """A serving client: Predict over the wire vs the direct dot product on
    the checkpointed weights (CPU only — the server holds the chip)."""
    import numpy as np

    from distributed_sgd_tpu.checkpoint import Checkpointer
    from distributed_sgd_tpu.data.synthetic import rcv1_like
    from distributed_sgd_tpu.rpc import dsgd_pb2 as pb
    from distributed_sgd_tpu.rpc.service import ServeStub, new_channel

    step, state = Checkpointer(ckpt_dir).restore_latest()
    w = np.asarray(state["weights"])
    rows = rcv1_like(n_requests, n_features=w.shape[0], seed=1,
                     idf_values=True)
    channel = new_channel("127.0.0.1", port)
    stub = ServeStub(channel)
    health = stub.ServeHealth(pb.Empty(), timeout=30)
    worst, steps = 0.0, set()
    for i in range(n_requests):
        idx, val = rows.indices[i], rows.values[i]
        nz = val != 0
        reply = stub.Predict(
            pb.PredictRequest(indices=idx[nz], values=val[nz]), timeout=120)
        direct = float((w[idx[nz]] * val[nz]).sum())
        worst = max(worst, abs(reply.margin - direct))
        steps.add(int(reply.model_step))
    channel.close()
    print(json.dumps({
        "requests": n_requests, "worst_abs_err": worst,
        "ckpt_step": int(step), "health_step": int(health.model_step),
        "reply_steps": sorted(steps),
        "weights_l1": float(np.abs(w).sum()),
    }))


# ---------------------------------------------------------------------------
# parent: process plumbing
# ---------------------------------------------------------------------------

def say(msg: str) -> None:
    print(msg, flush=True)


def _die_with_parent() -> None:
    """Child-side, before exec: have the kernel SIGKILL this child when the
    parent dies, however the parent dies (Linux prctl PR_SET_PDEATHSIG)."""
    import ctypes

    ctypes.CDLL("libc.so.6", use_errno=True).prctl(1, signal.SIGKILL)


def _stop(proc: subprocess.Popen, sig: int = signal.SIGTERM,
          grace_s: float = 10.0) -> None:
    """Stop a child: `sig` first, SIGKILL if it outlives the grace."""
    if proc.poll() is None:
        proc.send_signal(sig)
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    if proc in _live:
        _live.remove(proc)


def _stop_all(*_sig) -> None:
    for proc in list(_live):
        _stop(proc, grace_s=2.0)
    if _sig:
        sys.exit(128 + _sig[0])


def _child_env(platform: str, extra: dict) -> dict:
    # ambient DSGD_* knobs would change what the phases run
    env = {k: v for k, v in os.environ.items() if not k.startswith("DSGD_")}
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = platform
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _start(name: str, argv: list, env: dict) -> tuple:
    log_path = os.path.join(WORK, f"{name}.log")
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, *argv], cwd=ROOT, env=env, stdout=log,
        stderr=subprocess.STDOUT, preexec_fn=_die_with_parent)
    log.close()
    _live.append(proc)
    return proc, log_path


def _run(name: str, argv: list, env: dict, timeout_s: float) -> tuple:
    """Run one child to its end; (exit code or None on timeout, log text)."""
    proc, log_path = _start(name, argv, env)
    try:
        rc = proc.wait(timeout=max(1.0, timeout_s))
    except subprocess.TimeoutExpired:
        rc = None
    _stop(proc)
    with open(log_path, errors="replace") as f:
        return rc, f.read()


def _tail(text: str, n: int = 25) -> str:
    keep = [ln[:300] for ln in text.splitlines()
            if " absl - " not in ln and '"agg_tree"' not in ln]
    return "\n".join(keep[-n:])


def result_line(device: dict) -> str:
    """The last stdout line of a run that passed: these keys and no others
    (everything else the run learned is on the `summary:` line above it)."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def _last_json(text: str) -> dict:
    for ln in reversed(text.splitlines()):
        if ln.startswith("{"):
            return json.loads(ln)
    raise ValueError("child printed no JSON line")


# ---------------------------------------------------------------------------
# parent: what the program logs (main.py, core/trainer.py, core/worker.py,
# parallel/hogwild.py, serving/model_store.py)
# ---------------------------------------------------------------------------

_RE_DEVICE = re.compile(
    r"device: platform=(\S+) kind=(.+?) count=(\d+) jax=(\S+) jaxlib=(\S+) "
    r"libtpu=(\S+)")
_RE_ENGINE = re.compile(r"engine=mesh devices=(\d+) virtual_workers=(\d+)")
_RE_EPOCH = re.compile(
    r"epoch (\d+): loss=(\S+) acc=(\S+) test_loss=(\S+) test_acc=(\S+) "
    r"\((\S+)s\)")
_RE_SHARD = re.compile(r"\[id=(\d+) rows=(\d+) bytes_in_use=(\w+)\]")
_RE_CACHE = re.compile(r"compile cache: dir=(\S+) hits=(\d+) misses=(\d+)")
_RE_PEAK = re.compile(r"device memory peak: (.+)$", re.M)
_RE_WORKER_KERNEL = re.compile(r"worker kernel=(\S+) on (\S+)")
_RE_HOGWILD_KERNEL = re.compile(r"hogwild kernel=(\S+), workers on (.+)$", re.M)
_RE_FIT_DONE = re.compile(
    r"fit done: (\d+) epochs, final loss=(\S+), (\d+) updates")
_RE_ROUTER = re.compile(r"serving fleet: router :(\d+) over (\d+)")
_RE_SWAP = re.compile(r"serving model swapped to step (\d+) .* on (\S+)")


class PhaseFailed(Exception):
    pass


def need(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def parse_run(text: str) -> dict:
    """The facts every `python -m distributed_sgd_tpu.main` run logs."""
    out = {}
    m = _RE_DEVICE.search(text)
    need(m is not None, "no 'device:' start-up line")
    out["device"] = {"platform": m.group(1), "kind": m.group(2),
                     "count": int(m.group(3))}
    m = _RE_CACHE.search(text)
    need(m is not None, "no 'compile cache:' exit line")
    out["cache"] = {"dir": m.group(1), "hits": int(m.group(2)),
                    "misses": int(m.group(3))}
    m = _RE_PEAK.search(text)
    peaks = re.findall(r"\d+:(\d+)", m.group(1)) if m else []
    out["peak_bytes"] = max((int(p) for p in peaks), default=None)
    out["epochs"] = [
        {"loss": float(g[1]), "acc": float(g[2]), "test_loss": float(g[3]),
         "test_acc": float(g[4]), "seconds": float(g[5])}
        for g in _RE_EPOCH.findall(text)]
    return out


def check_mesh(text: str, device: dict, devices: int, virtual: int,
               rows: int, rehearsal: bool) -> dict:
    run = parse_run(text)
    need(run["device"] == device,
         f"ran on {run['device']}, the probe found {device}")
    m = _RE_ENGINE.search(text)
    need(m is not None, "no 'engine=mesh' line")
    need((int(m.group(1)), int(m.group(2))) == (devices, virtual),
         f"topology {m.group(1)} device(s) x {m.group(2)} virtual, wanted "
         f"{devices} x {virtual}")
    shards = [(int(i), int(r), b) for i, r, b in _RE_SHARD.findall(text)]
    n_train = int(rows * 0.8)
    need(len({i for i, _r, _b in shards}) == devices,
         f"rows sit on {len({i for i, _r, _b in shards})} device(s), "
         f"wanted {devices}: {shards}")
    per = math.ceil(n_train / devices)
    need(all(per <= r < per + 4096 for _i, r, _b in shards),
         f"uneven split of {n_train} rows: {shards}")
    if not rehearsal:  # the CPU backend reports no memory_stats
        need(all(b.isdigit() and int(b) > 0 for _i, _r, b in shards),
             f"a device reports no bytes in use: {shards}")
    ep = run["epochs"]
    need(len(ep) == 2, f"{len(ep)} epoch lines, wanted 2")
    need(all(math.isfinite(v) for e in ep for v in e.values()),
         f"non-finite epoch values: {ep}")
    if not rehearsal:
        max_loss = (MAX_LOSS if devices * virtual >= 3
                    else MAX_LOSS_UNDER_3_WORKERS)
        need(ep[-1]["loss"] < max_loss and ep[-1]["acc"] > MIN_ACC,
             f"outside the sanity band (loss < {max_loss}, acc > {MIN_ACC}): "
             f"{ep[-1]}")
    return {"devices": devices, "virtual_workers": virtual,
            "loss": ep[-1]["loss"], "acc": ep[-1]["acc"],
            "test_loss": ep[-1]["test_loss"],
            "epoch_seconds": [e["seconds"] for e in ep],
            "rows_per_device": {i: r for i, r, _b in shards},
            "bytes_in_use": {i: b for i, _r, b in shards},
            "kernel": "mxu (blocked one-hot, XLA)",
            "cache": run["cache"], "peak_bytes": run["peak_bytes"]}


def expected_kernel(device: dict) -> str:
    """ops/mxu.blocked_pays_off: blocked one-hot on TPU, scalar elsewhere."""
    return "blocked-onehot" if device["platform"] == "tpu" else "scalar"


def check_rpc(text: str, device: dict) -> dict:
    run = parse_run(text)
    kernels = _RE_WORKER_KERNEL.findall(text)
    need(len(kernels) == 3, f"{len(kernels)} 'worker kernel=' lines, wanted 3")
    need({k for k, _d in kernels} == {expected_kernel(device)},
         f"workers ran {kernels}, wanted {expected_kernel(device)}")
    ep = run["epochs"]
    need(len(ep) == 1 and math.isfinite(ep[0]["loss"])
         and ep[0]["loss"] < 1.0, f"rpc epoch: {ep}")
    return {"kernel": kernels[0][0],
            "worker_devices": [d for _k, d in kernels],
            "loss": ep[0]["loss"], "acc": ep[0]["acc"],
            "epoch_seconds": ep[0]["seconds"],
            "cache": run["cache"], "peak_bytes": run["peak_bytes"]}


def check_gossip(text: str, device: dict) -> dict:
    run = parse_run(text)
    m = _RE_HOGWILD_KERNEL.search(text)
    need(m is not None, "no 'hogwild kernel=' line")
    need(m.group(1) == expected_kernel(device),
         f"hogwild ran {m.group(1)}, wanted {expected_kernel(device)}")
    done = _RE_FIT_DONE.search(text)
    need(done is not None, "no 'fit done' line")
    loss, updates = float(done.group(2)), int(done.group(3))
    need(math.isfinite(loss) and updates > 0,
         f"gossip fit: loss={loss} updates={updates}")
    return {"kernel": m.group(1), "worker_devices": m.group(2).split(),
            "loss": loss, "updates": updates,
            "cache": run["cache"], "peak_bytes": run["peak_bytes"]}


# ---------------------------------------------------------------------------
# parent: the phases
# ---------------------------------------------------------------------------

def main(argv: list) -> int:
    if argv and argv[0] == "--child":
        if argv[1] == "client":
            child_client(argv[2], int(argv[3]))
        elif argv[1] == "placement":
            child_placement(int(argv[2]))
        else:
            raise SystemExit(f"unknown child {argv[1]!r}")
        return 0
    rehearsal = argv == ["--rehearsal"]
    if argv and not rehearsal:
        raise SystemExit("usage: python chip_smoke.py [--rehearsal]")
    if not os.path.isfile(
            os.path.join(ROOT, "distributed_sgd_tpu", "main.py")):
        print(f"chip_smoke: no distributed_sgd_tpu/main.py beside {__file__}; "
              f"run it from the root of a checkout", file=sys.stderr)
        return 2
    size = TINY if rehearsal else FULL
    t_start = time.monotonic()
    signal.signal(signal.SIGTERM, _stop_all)
    signal.signal(signal.SIGINT, _stop_all)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)

    def left() -> float:
        return DEADLINE_S - (time.monotonic() - t_start)

    # -- what does jax find?  (ambient platform: this is the one child that
    # may see a CPU, so that the refusal can name what it found)
    probe_env = _child_env("cpu", {}) if rehearsal else dict(os.environ)
    rc, text = _run("probe", ["-c", _PROBE], probe_env, 300)
    if rc != 0:
        print(f"chip_smoke: jax could not start (exit {rc}):\n{_tail(text)}",
              file=sys.stderr)
        return 2
    probe = _last_json(text)
    device = {k: probe[k] for k in ("platform", "kind", "count")}
    if not rehearsal and device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; jax found platform="
              f"{device['platform']} kind={device['kind']} "
              f"count={device['count']} (python chip_smoke.py --rehearsal "
              f"is the CPU form)", file=sys.stderr)
        return 2
    say(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} jax={probe['jax']} "
        f"libtpu={probe['libtpu']}" + ("  [REHEARSAL]" if rehearsal else ""))
    platform = "cpu" if rehearsal else "tpu"
    count = device["count"]
    ckpt = os.path.join(WORK, "ckpt")
    phases, failed = {}, []

    def phase(name: str, fn) -> None:
        t0 = time.monotonic()
        try:
            need(left() > 5, "out of time before the phase could start")
            result = fn()
            result["ok"] = True
        except PhaseFailed as e:
            result = {"ok": False, "error": str(e)}
            failed.append(name)
        result["seconds"] = round(time.monotonic() - t0, 1)
        phases[name] = result
        say(f"phase {name}: {json.dumps(result)}")

    def run_main(name: str, env: dict) -> str:
        rc, text = _run(name, ["-m", "distributed_sgd_tpu.main"],
                        _child_env(platform, env),
                        min(PHASE_TIMEOUT_S, left()))
        if rc != 0:
            print(f"--- {name} (exit {rc}; None = timed out), log tail:\n"
                  f"{_tail(text)}", file=sys.stderr)
            raise PhaseFailed(f"exit code {rc}")
        return text

    def mesh(name: str, devices: int, virtual: int, extra=None) -> dict:
        # 1 device x K virtual workers: DSGD_NODE_COUNT=K does it on a
        # one-chip machine (the shape of the July record); a multi-chip
        # host needs the explicit form or the mesh would take K chips
        if devices == 1 and count > 1:
            env = {"DSGD_NODE_COUNT": 1, "DSGD_VIRTUAL_WORKERS": virtual}
        else:
            env = {"DSGD_NODE_COUNT": devices * virtual}
        env.update({"DSGD_SYNTHETIC": size["rows"], "DSGD_MAX_EPOCHS": 2})
        env.update(extra or {})
        return check_mesh(run_main(name, env), device, devices, virtual,
                          size["rows"], rehearsal)

    def mesh1() -> dict:
        out = mesh("mesh1", 1, 3, {"DSGD_CHECKPOINT_DIR": ckpt})
        need(os.path.isdir(os.path.join(ckpt, "2")),
             "no checkpoint at step 2")
        return out

    def mesh_n() -> dict:
        out = mesh("meshN", count, 1)
        if count > 1:
            ref = mesh("meshN_ref", 1, count)
            out["one_device_same_workers"] = {
                k: ref[k] for k in ("loss", "acc", "epoch_seconds")}
            out["loss_delta"] = abs(out["loss"] - ref["loss"])
            # same row partition, different per-device key fold: at full
            # size the two agree; a rehearsal's half-dozen steps do not
            need(rehearsal or out["loss_delta"] < 0.01,
                 f"{count}-device loss {out['loss']} vs one device x "
                 f"{count} virtual {ref['loss']}")
        return out

    def rpc() -> dict:
        return check_rpc(run_main("rpc", {
            "DSGD_ENGINE": "rpc", "DSGD_NODE_COUNT": 3,
            "DSGD_SYNTHETIC": size["rpc_rows"], "DSGD_MAX_EPOCHS": 1,
        }), device)

    def gossip() -> dict:
        return check_gossip(run_main("gossip", {
            "DSGD_ASYNC": "true", "DSGD_ASYNC_MODE": "gossip",
            "DSGD_NODE_COUNT": 3, "DSGD_SYNTHETIC": size["gossip_rows"],
            "DSGD_MAX_EPOCHS": 1,
        }), device)

    def serve() -> dict:
        need(os.path.isdir(ckpt), "mesh1 left no checkpoint to serve")
        server, log_path = _start(
            "serve", ["-m", "distributed_sgd_tpu.main"],
            _child_env(platform, {
                "DSGD_ROLE": "serve", "DSGD_SERVE_REPLICAS": 2,
                "DSGD_SERVE_PORT": 0, "DSGD_CHECKPOINT_DIR": ckpt}))
        try:
            port, t_wait = None, min(PHASE_TIMEOUT_S, left())
            t0 = time.monotonic()
            while port is None and time.monotonic() - t0 < t_wait:
                need(server.poll() is None,
                     f"the server exited with code {server.returncode}")
                with open(log_path, errors="replace") as f:
                    m = _RE_ROUTER.search(f.read())
                port = int(m.group(1)) if m else None
                time.sleep(0.5)
            need(port is not None, "the server never announced its router")
            # the client never needs the chip, whatever the server holds
            rc, text = _run(
                "client", [os.path.abspath(__file__), "--child", "client",
                           ckpt, str(port)],
                _child_env("cpu", {}), min(PHASE_TIMEOUT_S, left()))
            if rc != 0:
                print(f"--- client (exit {rc}), log tail:\n{_tail(text)}",
                      file=sys.stderr)
                raise PhaseFailed(f"client exit code {rc}")
            out = _last_json(text)
        finally:
            # SIGINT, so that main()'s exit path still logs its summary
            _stop(server, sig=signal.SIGINT)
        with open(log_path, errors="replace") as f:
            server_text = f.read()
        need(out["worst_abs_err"] < 1e-4,
             f"served margins off by {out['worst_abs_err']}")
        need(out["health_step"] == out["ckpt_step"] == 2
             and out["reply_steps"] == [2], f"served the wrong step: {out}")
        need(out["weights_l1"] > 0, "the checkpoint holds zero weights")
        swaps = _RE_SWAP.findall(server_text)
        need(len(swaps) >= 2, f"{len(swaps)} replica load lines, wanted 2")
        out["replica_devices"] = [d for _s, d in swaps]
        run = parse_run(server_text)
        out.update(cache=run["cache"], peak_bytes=run["peak_bytes"])
        return out

    def child(name: str, *args) -> dict:
        """One `--child <name>` of this file on the phase's platform: the
        JSON object it printed last."""
        rc, text = _run(
            name, [os.path.abspath(__file__), "--child", name,
                   *(str(a) for a in args)],
            _child_env(platform, {}), min(PHASE_TIMEOUT_S, left()))
        if rc != 0:
            print(f"--- {name} (exit {rc}), log tail:\n{_tail(text)}",
                  file=sys.stderr)
            raise PhaseFailed(f"exit code {rc}")
        out = _last_json(text)
        need(out["platform"] == device["platform"],
             f"ran on {out['platform']}")
        return out

    def placement() -> dict:
        out = child("placement", size["placement_rows"])
        need(out["finite"] and out["moved"] > 0, f"degenerate epoch: {out}")
        # on the chip the 76-wide values hold the row's label as a 77th
        # column (of 80 sublanes), the dense rows in lane 2,000 of 2,048
        need((out["sparse_widths"], out["label_slots"])
             == (([76, 76], [None, None]) if rehearsal else ([76, 77], [2000, 76])),
             f"76-wide rows were padded, or a label is not in its row: {out}")
        need(out["resident_copies"] == 0,
             f"the epoch program copies its resident rows: {out}")
        # on the chip the one wide array is stored padded to whole lanes,
        # which the backend keeps row-major; the CPU's rule leaves all six
        # arrays of the two bindings as they come
        need(out["dense_stored"] == [[0, 1]], f"dense rows stored {out}")
        need((out["row_major"], out["default"], out["dense_width"])
             == ((0, 6, 2000) if rehearsal else (1, 5, 2048)),
             f"placement by the wrong rule: {out}")
        return out

    try:
        for name, fn in (("mesh1", mesh1), ("meshN", mesh_n), ("rpc", rpc),
                         ("gossip", gossip), ("serve", serve),
                         ("placement", placement)):
            phase(name, fn)
    finally:
        _stop_all()

    seconds = round(time.monotonic() - t_start, 1)
    if failed:
        print(f"chip_smoke: FAILED phases {failed} after {seconds}s; logs in "
              f"{WORK}", file=sys.stderr)
        return 1
    caches = [p["cache"] for p in phases.values() if "cache" in p]
    summary = {
        "jax": probe["jax"], "libtpu": probe["libtpu"],
        "seconds": seconds,
        "compile_cache": {
            "dir": next(c["dir"] for c in caches if "dir" in c),
            "hits": sum(c["hits"] for c in caches),
            "misses": sum(c["misses"] for c in caches)},
        "peak_device_bytes": max(
            (p["peak_bytes"] for p in phases.values()
             if p.get("peak_bytes") is not None), default=None),
        "kernels": {"mesh": phases["mesh1"]["kernel"],
                    "rpc": phases["rpc"]["kernel"],
                    "gossip": phases["gossip"]["kernel"]},
        "phases": phases,
    }
    if rehearsal:
        summary["rehearsal"] = True
    say(f"summary: {json.dumps(summary)}")
    say(result_line(device))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
