"""Compile-cache semantics (compile_cache.py, DSGD_COMPILE_CACHE).

The contracts under test:

- placement: JAX_COMPILATION_CACHE_DIR wins and no code path sets another
  directory; absent, every process resolves the same fixed
  `<checkout>/.jax_cache` whatever its working directory;
- the library is passive — without an entry point's `place()` it writes
  ZERO files — and the math stays byte-identical with the cache on or
  off (subprocess A/B — in-process runs would share jax's jit cache and
  prove nothing);
- the warmup pass populates the real dispatch cache: the first dispatch
  after warmup performs no tracing at all (poisoned-trace spy), and a
  dispatch racing the warmup thread is safe;
- cache-dir reuse across two processes actually HITS: the second process
  records persistent-cache hits and the file count stops growing.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from distributed_sgd_tpu import compile_cache
from distributed_sgd_tpu.core.worker import WorkerNode
from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import make_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one tiny spin-up: build a worker, (optionally) place the cache + warm,
# answer one gradient.  argv[1] is "place" or "-" for the passive library;
# the directory arrives the only way it can: JAX_COMPILATION_CACHE_DIR.
_CHILD = """
import hashlib, json, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from distributed_sgd_tpu import compile_cache
from distributed_sgd_tpu.core.worker import WorkerNode
from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.utils import metrics as mm

cache = sys.argv[1] == "place"
if cache:
    compile_cache.place(warmup=True)
data = rcv1_like(64, n_features=256, nnz=4, seed=0)
model = make_model("hinge", 1e-5, 256)
w = WorkerNode("127.0.0.1", 0, "127.0.0.1", 1, data, model)
if cache:
    t = compile_cache.warmup_async("child", w.warmup_thunks(8, 2))
    t.join()
g = w.compute_gradient(np.zeros(256, np.float32), np.arange(8))
m = mm.global_metrics()
print(json.dumps({
    "sha": hashlib.sha256(np.asarray(g).tobytes()).hexdigest(),
    "files": compile_cache.cache_file_count(),
    "hits": m.counter(mm.COMPILE_CACHE_HITS).value,
    "misses": m.counter(mm.COMPILE_CACHE_MISSES).value,
    "warmed": m.counter(mm.COMPILE_WARMUP_KERNELS).value,
}))
"""


def _run(code: str, *argv: str, cache_dir=None, cwd=REPO) -> dict:
    """Run `code` in a fresh CPU process; last stdout line is its JSON."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("DSGD_COMPILE_CACHE", None)
    env.pop(compile_cache.ENV_DIR, None)
    if cache_dir is not None:
        env[compile_cache.ENV_DIR] = cache_dir
    out = subprocess.run(
        [sys.executable, "-c", code, *argv],
        capture_output=True, text=True, env=env, cwd=str(cwd), check=False)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _spinup_child(cache_dir) -> dict:
    return _run(_CHILD, "place" if cache_dir else "-", cache_dir=cache_dir)


@pytest.fixture(scope="module")
def spinup_runs(tmp_path_factory):
    """(knobsoff, cold, warm) children sharing one cache dir — run once
    per module (each child pays a jax import)."""
    tmp = tmp_path_factory.mktemp("compile-cache")
    cache = str(tmp / "cc")
    off = _spinup_child(None)
    assert not os.path.exists(cache)
    cold = _spinup_child(cache)
    warm = _spinup_child(cache)
    return {"cache": cache, "off": off, "cold": cold, "warm": warm}


def test_knobs_off_writes_zero_files_and_is_byte_identical(spinup_runs):
    off, cold, warm = (spinup_runs[k] for k in ("off", "cold", "warm"))
    # passive library: no cache dir, no files, no warmup thread, no
    # hit/miss events (the listener is only installed by place())
    assert off["files"] == 0
    assert off["warmed"] == 0
    assert off["hits"] == 0 and off["misses"] == 0
    # and the cache never changes the math: same reply bytes in all three
    assert off["sha"] == cold["sha"] == warm["sha"]


def test_cache_dir_reuse_across_processes_hits(spinup_runs):
    cold, warm = spinup_runs["cold"], spinup_runs["warm"]
    # the first (cold) process compiled for real and populated the dir
    assert cold["misses"] > 0
    assert cold["files"] > 0
    assert cold["warmed"] == 2  # grad + window thunks
    # the second process READ those entries: hits recorded, zero fresh
    # compiles of the warmed shapes, and the file count stopped growing
    assert warm["hits"] > 0
    assert warm["misses"] == 0
    assert warm["files"] == cold["files"]


# one epoch program compiled through place(); says whether the persistent
# cache served it (compile_cache.compiles(): function, seconds, hit)
_EPOCH_CHILD = """
import json
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from distributed_sgd_tpu import compile_cache
compile_cache.place()
from distributed_sgd_tpu.data.synthetic import rcv1_like
from distributed_sgd_tpu.models.linear import make_model
from distributed_sgd_tpu.parallel.mesh import make_mesh
from distributed_sgd_tpu.parallel.sync import SyncEngine

data = rcv1_like(256, n_features=512, nnz=4, seed=0)
bound = SyncEngine(make_model("hinge", 1e-5, 512), make_mesh(1), 16, 0.5,
                   kernel="mxu", virtual_workers=2).bind(data)
jax.block_until_ready(bound.epoch(jnp.zeros((512,)), jax.random.PRNGKey(0)))
print(json.dumps({"epoch": [hit for _at, fun, _s, hit in compile_cache.compiles()
                            if fun == "jit(_epoch_shard)"]}))
"""


def test_cache_is_keyed_by_scope_names_but_not_by_where_the_checkout_lies(tmp_path):
    """An executable read back from the cache carries the names (the
    jax.named_scope paths a profile shows) of the code that compiled it,
    so another version's names must MISS; the same code unpacked at
    another path must still HIT (paths are named from the checkout's
    root)."""
    import shutil

    package = os.path.join(REPO, "distributed_sgd_tpu")
    for name in ("a", "b", "renamed"):
        shutil.copytree(package, tmp_path / name / "distributed_sgd_tpu",
                        ignore=shutil.ignore_patterns("__pycache__", "*.so*"))
    sync = tmp_path / "renamed" / "distributed_sgd_tpu" / "parallel" / "sync.py"
    text = sync.read_text()
    assert '"dsgd.draw"' in text
    sync.write_text(text.replace('"dsgd.draw"', '"dsgd.drawn"'))
    cache = str(tmp_path / "cc")

    def epoch_hits(name):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path / name))
        env[compile_cache.ENV_DIR] = cache
        out = subprocess.run([sys.executable, "-c", _EPOCH_CHILD], capture_output=True,
                             text=True, env=env, cwd=str(tmp_path), check=False)
        assert out.returncode == 0, out.stderr[-4000:]
        return json.loads(out.stdout.strip().splitlines()[-1])["epoch"]

    assert epoch_hits("a") == [False]        # cold
    assert epoch_hits("b") == [True]         # the same code, elsewhere
    assert epoch_hits("renamed") == [False]  # other names: never a stale executable


def _mini_worker(seed=0):
    data = rcv1_like(64, n_features=128, nnz=4, seed=seed)
    model = make_model("hinge", 1e-5, 128)
    return WorkerNode("127.0.0.1", 0, "127.0.0.1", 1, data, model), model


def test_warmup_leaves_first_dispatch_nothing_to_trace():
    """Poisoned-trace spy: after the warmup thread joins, the first real
    Gradient/window dispatch must be a pure dispatch-cache hit — jax only
    calls the traced python body (which reads model.grad_regularized) on
    a RE-trace, so poisoning the model after warmup proves there is
    none."""
    worker, model = _mini_worker()
    t = compile_cache.warmup_async("test", worker.warmup_thunks(8, 2))
    assert t is not None
    t.join(timeout=60)
    assert not t.is_alive()

    def boom(*a, **k):  # noqa: ANN001 - spy
        raise AssertionError("first dispatch re-traced after warmup")

    model.grad_regularized = boom
    w0 = np.zeros(128, np.float32)
    g = worker.compute_gradient(w0, np.arange(8))  # capacity bucket 8
    assert np.isfinite(g).all()
    d = worker.compute_local_window(w0, np.arange(16), 2, 8, 0.1)
    assert np.isfinite(d).all()


def test_warmup_racing_first_dispatch_is_safe():
    """A dispatch arriving while its shape is still warming must return
    the correct gradient (jax serializes/deduplicates the underlying
    compile; worst case is one redundant compile, never a wrong
    result)."""
    worker, _ = _mini_worker(seed=1)
    reference, _ = _mini_worker(seed=1)
    w0 = np.zeros(128, np.float32)
    t = compile_cache.warmup_async("race", worker.warmup_thunks(8, 2))
    g = worker.compute_gradient(w0, np.arange(8))  # races the warmup
    t.join(timeout=60)
    np.testing.assert_array_equal(g, reference.compute_gradient(
        w0, np.arange(8)))


def test_empty_slice_worker_has_no_thunks():
    """A joining host-local worker with an EMPTY resident slice (rows
    arrive with its first assignment) must not warm kernels over a
    zero-row gather."""
    from distributed_sgd_tpu.data.host_shard import dataset_reader

    data = rcv1_like(64, n_features=128, nnz=4, seed=0)
    model = make_model("hinge", 1e-5, 128)
    w = WorkerNode("127.0.0.1", 0, "127.0.0.1", 1,
                   data.slice(slice(0, 0)), model, data_offset=0,
                   row_reader=dataset_reader(data), total_rows=64)
    assert w.warmup_thunks(8, 2) == []
    assert compile_cache.warmup_async("empty", w.warmup_thunks(8, 2)) is None


def test_library_is_passive_in_this_process():
    """Only entry points place the cache: nothing in the suite may have
    done so in the test process (it would silently change every other
    test's compile path)."""
    assert compile_cache.cache_dir() is None
    assert not compile_cache.warmup_enabled()
    assert compile_cache.cache_file_count() == 0


# placement only — reports what place() resolved, compiles nothing
_PLACE = """
import json
import jax
from distributed_sgd_tpu import compile_cache
placed = compile_cache.place()
print(json.dumps({"placed": placed,
                  "jax": jax.config.jax_compilation_cache_dir}))
"""


def test_env_var_places_the_cache(spinup_runs, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins: place() reports jax's own reading
    of it, and the spin-up children's entries landed there."""
    want = str(tmp_path / "from-env")
    got = _run(_PLACE, cache_dir=want)
    assert got["placed"] == got["jax"] == want
    assert os.listdir(spinup_runs["cache"])


def test_default_is_one_fixed_dir_from_any_cwd(tmp_path):
    """No env var: two fresh processes started from different working
    directories resolve the same <checkout>/.jax_cache."""
    a = _run(_PLACE, cwd=tmp_path)
    b = _run(_PLACE, cwd=REPO)
    assert a == b
    assert a["placed"] == os.path.join(REPO, ".jax_cache")
    assert compile_cache.DEFAULT_DIR == a["placed"]


def test_no_other_code_sets_the_cache_dir():
    """The directory is set in exactly one place — compile_cache.place —
    and no cache path is built from a temp dir, a pid or the clock."""
    setters = []
    for root in ("distributed_sgd_tpu", "benches", "examples"):
        for dirpath, _dirs, files in os.walk(os.path.join(REPO, root)):
            setters += [os.path.join(dirpath, f) for f in files
                        if f.endswith(".py")]
    setters += [os.path.join(REPO, f) for f in
                ("bench.py", "chip_smoke.py", "__graft_entry__.py")]
    hits = []
    for path in setters:
        with open(path) as f:
            src = f.read()
        if '"jax_compilation_cache_dir"' in src:
            hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("distributed_sgd_tpu", "compile_cache.py")]
    with open(os.path.join(REPO, hits[0])) as f:
        src = f.read()
    assert not any(w in src for w in ("tempfile", "getpid", "mkdtemp"))
