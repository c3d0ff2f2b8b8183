"""Test harness: force an 8-device virtual CPU mesh.

Multi-chip behavior is tested without TPU hardware the same way the
reference tests distribution without a cluster — the reference loops real
gRPC through one JVM (Main.scala:143-158); we run real shard_map/pjit
shardings over 8 virtual CPU devices (SURVEY.md §4)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never run on an accelerator
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# A pytest plugin may have imported jax — and cached JAX_PLATFORMS — before
# this conftest ran, so pin the platform through the config API as well.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Flight-recorder dumps (trace/flight.py) default to DSGD_TRACE_DIR, or
# the process CWD — the black-box location a production crash should use —
# but under pytest that is the repo root: redirect the session default to
# a temp dir so eviction/crash tests don't litter the working tree.  The
# env var (not just the module attribute) is what SUBPROCESS children —
# multiproc/CLI tests, canary-rollback fits — inherit; without it their
# un-configured recorders dumped flight-*.json into the checkout.
import tempfile  # noqa: E402

_flight_dir = os.environ.setdefault(
    "DSGD_TRACE_DIR", tempfile.mkdtemp(prefix="dsgd-test-flight-"))

from distributed_sgd_tpu.trace import flight as _flight  # noqa: E402

_flight.DEFAULT_DIR = _flight_dir
