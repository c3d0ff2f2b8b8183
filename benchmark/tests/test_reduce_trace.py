"""The trace reducer on a small trace recorded on a v5e chip (PR 22: five
epochs of `rcv1-sync-1chip` at rehearsal size, 82 steps an epoch, full
width) and on hand-made intervals."""

import gzip
import os

import pytest

from benchmark import reduce_trace as rt

DATA = os.path.join(os.path.dirname(__file__), "data", "rcv1-sync-tiny.v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(DATA, "rb") as f:
        path.write_bytes(f.read())
    return rt.reduce(str(path), opens_in="_epoch_shard")


def test_window_busy_and_idle_share(reduced):
    # five epoch periods of about 20 ms, marked by five bench.epoch spans; the
    # window opens with the first epoch program: the two small programs the
    # trace holds before it (0.72 ms) are cut off
    assert reduced["annotations"] == 5
    assert reduced["cut_s"] == pytest.approx(0.000721, rel=1e-2)
    assert reduced["window_s"] == pytest.approx(0.101768, rel=1e-3)
    dev = reduced["devices"]["TPU:0"]
    assert reduced["worst_device"] == reduced["detail_device"] == "TPU:0"
    assert dev["busy_s"] == pytest.approx(0.045500, rel=1e-3)
    assert reduced["busy_s"] == dev["busy_s"]
    assert reduced["idle_share"] == pytest.approx(1 - 0.045500 / 0.101768, rel=1e-3)
    assert reduced["idle_s"] == pytest.approx(reduced["window_s"] - dev["busy_s"])


def test_programs_by_name(reduced):
    modules = reduced["devices"]["TPU:0"]["modules"]
    assert modules["jit__epoch_shard"][0] == 5
    assert modules["jit__eval_shard"][0] == 10  # train and test, every epoch
    # the fit loop's eager crumbs: seventeen programs an epoch
    assert sum(count for count, _s in modules.values()) == 85 - 2
    # a program's time is busy time
    assert sum(s for _c, s in modules.values()) == pytest.approx(
        reduced["devices"]["TPU:0"]["busy_s"], rel=0.01)


def test_classes_sum_to_busy_and_matmul_leads(reduced):
    dev = reduced["devices"]["TPU:0"]
    assert sum(dev["classes"].values()) == pytest.approx(dev["busy_s"], rel=1e-6)
    assert dev["classes"]["matmul"] / dev["busy_s"] > 0.8
    top = reduced["breakdown"]["device_ops"]
    assert len(top) == 10 and top[0][1] >= top[1][1] >= top[2][1]
    # the one-hot gather, the one-hot scatter, the evaluation's gather
    assert [label.split()[-1] for label, _s in top[:3]] == ["kOutput"] * 3
    assert all(len(label) <= 80 for label, _s in top)


def test_the_epoch_program_and_what_lies_between(reduced):
    dev = reduced["devices"]["TPU:0"]
    program, between = dev["program"], dev["between"]
    assert program["runs"] == 5
    assert program["step"]["steps"] == 82 * 5
    assert program["step"]["seconds"] == pytest.approx(83.5e-6, rel=0.02)
    assert program["seconds"] == pytest.approx(0.034491, rel=1e-3)
    assert program["idle_s"] == 0.0  # inside a program's event the device is busy
    assert program["seconds"] + between["seconds"] == pytest.approx(reduced["window_s"])
    assert program["busy_s"] + between["busy_s"] == pytest.approx(dev["busy_s"])
    # every idle second of the window lies between the epoch programs
    assert between["idle_s"] == pytest.approx(reduced["idle_s"], rel=1e-6)
    assert 1e3 * between["idle_s"] / program["runs"] == pytest.approx(11.25, rel=0.01)
    # per step: the two one-hot matmuls 68.5 us of the 84 us a step takes
    assert rt.class_us_per_step(dev, "matmul") == pytest.approx(68.5, rel=0.01)
    assert rt.class_us_per_step(dev, "allreduce") is None
    assert rt.class_us_per_step(dev, "allreduce", absent=0.0) == 0.0
    per_step = sum(program["classes"].values()) / program["step"]["steps"]
    assert per_step == pytest.approx(program["seconds"] / 410)
    # evaluation's own one-hot gather is outside the program
    assert between["classes"]["matmul"] == pytest.approx(0.009317, rel=1e-3)


def test_without_a_named_program_the_window_is_the_whole_trace(tmp_path):
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(DATA, "rb") as f:
        path.write_bytes(f.read())
    whole = rt.reduce(str(path))
    assert whole["cut_s"] == 0.0 and whole["opens_in"] is None
    assert whole["window_s"] == pytest.approx(0.10249, rel=1e-3)
    assert whole["devices"]["TPU:0"]["program"] is None
    with pytest.raises(rt.TraceError, match="ran no program named"):
        rt.reduce(str(path), opens_in="_no_such_program")


def test_gaps_are_named_after_what_the_host_did(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(reduced["idle_s"], rel=0.05)
    label, seconds = reduced["breakdown"]["idle_gaps"][0]
    assert label == "bench.epoch / sync.py:579 evaluate"
    assert seconds == pytest.approx(0.0310, rel=0.02)


# -- hand-made events: a window as the synchronous driver records it ---------

STEP = "%fusion.60 = f32[4,7600]{1,0} fusion(s32[4,7600]{1,0} %a), kind=kOutput, calls=%fc"
DRAW = "%fusion.57 = f32[400,76]{1,0} fusion(f32[5767168,76]{1,0} %g), kind=kCustom, calls=%fc"
PSUM = "%all-reduce.3 = f32[376,128]{1,0} all-reduce(f32[376,128]{1,0} %g), to_apply=%add"
EVAL = "%fusion.10 = f32[38912]{0} fusion(f32[38912]{0} %a), kind=kOutput, calls=%fc"


def mid_epoch_trace(steps=20, devices=(0,)):
    """The profiler came up while the epoch program ran: the program's event
    starts with the trace and has no `%while`; 100 ns steps of 60 ns matmul,
    20 ns draw, 10 ns all-reduce, 10 ns nothing (loop control); then 50 ns
    idle, an evaluation program of 400 ns, 30 ns idle, the boundary."""
    ops = []
    for k in range(steps):
        t = 100 * k
        ops += [(t, t + 60, STEP), (t + 60, t + 80, DRAW), (t + 80, t + 90, PSUM)]
    end = 100 * steps
    ops.append((end + 50, end + 450, EVAL))
    modules = [(0, end, "jit__epoch_shard(1)"), (end + 50, end + 450, "jit__eval_shard(2)")]
    host = [(end + 480, end + 480, "bench.boundary")]
    lines = {rt.OPS_LINE: ops, rt.MODULES_LINE: modules}
    return {i: lines for i in devices}, host


def test_a_window_that_opens_inside_the_epoch_program():
    devices, host = mid_epoch_trace()
    r = rt.reduce_events(devices, host, opens_in="_epoch_shard")
    dev = r["devices"]["TPU:0"]
    assert r["window_s"] == pytest.approx(2480e-9) and r["cut_s"] == 0.0
    assert dev["program"]["runs"] == 1 and dev["program"]["step"]["steps"] == 20
    assert dev["program"]["step"]["seconds"] == pytest.approx(100e-9)
    assert rt.class_us_per_step(dev, "matmul") == pytest.approx(0.060)
    assert rt.class_us_per_step(dev, "allreduce") == pytest.approx(0.010)
    assert rt.class_us_per_step(dev, "gather") == pytest.approx(0.020)
    # no %while was recorded: the 10 ns a step nothing accounts for are loop control
    assert rt.class_us_per_step(dev, "container") == pytest.approx(0.010)
    assert dev["program"]["idle_s"] == 0.0
    assert dev["between"]["busy_s"] == pytest.approx(400e-9)
    assert dev["between"]["idle_s"] == pytest.approx(80e-9)
    assert r["idle_s"] == pytest.approx(80e-9)
    # where the window opened does not move the per-step numbers or the boundary's
    longer = rt.reduce_events(*mid_epoch_trace(steps=200), opens_in="_epoch_shard")
    dev2 = longer["devices"]["TPU:0"]
    assert rt.class_us_per_step(dev2, "matmul") == pytest.approx(0.060)
    assert dev2["between"]["busy_s"] == pytest.approx(dev["between"]["busy_s"])
    assert dev2["between"]["idle_s"] == pytest.approx(dev["between"]["idle_s"])
    assert longer["idle_share"] < r["idle_share"]  # the share of the window does move


def test_a_trace_that_began_after_the_epoch_program_is_refused():
    devices, host = mid_epoch_trace()
    late = {0: {rt.OPS_LINE: [op for op in devices[0][rt.OPS_LINE] if op[2] == EVAL],
                rt.MODULES_LINE: devices[0][rt.MODULES_LINE][1:]}}
    with pytest.raises(rt.TraceError, match="does not open inside it"):
        rt.reduce_events(late, host, opens_in="_epoch_shard")
    # an epoch program that starts after the boundary does not count either
    late[0][rt.MODULES_LINE].append((5000, 6000, "jit__epoch_shard(1)"))
    with pytest.raises(rt.TraceError, match="does not open inside it"):
        rt.reduce_events(late, host, opens_in="_epoch_shard")


def test_the_window_opens_where_the_last_device_is_recorded():
    """The profiler comes up on one chip after the other: the time before a
    device's recording began is not idle time of that device."""
    devices, host = mid_epoch_trace(devices=(0, 1))
    late = 700  # device 1 is recorded from here on, inside the same epoch program
    second = {rt.OPS_LINE: [op for op in devices[1][rt.OPS_LINE] if op[0] >= late],
              rt.MODULES_LINE: [(late, 2000, "jit__epoch_shard(1)"),
                                devices[1][rt.MODULES_LINE][1]]}
    r = rt.reduce_events({0: devices[0], 1: second}, host, opens_in="_epoch_shard")
    assert r["cut_s"] == pytest.approx(700e-9)
    assert r["window_s"] == pytest.approx((2480 - 700) * 1e-9)
    for dev in r["devices"].values():
        assert dev["program"]["seconds"] == pytest.approx(1300e-9)
        assert dev["between"]["idle_s"] == pytest.approx(80e-9)
        assert rt.class_us_per_step(dev, "matmul") == pytest.approx(0.060)
    # a device that has left the program by then is refused
    gone = {rt.OPS_LINE: [(0, 60, STEP), (300, 900, EVAL)],
            rt.MODULES_LINE: [(0, 100, "jit__epoch_shard(1)"), (300, 900, "jit__eval_shard(2)")]}
    with pytest.raises(rt.TraceError, match="device 0 is not inside"):
        rt.reduce_events({0: gone, 1: second}, host, opens_in="_epoch_shard")


def test_every_device_has_to_hold_the_program_and_the_worst_is_reported():
    devices, host = mid_epoch_trace(devices=(0, 1))
    slow = dict(devices[1])
    slow[rt.OPS_LINE] = [op for op in slow[rt.OPS_LINE] if op[2] != EVAL] + [(2100, 2450, EVAL)]
    slow[rt.MODULES_LINE] = [slow[rt.MODULES_LINE][0], (2100, 2450, "jit__eval_shard(2)")]
    r = rt.reduce_events({0: devices[0], 1: slow}, host, opens_in="_epoch_shard")
    assert r["worst_device"] == "TPU:1" and r["detail_device"] == "TPU:0"
    assert r["devices"]["TPU:1"]["between"]["idle_s"] == pytest.approx(130e-9)
    assert r["busy_s"] == pytest.approx((2400e-9 + 2350e-9) / 2)
    missing = {0: devices[0], 1: {rt.OPS_LINE: [(0, 10, STEP)], rt.MODULES_LINE: []}}
    with pytest.raises(rt.TraceError, match="device 1 ran no program"):
        rt.reduce_events(missing, host, opens_in="_epoch_shard")


def test_the_trace_readers_read_the_trace_alone():
    """The per-layer metrics of the device trace take nothing from the host
    clock: a run object that holds only the reduced trace is enough."""
    from types import SimpleNamespace

    from benchmark import harness

    devices, host = mid_epoch_trace(devices=(0, 1))
    run = SimpleNamespace(trace=rt.reduce_events(devices, host, opens_in="_epoch_shard"))
    read = lambda name: harness.layer_reader(name).read(run)  # noqa: E731
    assert read("matmul_us_per_step") == pytest.approx(0.060)
    assert read("allreduce_us_per_step") == pytest.approx(0.010)
    assert read("eval_device_ms") == pytest.approx(400e-6)
    assert read("boundary_idle_ms") == pytest.approx(80e-6)
    assert read("async_device_idle_share") == pytest.approx(100 * 80 / 2480)
    untraced = SimpleNamespace(trace=None)
    hogwild = SimpleNamespace(trace=rt.reduce_events(devices, host))  # no named program
    for name in ("matmul_us_per_step", "allreduce_us_per_step", "eval_device_ms",
                 "boundary_idle_ms", "async_device_idle_share"):
        assert harness.layer_reader(name).read(untraced) is None
        if name != "async_device_idle_share":
            assert harness.layer_reader(name).read(hogwild) is None


def test_a_trace_without_a_device_plane_is_an_error(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    with pytest.raises(rt.TraceError, match="no /device:TPU"):
        rt.reduce(path)


def test_union_and_gaps():
    spans = [(0, 10), (5, 12), (20, 30), (22, 25), (30, 31)]
    assert rt.union_seconds(spans) == 12 + 11
    assert rt.union_seconds([]) == 0.0
    assert rt.gaps_of(spans, -5, 40) == [(-5, 0), (12, 20), (31, 40)]
    assert rt.gaps_of(spans, 6, 25) == [(12, 20)]
    assert rt.gaps_of([], 0, 3) == [(0, 3)]


def test_self_time_takes_the_children_out():
    events = [(0, 100, "while"), (10, 30, "a"), (30, 60, "b"), (35, 40, "c"),
              (120, 130, "a")]
    own = rt.self_times(events)
    assert own == {"while": 50, "a": 30, "b": 25, "c": 5}


OPS = {
    "%fusion.61 = f32[4,7600]{1,0:T(4,128)S(1)} fusion(s32[4,7600]{1,0:T(4,128)S(1)} %a, "
    "f32[376,128,1]{1,0,2:T(8,128)S(1)} %b), kind=kOutput, calls=%fused_computation.9":
        ("matmul", "fusion.61 f32[4,7600] kOutput"),
    "%fusion.63 = (u8[376,128]{1,0:T(8,128)(4,1)S(1)}, f32[4,376,128]{2,1,0:T(8,128)S(1)}) "
    "fusion(bf16[4,7600]{1,0} %r), kind=kOutput, calls=%fc":
        ("matmul", "fusion.63 u8[376,128] kOutput"),
    "%fusion.58 = f32[400,76]{1,0:T(8,128)S(1)} fusion(f32[32768,76]{1,0:T(8,128)S(1)} %g, "
    "s32[1024]{0:T(1024)S(1)} %p), kind=kCustom, calls=%fc": ("gather", "fusion.58 f32[400,76] kCustom"),
    "%copy.5 = s32[32768,76]{1,0:T(8,128)S(1)} copy(s32[32768,76]{0,1:T(8,128)} %idx.1)":
        ("copy", "copy.5 s32[32768,76] copy"),
    "%all-reduce.3 = f32[376,128]{1,0:T(8,128)} all-reduce(f32[376,128]{1,0:T(8,128)} %g), "
    "channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add":
        ("allreduce", "all-reduce.3 f32[376,128] all-reduce"),
    "%all-reduce-start.1 = f32[2]{0} all-reduce-start(f32[2]{0} %x), to_apply=%add":
        ("allreduce", "all-reduce-start.1 f32[2] all-reduce-start"),
    "%while.5 = (s32[]{:T(128)}, f32[376,128]{1,0:T(8,128)S(1)}) while((s32[]{:T(128)}, "
    "f32[376,128]{1,0}) %tuple.82), condition=%c, body=%b": ("container", "while.5 s32[] while"),
    "%multiply_reduce_fusion.2 = f32[4096]{0:T(1024)S(1)} fusion(f32[409600,2000]{0,1:T(8,128)} %v), "
    "kind=kLoop, calls=%fc": ("other", "multiply_reduce_fusion.2 f32[4096] kLoop"),
    "%convolution.4 = f32[8,128]{1,0} convolution(f32[8,64]{1,0} %a, f32[64,128]{1,0} %b), "
    "dim_labels=bf_io->bf": ("matmul", "convolution.4 f32[8,128] convolution"),
    "%collective-permute.1 = f32[4]{0} collective-permute(f32[4]{0} %x), source_target_pairs={{0,1}}":
        ("collective", "collective-permute.1 f32[4] collective-permute"),
}


@pytest.mark.parametrize("text", list(OPS))
def test_operation_class_and_label(text):
    assert (rt.op_class(text), rt.op_label(text)) == OPS[text]
