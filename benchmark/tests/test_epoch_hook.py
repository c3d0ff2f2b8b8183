"""The synchronous driver's criterion hook against a simulated fit: a fake
clock, a fake profiler with a start latency, epochs of a fixed length.
What is checked is where the traced window falls, never a speed."""

import heapq

import pytest

from benchmark import harness
from benchmark.drivers import sync_mesh


class Sim:
    """A clock the test advances, with events (timer fires, the profiler
    coming up) that happen on the way."""

    def __init__(self, latency):
        self.now, self.latency, self.queue, self.n = 0.0, latency, [], 0

    def perf_counter(self):
        return self.now

    def at(self, when, fn):
        self.n += 1
        heapq.heappush(self.queue, (when, self.n, fn))

    def advance(self, seconds):
        to = self.now + seconds
        while self.queue and self.queue[0][0] <= to:
            when, _n, fn = heapq.heappop(self.queue)
            self.now = max(self.now, when)
            fn()
        self.now = to


class FakeTrace(harness.TraceSession):
    def __init__(self, sim):
        super().__init__("unused")
        self.sim, self.windows = sim, []

    def start(self):
        self.started_at = self.stopped_at = None
        self.requested_at = self.sim.now
        latency = self.sim.latency.pop(0) if len(self.sim.latency) > 1 else self.sim.latency[0]
        self.sim.at(self.sim.now + latency, lambda: setattr(self, "started_at", self.sim.now))

    def stop(self):
        self.stopped_at = self.sim.now
        self.windows.append((self.started_at, self.stopped_at))


@pytest.fixture
def fit(monkeypatch):
    def run(epoch_s, eval_s, latency, seconds=20.0, warm=2, traced=True):
        sim = Sim(list(latency))

        class Timer:
            daemon = False

            def __init__(self, delay, fn):
                self.delay, self.fn = delay, fn

            def start(self):
                sim.at(sim.now + self.delay, self.fn)

            def cancel(self):
                pass

            join = cancel

        monkeypatch.setattr(sync_mesh.time, "perf_counter", sim.perf_counter)
        monkeypatch.setattr(sync_mesh.threading, "Timer", Timer)
        trace = FakeTrace(sim) if traced else None
        hook = sync_mesh._EpochHook(warm, seconds, trace, epoch_seconds=lambda: epoch_s,
                                    compile_count=lambda: 0)
        ends = []  # when each epoch program ended
        for _ in range(100000):
            sim.advance(epoch_s)
            ends.append(sim.now)
            sim.advance(eval_s)
            if hook(None):
                break
            sim.advance(1e-5)  # the loop's way back to the next epoch
        hook.cancel()
        return hook, trace, ends
    return run


def test_an_untraced_fit_stops_at_the_first_boundary_past_the_deadline(fit):
    hook, _trace, _ends = fit(1.3, 0.4, [0.05], seconds=20.0, traced=False)
    window = hook.entries[-1] - hook.exits[hook.warm - 1]
    assert 20.0 <= window < 20.0 + 1.7 + 1e-3
    assert hook.compiles == [0, 0]


def test_long_epochs_the_window_opens_inside_one_epoch_program(fit):
    hook, trace, ends = fit(1.3, 0.4, [0.05])
    assert hook.kept and len(trace.windows) == 1
    (attempt,) = hook.attempts
    began, stopped = trace.windows[0]
    # 0.25 s of lead less the profiler's 0.05 s: 0.2 s of steps, then the
    # evaluation, and the trace stops at that epoch's boundary
    assert attempt["steps_s"] == pytest.approx(0.2, abs=1e-3)
    end = max(e for e in ends if e <= stopped)
    assert began == pytest.approx(end - 0.2, abs=1e-3)
    assert stopped - began == pytest.approx(0.6, abs=1e-3)
    assert stopped in hook.entries


def test_a_profiler_that_comes_up_late_is_dropped_and_asked_again_earlier(fit):
    # 0.4 s to start: past the epoch program's end the first time (lead 0.25),
    # inside it with the doubled lead
    hook, trace, _ends = fit(1.3, 0.4, [0.4, 0.4])
    assert hook.kept and len(trace.windows) == 2
    first, second = hook.attempts
    assert not first["kept"] and first["steps_s"] < 0 and first["lead"] == 0.25
    assert second["kept"] and second["lead"] == 0.5
    assert second["steps_s"] == pytest.approx(0.1, abs=1e-3)
    # the dropped trace held evaluation only, never a whole period
    assert trace.windows[0][1] - trace.windows[0][0] < 0.4


def test_a_profiler_that_never_makes_it_ends_the_run_without_a_trace(fit):
    # each time slower than the lead asked for: 0.25, 0.5, 1.0 s
    hook, trace, _ends = fit(1.3, 0.4, [0.4, 0.6, 1.2, 1.2], seconds=5.0)
    assert not hook.kept and hook.trace_done
    assert len(hook.attempts) == sync_mesh._EpochHook.ATTEMPTS == len(trace.windows)


def test_short_epochs_the_trace_runs_over_whole_periods(fit):
    # epsilon's shape: 42 ms of epoch program, 15 ms of evaluation, and a
    # profiler that needs longer than a period to come up
    hook, trace, ends = fit(0.042, 0.015, [0.08])
    assert hook.kept and len(trace.windows) == 1 and len(hook.attempts) == 1
    began, stopped = trace.windows[0]
    assert 0.2 <= stopped - began < 0.2 + 2 * 0.057
    assert stopped in hook.entries
    assert sum(1 for e in ends if began < e <= stopped) >= 3  # whole epoch programs inside


def test_the_fit_runs_on_until_the_trace_is_done(fit):
    hook, trace, _ends = fit(1.3, 0.4, [0.05], seconds=0.5)
    assert hook.kept
    # the deadline passed at the first window boundary; the trace was kept at the second
    assert len(hook.entries) == hook.warm + 2


@pytest.mark.parametrize("stalled, expected", [
    ((), 1000.0),             # every period 0.1 s: 100 samples / 0.1 s
    ((3,), 1000.0),           # one period held up by a stall: the median leaves it out
    ((1, 4, 7), 1000.0),      # a third of them: still the plain period
    (tuple(range(10)), 500.0),  # every period twice as long: the rate halves
])
def test_the_rate_is_samples_over_the_median_period(stalled, expected):
    periods, t = [], 0.0
    for j in range(10):
        length = 0.2 if j in stalled else 0.1
        periods.append({"epoch": j, "start": t, "end": t + length, "work_s": 0.08})
        t += length + 0.001  # the hook's own time lies between two periods
    assert sync_mesh.samples_per_second(periods, 100) == pytest.approx(expected)
