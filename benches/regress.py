"""Round-over-round performance regression gate (VERDICT r3 item 7).

The reference ships a ScalaMeter regression reporter (ExponentialBackoff
historian + RegressionReporter, src/test/scala/epfl/distributed/math/
SparseBench.scala:9-15): every bench run is compared against stored
history and flagged when it regresses beyond a confidence window.  The
TPU equivalent: a JSON history of every round's kernel/step/epoch numbers
(`benches/history.json`, committed) and a gate that compares a fresh run
against the MEDIAN of the stored runs with a run-to-run-variance
tolerance (BASELINE.md records 0.17-0.21 s epoch spread across the July
rounds, ~±20%, so the default tolerance is 35%).

Usage:
    python bench.py                                        # gates + appends itself
    python bench.py | python benches/regress.py gate --no-record  # re-check only
    python benches/regress.py gate < run.json              # check + append
    python benches/regress.py show                         # print history

`gate` reads one JSON object on stdin (bench.py's output line), checks
every numeric field it has history for, appends the run to the history
(unless --no-record; a REGRESSED run is never appended — recording a
regression would drag the rolling median toward it until it "passes",
the erosion failure the kernel gate in sparse_bench.py also refuses),
prints a verdict line per metric to stderr, and exits 1 if any metric
regressed.  bench.py gates and appends its run directly (see its
main()), so the pipe form above uses --no-record to avoid gating a
history that already contains the run under test.

History may hold several independent series (the uniform headline, the
ltc convergence record, ...): entries are compared only against prior
entries with the SAME top-level `"metric"` name, so one series' `value`
never pollutes another's median.

Direction is inferred from the metric name: `*_seconds`/`*_s`/`*_loss`
are lower-is-better, `*_per_s`/`*_acc` are higher-is-better; anything
else — including the `vs_*` speedup ratios — is recorded but not gated.
The
ratios couple the TPU number to a baseline floor RE-MEASURED on the bench
host each run (benches/boxed_baseline.py), so their variance includes the
host's; a genuine TPU regression already shows in the directly-measured
`value`, and gating the ratios only adds host-noise false alarms
(observed: a 123 s floor window vs the 165 s median flagged
`vs_boxed_floor_workers_parallel` while the epoch itself was in range).
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional, Tuple

HISTORY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "history.json")
DEFAULT_TOLERANCE = 0.35  # shared-chip variance headroom (TIMING metrics)

# Per-metric-CLASS tolerances (VERDICT item 5): one 35% knob sized for
# shared-chip timing variance would let a deterministic-seeded convergence
# metric regress 0.1648 -> 0.22 unflagged.  Classes, checked in order:
#
# - loss/acc: deterministic given the seed — a 2% band catches a real
#   convergence break while absorbing float-order drift (quorum/chaos
#   runs assert their own in-run parity bounds besides);
# - bytes: wire traffic is shape-determined, not timing-determined — 10%
#   absorbs protobuf framing jitter across refactors while failing a
#   silently re-inflated payload;
# - latency quantiles (`*_p50_s` / `*_p99_s`, the serve-bench SLO rows):
#   lower-is-better like every `_s` metric, but tail latency on a shared
#   host is noisier than a median wall clock AND more load-bearing than a
#   timing diagnostic — a 50% band fails a doubled p99 (a real routing /
#   batching break) without false-alarming on scheduler jitter that the
#   bench's own hard SLO assert already bounds;
# - spin-up latency (`*_spinup_s`, the bench_spinup join rows): one-shot
#   subprocess wall clocks dominated by XLA compile (cold) or disk-cache
#   reads (warm) — noisier than steady-state slope fits, and the bench's
#   own >= 2x cold/warm hard assert is the load-bearing gate; 50% fails
#   a genuinely broken fast path (a warm join that compiles again
#   roughly triples) without false-alarming on build-host jitter;
# - round throughput (`*_rounds_per_s`, the rpc-bench streaming rows):
#   HIGHER is better — the suffix ends in `_s`, which the naive
#   lower-is-better timing rule would gate BACKWARDS (treating a
#   throughput gain as a regression and a collapse as an improvement);
#   direction() resolves `_per_s` first, and this class entry pins the
#   pairing explicitly so the rule can never silently reorder.  The
#   35% band matches the loopback-RPC timing variance the rows measure;
# - scaling efficiency (`*_scale_eff`, the bench_scale worker-count
#   sweep): HIGHER is better — the ratio of rounds/s at a swept worker
#   count to rounds/s at the smallest count, i.e. how flat the master's
#   per-round cost stays as N grows.  A collapse here means a master
#   stage went serial-in-N again; the 35% band matches the loopback
#   throughput variance of the rows the ratio is built from;
# - recovery rounds (`*_recovery_rounds`, the flywheel bench): LOWER is
#   better — how many probe-refresh rounds the autopilot needs to pull
#   serving loss back inside the pre-shift parity band after a planted
#   distribution shift.  The count is quantized by the refresh cadence
#   and depends on thread-scheduling races between the pump, the health
#   loop, and the retrain, so it is latency-shaped noise-wise: the 50%
#   band fails a flywheel that roughly doubles its recovery (a detector
#   or warm-start break) without false-alarming on cadence jitter the
#   bench's own hard round-budget assert already bounds;
# - bytes reduction (`*_bytes_reduction`, the bench_scale shard sweep):
#   HIGHER is better — the flat master's per-process wire total over the
#   worst shard lane's, i.e. how much broadcast+fan-in capacity
#   DSGD_MASTER_SHARDS takes off one master process.  Wire traffic is
#   shape-determined like the `_bytes` rows it is built from, so the
#   same 10% band applies: a silently re-inflated slice wire fails the
#   gate without timing noise ever touching it;
# - everything else (seconds, rates, `value`): the 35% shared-chip knob.
CLASS_TOLERANCES = (
    (("_loss", "_acc"), 0.02),
    (("_bytes",), 0.10),
    (("_bytes_reduction",), 0.10),
    (("_p50_s", "_p99_s"), 0.50),
    (("_spinup_s",), 0.50),
    (("_rounds_per_s",), 0.35),
    (("_scale_eff",), 0.35),
    (("_recovery_rounds",), 0.50),
    # leak slopes (`*_slope`, the bench_soak long-horizon rows): LOWER is
    # better — Theil–Sen units/s of rss (bytes) or fds across the chaos
    # soak.  A healthy soak's slope hovers around ZERO and flips sign with
    # allocator/GC timing, so a relative band around the median is mostly
    # noise-vs-noise; the 100% band only flags a slope that clearly
    # doubles a genuinely positive median, and check() additionally skips
    # gating entirely when either side is <= 0 (no leak to compare).  The
    # bench's own absolute thresholds (MAX_*_SLOPE) are the load-bearing
    # gate — the history rows exist to watch the trend across rounds.
    (("_slope",), 1.00),
)


def tolerance_for(name: str, timing_tolerance: float = DEFAULT_TOLERANCE,
                  series: Optional[str] = None) -> float:
    """The gate tolerance for one metric: its class band, or the timing
    tolerance (the CLI `--tolerance` knob) when unclassed.

    Chaos/quorum series — the soak included — are exempt from the tight
    loss/acc band: their loss depends on WHICH replies beat a wall-clock
    soft deadline, not only on the seed — bench_chaos's/bench_soak's own
    in-run parity bound (max(1.02*base, base+0.02), ~12% at typical
    losses) is the real gate, and a 2% history band would turn normal
    quorum-timing noise into false alarms."""
    if ((series or "").startswith(("chaos", "soak"))
            and name.endswith(("_loss", "_acc"))):
        return timing_tolerance
    # serve_ha (benches/bench_serve_ha.py): the HA scenario's p50/p99 ride
    # a load ramp AND a mid-run decider-router kill on a shared CI box —
    # the bench's own hard SLO assert is the latency gate.  The history
    # series exists for the DETERMINISTIC rows (dropped-request count,
    # split-brain window, failover/rollback counters, recorded as *_info)
    # — timing noise must not block recording those, so the latency
    # columns of this class report but never gate.
    if ((series or "").startswith("serve_ha")
            and name.endswith(("_p50_s", "_p99_s"))):
        return float("inf")
    for suffixes, tol in CLASS_TOLERANCES:
        if name.endswith(suffixes):
            return tol
    return timing_tolerance


def direction(name: str) -> Optional[str]:
    """'down' = lower is better, 'up' = higher is better, None = don't gate.

    `vs_*` ratios are deliberately ungated: their denominator is the
    boxed-map floor re-measured on the bench HOST each run, so the ratio's
    variance includes host noise that `value` (the direct TPU measurement)
    does not (see module docstring)."""
    # host-measured quantities (the boxed floor, JVM-model scalars) are
    # recorded but never gated: their variance is the bench HOST's, not the
    # framework's — the same reason the vs_* ratios are ungated
    if "floor" in name or "jvm" in name:
        return None
    # rate suffixes first: "*_per_s" would otherwise match the "_s"
    # lower-is-better check and gate throughput backwards; scaling
    # efficiency (`*_scale_eff`, bench_scale.py) and bytes reduction
    # (`*_bytes_reduction`, the shard sweep) are higher-is-better ratios
    # — the latter checked BEFORE the `_bytes` lower-is-better rule so a
    # bigger reduction can never be gated as re-inflated wire
    if name.endswith(("_per_s", "_acc", "_scale_eff", "_bytes_reduction")):
        return "up"
    # wire-traffic series (benches/bench_rpc_sync.py, bench_comms.py):
    # bytes gate DOWN so a PR that silently re-inflates the broadcast or
    # fan-in payloads fails the gate; `*_info` fields are context only
    # (e.g. the default path's loss, whose gating belongs to ITS series)
    if name.endswith("_info"):
        return None
    if name.endswith("_bytes"):
        return "down"
    # *_loss gates DOWN: the north star is epoch time AT MATCHED final
    # loss (BASELINE.md), so the loss half of the pair must gate too —
    # final_acc alone is an insensitive proxy for a convergence break.
    # *_recovery_rounds gates DOWN: fewer probe-refresh rounds from
    # shift to recovered means a faster flywheel (bench_flywheel.py)
    # *_slope gates DOWN: a leak slope (units/s) growing across rounds is
    # a slow-burn regression even when each run's absolute bar passes
    # (bench_soak.py long-horizon rows; near-zero medians are exempted in
    # check() — see CLASS_TOLERANCES)
    if (name.endswith(("_seconds", "_s", "_loss", "_recovery_rounds",
                       "_slope"))
            or name == "value"):
        return "down"
    return None


def numeric_fields(run: Dict) -> Dict[str, float]:
    return {
        k: float(v) for k, v in run.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)
    }


def load_history(path: str = HISTORY) -> List[Dict]:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return json.load(f)


def save_history(history: List[Dict], path: str = HISTORY) -> None:
    with open(path, "w") as f:
        json.dump(history, f, indent=1)
        f.write("\n")


def median(xs: List[float]) -> float:
    s = sorted(xs)
    m = len(s) // 2
    return s[m] if len(s) % 2 else 0.5 * (s[m - 1] + s[m])


def check(
    run: Dict,
    history: List[Dict],
    tolerance: float = DEFAULT_TOLERANCE,
) -> Tuple[List[str], List[str]]:
    """Compare `run` against the metric-wise MEDIAN of `history`.

    Returns (regressions, report_lines).  A metric regresses when it is
    worse than the median by more than its CLASS tolerance (loss/acc 2%,
    bytes 10% — see CLASS_TOLERANCES) or, for unclassed timing metrics,
    `tolerance` (relative).  Metrics with no direction, no history, or a
    zero median are reported as ungated.

    When `run` carries a `"metric"` name, only history entries of the
    SAME series are compared (entries without a name stay eligible, so
    synthetic test histories keep working); runs without a name see the
    whole history unchanged.
    """
    series = run.get("metric")
    if series is not None:
        history = [h for h in history if h.get("metric") in (series, None)]
    fields = numeric_fields(run)
    regressions: List[str] = []
    lines: List[str] = []
    for name, value in sorted(fields.items()):
        d = direction(name)
        prior = [numeric_fields(h)[name] for h in history if name in numeric_fields(h)]
        if d is None or not prior:
            lines.append(f"  {name} = {value:g} (not gated)")
            continue
        med = median(prior)
        if med == 0:
            lines.append(f"  {name} = {value:g} (zero median, not gated)")
            continue
        if name.endswith("_slope") and (med <= 0 or value <= 0):
            # a non-positive slope is no leak, and a ratio against a
            # near-zero (or negative) median gates noise-vs-noise — the
            # bench's absolute MAX_*_SLOPE bars are the real gate
            lines.append(f"  {name} = {value:g} (non-positive slope, "
                         f"not gated)")
            continue
        tol = tolerance_for(name, tolerance, series=series)
        ratio = value / med
        bad = ratio > 1 + tol if d == "down" else ratio < 1 / (1 + tol)
        tag = "REGRESSED" if bad else "ok"
        lines.append(
            f"  {name} = {value:g} vs median {med:g} over {len(prior)} run(s) "
            f"[{d}, x{ratio:.2f}, tol {tol:.0%}] {tag}"
        )
        if bad:
            regressions.append(name)
    return regressions, lines


def record(run: Dict, path: str = HISTORY) -> None:
    history = load_history(path)
    history.append(run)
    save_history(history, path)


def gate(run: Dict, path: str = HISTORY, tolerance: float = DEFAULT_TOLERANCE,
         do_record: bool = True) -> int:
    """Check + optionally append; returns the exit code."""
    history = load_history(path)
    regressions, lines = check(run, history, tolerance)
    metric = run.get("metric", "?")
    print(f"regression gate for {metric!r} vs {len(history)} stored run(s), "
          f"tolerance {tolerance:.0%}:", file=sys.stderr)
    for ln in lines:
        print(ln, file=sys.stderr)
    if do_record:
        if regressions:
            # a regressed run NEVER enters history: appending it would pull
            # the rolling median toward the regression until it passes
            # (sparse_bench.py's kernel gate states the same policy)
            print(f"run NOT recorded (regressed; history {path} unchanged)",
                  file=sys.stderr)
        else:
            record(run, path)
            print(f"run appended to {path}", file=sys.stderr)
    if regressions:
        print(f"FAIL: regressed metrics: {', '.join(regressions)}", file=sys.stderr)
        return 1
    print("PASS", file=sys.stderr)
    return 0


def main(argv: List[str]) -> int:
    if not argv or argv[0] not in ("gate", "show"):
        print(__doc__, file=sys.stderr)
        return 2
    if argv[0] == "show":
        for run in load_history():
            print(json.dumps(run))
        return 0
    tolerance = DEFAULT_TOLERANCE
    do_record = "--no-record" not in argv
    for i, a in enumerate(argv):
        if a == "--tolerance":
            try:
                tolerance = float(argv[i + 1])
            except (IndexError, ValueError):
                print("--tolerance needs a numeric value", file=sys.stderr)
                return 2
    run = json.loads(sys.stdin.read())
    return gate(run, tolerance=tolerance, do_record=do_record)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
