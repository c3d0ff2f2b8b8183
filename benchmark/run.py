"""One run of one cell of the benchmark, in one process that holds the
cell's chips:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (rows generated on the device from the seed, the program's engine
built as `main.py` builds it, warm-up inside the one fit), then a measured
window of `--seconds`, then the correctness checks outside all timing.
Earlier lines print the split of set-up, the checks and the fit's series;
the LAST line of stdout is the one JSON object the driver reads.  With
`--trace 0` its metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace the
benchmark takes itself inside the window.

Off a TPU, on another number of chips than the cell names, on a chip the
peak table does not list, or without the program beside it, the run exits
2 and prints no result.  `--rehearse` is the CPU form for finding faults:
tiny rows, any device, no metric printed.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _say(label: str, obj) -> None:
    print(f"{label}: {json.dumps(obj, default=float)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny rows, no device check, no metric")
    args = ap.parse_args(argv)

    from benchmark import harness

    try:
        bench = harness.load_benchmark(ROOT)
        cell = harness.load_cell(bench, args.workload, ROOT)
        if not os.path.isdir(os.path.join(ROOT, "distributed_sgd_tpu")):
            raise harness.BenchmarkError(
                "the program (distributed_sgd_tpu/) is not beside the benchmark")
        reported = harness.metrics_for(
            bench, "per_layer" if args.trace else "end_to_end", cell.name)
        readers = {m["name"]: harness.layer_reader(m["name"])
                   for m in reported} if args.trace else {}
        devices, device, peaks = harness.check_devices(cell.chips, args.rehearse)
    except harness.BenchmarkError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2

    from distributed_sgd_tpu import compile_cache

    compile_cache.place()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    seconds = float(args.seconds if args.seconds is not None else bench["run_seconds"])
    ctx = harness.Context(
        cell=cell, seed=args.seed, seconds=seconds, trace=bool(args.trace),
        rehearse=args.rehearse, t_process=T_PROCESS, devices=devices,
        device=device, peaks=peaks,
        trace_dir=os.path.join(ROOT, "benchmark", ".trace", cell.name))
    ctx.setup["reach_chip_s"] = time.perf_counter() - T_PROCESS

    driver = importlib.import_module(f"benchmark.drivers.{cell.traffic['engine']}")
    run = driver.run(ctx)
    setup_s = run.window_start - T_PROCESS
    _say("setup", dict(ctx.setup, setup_s=setup_s))
    _say("engine", run.engine)
    _say("fit", run.fit)
    _say("checks", run.checks)
    _say("window", {"seconds": run.window_seconds, "compiles": run.compiles,
                    "attempted": run.attempted, "failed": run.failed,
                    "periods": len(run.periods), "counters": run.counters})

    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes(devices))
    if args.rehearse:
        # a rehearsal proves control flow, not speed: counts only
        _say("rehearsal", {"correct": run.correct, "attempted": run.attempted,
                           "failed": run.failed, "device": device,
                           "would_report": sorted(m["name"] for m in reported)})
        return 0

    out = {"correct": run.correct, "attempted": run.attempted, "failed": run.failed}
    metrics = {}
    if args.trace:
        from benchmark import reduce_trace

        if run.trace_path is None:
            print("benchmark/run.py: the traced run left no trace", file=sys.stderr)
            return 3
        run.trace = reduce_trace.reduce(run.trace_path, opens_in=run.trace_opens_in)
        _say("trace", dict(run.trace, devices={
            name: {k: v for k, v in dev.items() if k != "ops"}
            for name, dev in run.trace["devices"].items()}))
        for m in reported:
            value = readers[m["name"]].read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = run.trace["breakdown"]
    else:
        readings = dict(run.end_to_end, setup_s=setup_s)
        for m in reported:
            if m["name"] in readings:
                metrics[m["name"]] = {"value": float(readings[m["name"]]), "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
