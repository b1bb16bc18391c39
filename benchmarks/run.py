"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on the machine that holds the chips the
cell asks for.  Progress goes out as JSON lines; the LAST line of stdout is
the result: `correct`, `attempted`, `failed`, `metrics`, `device`, and with
`--trace 1` `breakdown`.  `--trace 0` prints the cell's end-to-end metrics,
`--trace 1` its per-layer metrics from a run with the profiler on.  Without
a TPU (or with fewer chips than the cell asks for) it exits non-zero and
prints no result.

    python3 benchmarks/run.py --workload <cell> --sweep 1,2,3,4,6 --seconds 30

is the knee sweep of an open-loop cell: one replica, each rate in turn
(see README.md); it prints a table and no result line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import loader  # noqa: E402

MEASURED_PLATFORM = "tpu"


def log(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def run_cell(cell: loader.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, platform: str = MEASURED_PLATFORM,
             log=log) -> dict:
    """Run the cell on the cluster this process is connected to; returns
    the result line as a dict.  `platform` is what the lease-holder must be
    on: the command always measures a TPU; only the rehearsal in the tests
    passes the platform its CPU cluster gives a lease-holder."""
    out = cell.driver.run(cell, seed, seconds, trace, t_start, platform, log)
    if out["problems"]:
        log(phase="check", problems=out["problems"])
        # Also where a caller that keeps only the end of stderr finds it.
        print("benchmark: not correct: " + "; ".join(out["problems"]),
              file=sys.stderr, flush=True)
    # Each number compared beside its limit, in every run.
    for line in out["checked"]:
        print("benchmark: compared: " + line, file=sys.stderr, flush=True)
    device = dict(out["device"])
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        reduced = out["obs"]["trace"]
        result["metrics"] = loader.read_layer_metrics(cell, out["obs"])
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        elif platform == MEASURED_PLATFORM:
            # (The CPU rehearsal's trace has no device plane to reduce.)
            raise loader.BenchmarkError(
                "the traced run holds no device operation")
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        missing = set(units) - set(out["end_to_end"])
        if missing:
            raise loader.BenchmarkError(f"the driver reported no {missing}")
        result["metrics"] = {k: {"value": float(out["end_to_end"][k]),
                                 "unit": units[k]} for k in units}
    result["device"] = device
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates (req/s): the knee sweep")
    args = ap.parse_args(argv)

    cell = loader.load_cell(args.workload)
    seconds = args.seconds if args.seconds is not None \
        else float(loader.load_benchmark()["run_seconds"])

    import ray_tpu
    from ray_tpu._private import accelerator

    from benchmarks.harness import cluster

    chips = accelerator.detect_tpu_chip_count()
    if chips < cell.chips:
        raise loader.BenchmarkError(
            f"this machine shows {chips} TPU chip(s) and the cell asks for "
            f"{cell.chips}: nothing is measured without the chip")
    log(phase="start", workload=cell.name, seed=args.seed, seconds=seconds,
        trace=args.trace, driver_pid=os.getpid())
    ray_tpu.init()
    try:
        if args.sweep:
            from benchmarks.harness import sweep

            sweep.run(cell, args.seed, seconds,
                      [float(r) for r in args.sweep.split(",")],
                      T_START, MEASURED_PLATFORM, log)
            result = None
        else:
            result = run_cell(cell, args.seed, seconds, bool(args.trace),
                              T_START)
    finally:
        ray_tpu.shutdown()
    cluster.driver_stayed_off_jax()
    if result is not None:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except loader.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        sys.exit(1)
