"""One run of one cell:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result. Exits non-zero, with no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cell(cell, seed, seconds, trace, devices, log, clock,
             keep_trace=None, **driver_args) -> int:
    """Everything after the look for a chip: drive the cell, judge it,
    print the result line."""
    from perfbench import harness, trace_reader
    work_dir = os.path.join(ROOT, ".perfbench_work", cell.name)
    shutil.rmtree(work_dir, ignore_errors=True)
    driver = harness.driver_for(cell.traffic["kind"])
    measured, context, readings, tally = driver.run(
        cell, seed, seconds, trace, devices, clock, log,
        work_dir=work_dir, **driver_args)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": tally["memory_peak_bytes"]}
    result = {"correct": None, "attempted": tally["attempted"],
              "failed": tally["failed"]}
    if trace:
        context["trace"] = reduced = trace_reader.reduce(
            trace_reader.load(context["trace_file"]))
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(context["trace_file"], keep_trace)
        shutil.rmtree(work_dir, ignore_errors=True)
        if not reduced.get("busy_s"):
            raise SystemExit("perfbench: no device operation in the trace")
        for plane, s in sorted(reduced["busy_s_by_device"].items()):
            print(f"busy {plane}: {s:.6f} s of {reduced['window_s']:.6f}",
                  file=sys.stderr)
        result["metrics"] = harness.layer_metrics(cell, context)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
            d.items(), key=lambda kv: -kv[1])[:10]]
        result["breakdown"] = {
            "device_ops": top(trace_reader.grouped(reduced["ops_s"])),
            "idle_gaps": top(reduced["gaps_s"])}
    else:
        result["metrics"] = {
            m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
            for m in cell.metrics("end_to_end")}
    result["device"] = device
    checks = harness.judge(readings, cell.limits)
    result["correct"] = (all(c["ok"] for c in checks.values())
                         and tally["failed"] == 0)
    harness.emit(result, checks)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this "
                         "directory (for a reader's test fixture)")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from perfbench import harness
    cell = harness.Cell(args.workload)
    harness.place_compile_cache()
    devices = harness.require_chips(cell.chips)
    log = harness.CompileLog()
    clock = lambda: time.perf_counter() - _START  # noqa: E731
    print(f"set-up: {len(devices)} chips by {clock():.2f} s", file=sys.stderr)
    return run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                    log, clock, keep_trace=args.keep_trace)


if __name__ == "__main__":
    sys.exit(main())
