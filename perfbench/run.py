"""The benchmark's one command.

  python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: find the cell's chips or exit non-zero, turn on the
compile cache, let the cell's runner build, check and time the system,
and print one JSON object as the last line of standard output.  With
``--trace 0`` its ``metrics`` are the cell's end-to-end metrics; with
``--trace 1`` they are its per-layer metrics, read by
``layer_metrics/<name>.py`` from the profiler's trace and the
benchmark's spans.  Its last key, ``checked``, holds each number the
output check compared beside its limit, and standard error ends with the
same.  Everything else worth reading goes on earlier lines.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python gives it

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OUT_DIR = "perfbench_out"  # under the checkout; traces; in .gitignore


def say(text: str) -> None:
    print(f"[run] {text}", flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def layer_metrics(cell, trace, spans, ctx) -> dict:
    """Every per-layer metric of the cell whose reader finds something
    to read."""
    out = {}
    for entry in cell.per_layer:
        value = cell.layer_metric(entry["name"]).reduce(trace, spans, ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def checked(result) -> dict:
    """Each number the runner's check compared, beside its limit (a
    reading that is no number, nothing having been served: ``null``)."""
    return {name: {"value": value if math.isfinite(value) else None,
                   "limit": limit}
            for name, (value, limit) in result["checked"].items()}


def main(argv=None, t_start: float = T_START, root=None) -> int:
    """``root``: where ``BENCHMARK.json`` and ``perfbench/`` are read
    from (the tests' temporary copy); the command itself reads its own
    checkout."""
    args = parse_args(argv)
    from perfbench.harness import clocks, device, loader, trace as tr

    try:
        cell = loader.load_cell(args.workload, root or loader.ROOT)
    except loader.BenchmarkError as e:
        sys.exit(f"perfbench: {e}")
    devices = device.require_chips(cell.chips)

    import jax

    cache_dir = device.configure_compile_cache()
    d = devices[0]
    say(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, {d.platform} {d.device_kind} x{len(devices)}"
        f", jax {jax.__version__}, compile cache {cache_dir}")

    trace_dir = os.path.join(str(cell.root), OUT_DIR, "trace", cell.name)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    spans = clocks.Spans()
    result = cell.runner().run(
        cell, args.seed, args.seconds, bool(args.trace), devices, spans,
        t_start, trace_dir)

    record = {"correct": result["correct"],
              "attempted": result["attempted"], "failed": result["failed"]}
    dev_rec = device.device_record(devices, result.get("program_bytes", 0))
    say(f"peak_bytes_in_use "
        f"{max(device.memory_stat(x, 'peak_bytes_in_use') for x in devices)}"
        f", compiler's count of the largest resident program "
        f"{result.get('program_bytes', 0)}")
    if args.trace:
        trace = tr.load(tr.find_xplane(trace_dir))
        busy_s, window_s = tr.busy_and_window_s(trace)
        if busy_s <= 0:
            sys.exit("perfbench: the trace shows no operation on the "
                     "device")
        dev_rec.update(busy_s=busy_s, window_s=window_s)
        ctx = dict(result["ctx"], spans=spans)
        record["metrics"] = layer_metrics(cell, trace, spans, ctx)
        record["breakdown"] = tr.breakdown(trace)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        missing = set(units) - set(result["end_to_end"])
        if missing:
            sys.exit(f"perfbench: the runner reported no {sorted(missing)}")
        record["metrics"] = {
            name: {"value": float(result["end_to_end"][name]),
                   "unit": unit} for name, unit in units.items()}
    record["device"] = dev_rec
    # what decided ``correct``, last on the line and last on standard
    # error: the driver keeps the end of both of a run that is not
    record["checked"] = checked(result)
    for name, pair in record["checked"].items():
        print(f"perfbench check: {name} = {pair['value']} (limit "
              f"{pair['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
