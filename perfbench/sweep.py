"""The sweep that finds a serving cell's knee, once, on the chip: one
warmed engine, one window at each offered rate, the same traffic file
otherwise.  The knee is the highest rate at which at least 98% of the
offered requests complete inside the window plus the drain time and the
queue is no deeper on average in the second half of the window than in
the first (by more than half a request); the cell's rate is four fifths
of it, written into the traffic file by hand with this table in
PERF.md.

  python3 perfbench/sweep.py --workload <name> --rates 2,3,4 --seconds 20 --seed 1
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda t: [float(x) for x in t.split(",")])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from perfbench.harness import clocks, device, loader

    cell = loader.load_cell(args.workload)
    devices = device.require_chips(cell.chips)
    device.configure_compile_cache()
    runner, traffic = cell.runner(), cell.traffic
    spans = clocks.Spans()
    t0 = clocks.now()
    server = runner.Server(cell, args.seed, spans)
    print(f"set-up {clocks.now() - t0:.1f} s on {devices[0].device_kind}",
          flush=True)
    print("rate/s requests completed tokens/s ttft_p50 ttft_p95 itl_p50 "
          "itl_p95 queue_1st queue_2nd late_p95 step_ms", flush=True)
    for i, rate in enumerate(args.rates):
        seed = args.seed + i
        due, prompts, outputs = runner.schedule(traffic, args.seconds,
                                                rate=rate)
        requests = runner.make_requests(server.sz, prompts, outputs, seed)
        trial = runner.drive(server, requests, due, args.seconds,
                             traffic["drain_s"])
        s = runner.summarize(trial, requests, due, args.seconds)
        steps = [e - b for b, e in trial.step_spans]
        pct = clocks.percentile
        print(f"{rate:6.2f} {s['attempted']:8d} {s['completed_share']:9.3f} "
              f"{s['serve_tokens_per_s']:8.1f} "
              f"{pct(s['ttft_ms'], 50):8.1f} {pct(s['ttft_ms'], 95):8.1f} "
              f"{pct(s['itl_ms'], 50):7.2f} {pct(s['itl_ms'], 95):7.2f} "
              f"{s['queue_depth_halves'][0]:9.2f} "
              f"{s['queue_depth_halves'][1]:9.2f} "
              f"{pct(s['late_ms'], 95):8.2f} "
              f"{1e3 * clocks.median(steps):7.2f}", flush=True)
        # leave the engine empty for the next rate
        for r in requests:
            if not r.done:
                server.engine.cancel(r)
        while server.engine.step():
            pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
