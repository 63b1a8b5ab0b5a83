"""The readings a limit of the output check is set from, at the cell's
own size, many seeds in one process (set-up is paid once where it can
be): for each seed the numbers that the program gives against the plain
reference, and for each control seed the numbers that the control gives
(the reference computed in the precision below the configuration's, in
the program's place).  PERF.md records what this printed and the limits
set between the two.

  python3 perfbench/readings.py --workload <name> --seeds 1,2,3 [--control-seeds 1,2,3] [--seconds 8] [--mix-only 1]

Prints one line a seed and, last, the largest sound and smallest
control reading of every number.  ``--mix-only 1`` (training cells)
reads ``mix_abs_gap`` alone, which needs no reference, through one
job; its control mixes the program's own updates by the exchange's
NEXT round, which on the program's side is a wrong peer.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def ints(text: str):
    return [int(x) for x in text.split(",") if x]


def train_readings(cell, devices, seeds, control_seeds):
    from perfbench.harness import clocks

    runner = cell.runner()
    limits = cell.traffic["limits"]
    sound, control = [], []
    for seed in seeds:
        want = runner.reference_readings(cell, seed, devices)
        job = runner.Job(cell, seed, devices, clocks.Spans())
        got = runner.program_readings(job)
        numbers, _ = runner.compare(got, want, limits)
        del job
        sound.append({k: v for k, (v, _) in numbers.items()})
        sound[-1]["mix_abs_gap"] = got["mix_abs_gap"]
        print(f"sound seed {seed}: {sound[-1]}", flush=True)
        if seed in control_seeds:
            low = runner.reference_readings(cell, seed, devices,
                                            control=True)
            numbers, _ = runner.compare(low, want, limits)
            control.append({k: v for k, (v, _) in numbers.items()})
            print(f"control seed {seed}: {control[-1]}", flush=True)
    return sound, control


def mix_readings(cell, devices, seeds, control_seeds):
    from perfbench.harness import clocks, reference_train

    runner = cell.runner()
    job = runner.Job(cell, seeds[0], devices, clocks.Spans())
    sound, control = [], []
    for seed in seeds:
        job.reseed(seed)
        got = runner.program_readings(job)
        sound.append({"mix_abs_gap": got["mix_abs_gap"]})
        print(f"sound seed {seed}: {sound[-1]}", flush=True)
        if seed in control_seeds:
            ws = [mix[0] for mix in got["mixes"]]
            wrong = ws[1:] + ws[:1]          # every step the next round
            control.append({"mix_abs_gap": max(
                reference_train.mix_gap(job.opt_spec, job.rule, w, *mix[1:])
                for w, mix in zip(wrong, got["mixes"]))})
            print(f"control (wrong peer) seed {seed}: {control[-1]}",
                  flush=True)
    return sound, control


def serve_readings(cell, devices, seeds, control_seeds, seconds):
    import jax

    from perfbench.harness import clocks

    runner, family = cell.runner(), cell.family()
    traffic = cell.traffic
    spans = clocks.Spans()
    server = runner.Server(cell, seeds[0], spans)
    sz = server.sz
    dtype = family.dtype_of(sz["param_dtype"])
    sound, control, params = [], [], None
    for seed in seeds:
        # new weights under the same warmed engine: its programs take
        # the parameters as an argument, so nothing recompiles
        key = jax.random.fold_in(runner.key_from_seed(seed), 0)
        server.params = server.engine._params = params = None  # free first
        params = jax.jit(
            lambda k: family.make_params(sz, k, dtype)[0])(key)
        server.params = server.engine._params = params
        due, prompts, outputs = runner.schedule(traffic, seconds)
        requests = runner.make_requests(sz, prompts, outputs, seed + 1)
        trial = runner.drive(server, requests, due, seconds,
                             traffic["drain_s"])
        stats = runner.summarize(trial, requests, due, seconds)
        sample = runner.check_sample(requests, seed,
                                     traffic["check_requests"])
        gap, read = runner.logit_gaps(cell, sz, params, requests, sample)
        sound.append({"logit_gap": gap})
        print(f"sound seed {seed}: logit_gap {gap:.6g} over {read} tokens "
              f"of {len(sample)} requests; completed "
              f"{stats['completed_share']:.3f} of {stats['attempted']}",
              flush=True)
        if seed in control_seeds:
            low, _ = runner.logit_gaps(cell, sz, params, requests, sample,
                                       control=True)
            control.append({"logit_gap": low})
            print(f"control seed {seed}: logit_gap {low:.6g}", flush=True)
    return sound, control


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, required=True)
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--mix-only", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from perfbench.harness import device, loader

    cell = loader.load_cell(args.workload)
    devices = device.require_chips(cell.chips)
    device.configure_compile_cache()
    if cell.traffic["runner"] == "serve":
        sound, control = serve_readings(cell, devices, args.seeds,
                                        args.control_seeds, args.seconds)
    elif args.mix_only:
        sound, control = mix_readings(cell, devices, args.seeds,
                                      args.control_seeds)
    else:
        sound, control = train_readings(cell, devices, args.seeds,
                                        args.control_seeds)
    for name in sound[0]:
        line = f"{name}: largest sound {max(s[name] for s in sound):.6g}" \
               f" over {len(sound)} seeds"
        if control and name in control[0]:
            line += f"; smallest control " \
                    f"{min(c[name] for c in control):.6g} over " \
                    f"{len(control)} seeds"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
