"""Device time a train step spends with a collective in flight
(``collective-permute`` and kin; an asynchronous pair counts from its
start's begin to its done's end), per step, mean over the chips."""

from perfbench.harness import trace as tr


def reduce(trace, spans, ctx):
    if ctx.get("chips", 1) < 2:
        return None
    in_flight, _ = tr.exchange_seconds(trace)
    steps = len(tr.module_calls(trace))
    if in_flight <= 0 or steps == 0:
        return None
    print("[exchange_ms] by chip, in flight / exposed ms a step over "
          f"{steps} steps: " + ", ".join(
              f"{1e3 * a / steps:.2f} / {1e3 * b / steps:.2f}"
              for a, b in tr.exchange_seconds_by_chip(trace)), flush=True)
    return 1e3 * in_flight / steps
