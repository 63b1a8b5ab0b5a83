"""Continuous-batching serving demo: the slot-pooled engine under
synthetic Poisson traffic.

  python examples/serve_llama.py
  python examples/serve_llama.py --rate 20 --num-requests 16 --capacity 4
  python examples/serve_llama.py --timeline /tmp/serve_tl   # + tracing

Requests (random prompts, varied lengths and token budgets, a few with
tight deadlines) arrive on a seeded Poisson trace; the engine admits
them into K/V slots as they arrive, mixes chunked prefill with batched
decode every step, and retires slots on budget/EOS/deadline.  Prints a
per-request line as each retires and the serving metrics summary at the
end.  With ``--timeline`` the per-request lifecycle spans
(admission -> prefill -> decode -> retire) land in a chrome://tracing
file.  See docs/serving.md.
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from bluefog_tpu import models, timeline
from bluefog_tpu.benchutil import poisson_arrivals
from bluefog_tpu.config import configure_compilation_cache
from bluefog_tpu.serving import Request, RequestRejected, ServingEngine

parser = argparse.ArgumentParser()
parser.add_argument("--num-requests", type=int, default=12)
parser.add_argument("--rate", type=float, default=30.0,
                    help="Poisson arrival rate, requests/s")
parser.add_argument("--capacity", type=int, default=4)
parser.add_argument("--max-len", type=int, default=96)
parser.add_argument("--prefill-chunk", type=int, default=16)
parser.add_argument("--decode-horizon", type=int, default=4)
parser.add_argument("--seed", type=int, default=0)
parser.add_argument("--temperature", type=float, default=0.0)
parser.add_argument("--timeline", default=None, metavar="PATH",
                    help="write request-lifecycle spans to PATH<rank>.json")


def main():
    configure_compilation_cache()
    args = parser.parse_args()
    cfg = models.LlamaConfig.tiny(dtype=jnp.float32)
    variables = models.Llama(cfg).init(jax.random.PRNGKey(1),
                                       jnp.zeros((1, 4), jnp.int32))
    if args.timeline:
        timeline.start_timeline(args.timeline)

    eng = ServingEngine(variables, cfg, capacity=args.capacity,
                        max_len=args.max_len,
                        prefill_chunk=args.prefill_chunk,
                        decode_horizon=args.decode_horizon,
                        max_queue=args.num_requests)
    rs = np.random.RandomState(args.seed)
    arrivals = poisson_arrivals(args.rate, args.num_requests, args.seed)
    reqs = []
    for i in range(args.num_requests):
        prompt = rs.randint(0, cfg.vocab_size,
                            (rs.randint(3, 32),)).astype(np.int32)
        deadline = None
        if i % 5 == 4:  # every 5th request carries a tight deadline
            deadline = float(arrivals[i]) + 0.05
        # budget clamped so prompt + budget fits the slot (submit
        # rejects requests that could never fit)
        budget = min(int(rs.randint(4, 40)), args.max_len - prompt.size)
        reqs.append(Request(prompt, budget,
                            temperature=args.temperature, seed=i,
                            deadline=deadline))

    t0 = time.monotonic()
    pending = list(range(args.num_requests))
    reported = set()
    while True:
        now = time.monotonic() - t0
        while pending and arrivals[pending[0]] <= now:
            i = pending.pop(0)
            try:
                eng.submit(reqs[i])
            except RequestRejected as exc:
                print(f"req {reqs[i].rid}: rejected ({exc})")
                reported.add(i)
        busy = eng.step()
        for i, r in enumerate(reqs):
            if i not in reported and r.done:
                print(f"req {r.rid}: {r.state:9s} prompt={r.prompt.size:2d} "
                      f"generated={len(r.tokens):2d} "
                      f"ids={r.tokens[:8]}{'...' if len(r.tokens) > 8 else ''}")
                reported.add(i)
        if not busy:
            if not pending:
                break
            time.sleep(max(0.0, arrivals[pending[0]]
                           - (time.monotonic() - t0)))

    print("serving metrics:", eng.metrics.summary())
    if args.timeline:
        timeline.stop_timeline()
        print(f"timeline written: {args.timeline}0.json "
              "(load in chrome://tracing)")


if __name__ == "__main__":
    main()
