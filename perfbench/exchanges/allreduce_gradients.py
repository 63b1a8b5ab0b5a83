"""The global all-reduce that decentralized averaging replaces: every
rank's gradient becomes the mean of all the ranks' gradients before the
optimizer reads it, so every rank takes the same step.  One round."""

import numpy as np

MIXES = "gradients"   # combine the gradients, then adapt


def matrices(n: int):
    """Row-stochastic ``W[dst, src]``: every entry ``1 / n``."""
    return [np.full((n, n), 1.0 / n)]
