"""The one-peer dynamic exponential graph (arXiv:2110.13363): in round
``k`` rank ``r`` averages its updated parameters with those of rank
``r - 2**k``, and the rounds repeat after ``log2(n)``.  Built from
these words alone, not from the program's schedule."""

import numpy as np

MIXES = "parameters"   # adapt, then combine


def matrices(n: int):
    """Row-stochastic ``W[dst, src]`` of each round."""
    out, shift = [], 1
    while shift < n:
        w = 0.5 * np.eye(n)
        for r in range(n):
            w[r, (r - shift) % n] += 0.5
        out.append(w)
        shift *= 2
    return out
