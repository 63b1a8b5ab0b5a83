"""AdamW (arXiv:1711.05101) as the plain reference writes it: decoupled
weight decay, bias-corrected moments, what ``optax.adamw`` of the same
keys computes.  A traffic file's ``optimizer`` names this file and gives
``learning_rate``, ``b1``, ``b2``, ``eps`` and ``weight_decay``."""

import numpy as np


def moments(opt: dict, g, m, v):
    """The two moments after a step with gradient ``g``."""
    b1, b2 = opt["b1"], opt["b2"]
    return b1 * m + (1 - b1) * g, b2 * v + (1 - b2) * g * g


def apply(opt: dict, p, m, v, t, sqrt=np.sqrt):
    """The parameters after step ``t`` (1-based) from the parameters
    before it and the moments after it."""
    mhat = m / (1 - opt["b1"] ** t)
    vhat = v / (1 - opt["b2"] ** t)
    step = mhat / (sqrt(vhat) + opt["eps"]) + opt["weight_decay"] * p
    return p - opt["learning_rate"] * step


def first_gradient_scale(opt: dict) -> float:
    """What the first moment after one step is multiplied by to give
    the first gradient."""
    return 1.0 / (1.0 - opt["b1"])
