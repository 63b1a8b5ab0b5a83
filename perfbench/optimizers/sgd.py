"""SGD with momentum as the plain reference writes it (the trace is the
first moment, there is no second), what ``optax.sgd`` of the same keys
computes.  A traffic file's ``optimizer`` names this file and gives
``learning_rate`` and ``momentum``."""

import numpy as np


def moments(opt: dict, g, m, v):
    return opt["momentum"] * m + g, v


def apply(opt: dict, p, m, v, t, sqrt=np.sqrt):
    return p - opt["learning_rate"] * m


def first_gradient_scale(opt: dict) -> float:
    return 1.0
