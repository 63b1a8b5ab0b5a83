"""No exchange: every rank keeps what it computed."""

import numpy as np

MIXES = "parameters"


def matrices(n: int):
    return [np.eye(n)]
