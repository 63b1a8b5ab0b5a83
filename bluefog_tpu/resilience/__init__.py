"""Resilience subsystem: fault injection, failure detection, topology
healing, and guarded-rollback training.

The reference (and the paper) argue decentralized neighbor averaging
tolerates imperfect communication; this package makes the TPU build
actually survive it, in four shape-stable layers — faults change
jitted-program *inputs*, never shapes, so nothing ever recompiles:

* :mod:`~bluefog_tpu.resilience.faults` — deterministic fault plans
  (NaN/Inf gradient bursts, rank death, host stalls) injected through
  the batch, for tests and the chaos benchmark
  (benchmarks/chaos_resilience.py);
* :mod:`~bluefog_tpu.resilience.detector` — per-rank numeric health
  from the guard's in-graph ``isfinite`` reduce + process liveness from
  the heartbeat beacons;
* :mod:`~bluefog_tpu.resilience.healing` — dead-rank excision as a
  weight re-planning problem: row-stochasticity-preserving healed
  weights delivered as traced DATA to the train step's programs (one
  a round of the schedule, the tables an operand of each);
* :mod:`~bluefog_tpu.resilience.runner` — ``run_resilient``, the
  skip -> detect -> heal -> rollback-with-backoff control loop over the
  ``Checkpointer``.

The jitted half lives in ``optim.functional``:
``build_train_step(..., guard=GuardConfig(...))``.  The GROWTH
direction of the lifecycle — ranks that join back, with quarantined
bootstrap and the exact inverse of healing — is the sibling package
:mod:`bluefog_tpu.elastic` (``run_resilient(elastic=...)``).  Guide:
docs/resilience.md.
"""

from bluefog_tpu.optim.functional import (  # noqa: F401
    GuardConfig,
    comm_weight_inputs,
)
from bluefog_tpu.resilience.faults import (  # noqa: F401
    Fault,
    FaultPlan,
    PREEMPT,
    ServingFault,
    ServingFaultPlan,
)
from bluefog_tpu.resilience.detector import (  # noqa: F401
    FailureDetector,
    update_health,
)
from bluefog_tpu.resilience.healing import (  # noqa: F401
    consensus_simulation,
    heal_spec,
    heal_weights,
    healed_comm_weights,
    is_row_stochastic,
    mixing_matrix,
    mixing_matrix_from_weights,
    row_sums,
)
from bluefog_tpu.resilience.runner import (  # noqa: F401
    ResilienceEvent,
    ResilientResult,
    run_resilient,
)
# the growth direction of the lifecycle rides run_resilient(elastic=...),
# so its config is part of this package's surface too
from bluefog_tpu.elastic.membership import ElasticConfig  # noqa: F401

__all__ = [
    "ElasticConfig",
    "GuardConfig",
    "comm_weight_inputs",
    "Fault",
    "FaultPlan",
    "PREEMPT",
    "ServingFault",
    "ServingFaultPlan",
    "FailureDetector",
    "update_health",
    "consensus_simulation",
    "heal_spec",
    "heal_weights",
    "healed_comm_weights",
    "is_row_stochastic",
    "mixing_matrix",
    "mixing_matrix_from_weights",
    "row_sums",
    "ResilienceEvent",
    "ResilientResult",
    "run_resilient",
]
