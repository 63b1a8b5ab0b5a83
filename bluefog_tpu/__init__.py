"""bluefog_tpu — a TPU-native decentralized deep-learning training framework.

A from-scratch JAX/XLA re-design of the capabilities of BlueFog
(reference: /root/reference, a Horovod-style C++ MPI/NCCL core with torch
bindings).  Instead of a background negotiation thread + MPI graph
communicators, this build lowers every decentralized primitive to XLA
collectives (``lax.ppermute`` / ``psum`` / ``all_gather``) over a
``jax.sharding.Mesh``, so neighbor averaging rides the ICI/DCN fabric with
no host round-trips.

Public surface mirrors ``bluefog.torch`` (reference
bluefog/torch/__init__.py:34-110); see ``bluefog_tpu.api`` for the
flat op API and ``bluefog_tpu.topology`` for graph generators.
"""

from bluefog_tpu.version import __version__

# Flat API re-exports (reference: bluefog/torch/__init__.py:34-110).
from bluefog_tpu.api import (  # noqa: F401
    init,
    shutdown,
    is_initialized,
    size,
    local_size,
    rank,
    local_rank,
    machine_size,
    machine_rank,
    load_topology,
    set_topology,
    is_topo_weighted,
    load_machine_topology,
    set_machine_topology,
    is_machine_topo_weighted,
    in_neighbor_ranks,
    out_neighbor_ranks,
    in_neighbor_machine_ranks,
    out_neighbor_machine_ranks,
    is_homogeneous,
    suspend,
    resume,
    set_skip_negotiate_stage,
    get_skip_negotiate_stage,
    mpi_threads_supported,
    unified_mpi_window_model_supported,
    nccl_built,
    # collectives
    allreduce,
    allreduce_nonblocking,
    allreduce_,
    allreduce_nonblocking_,
    allgather,
    allgather_nonblocking,
    broadcast,
    broadcast_nonblocking,
    broadcast_,
    broadcast_nonblocking_,
    neighbor_allgather,
    neighbor_allgather_nonblocking,
    neighbor_allreduce,
    neighbor_allreduce_nonblocking,
    hierarchical_neighbor_allreduce,
    hierarchical_neighbor_allreduce_nonblocking,
    pair_gossip,
    pair_gossip_nonblocking,
    barrier,
    poll,
    synchronize,
    wait,
    # windows
    win_create,
    win_free,
    win_update,
    win_update_then_collect,
    win_put,
    win_put_nonblocking,
    win_get,
    win_get_nonblocking,
    win_accumulate,
    win_accumulate_nonblocking,
    win_set_value,
    win_wait,
    win_poll,
    win_mutex,
    win_lock,
    win_unlock,
    win_fence,
    get_win_version,
    get_current_created_window_names,
    win_associated_p,
    turn_on_win_ops_with_associated_p,
    turn_off_win_ops_with_associated_p,
    # timeline
    timeline_start_activity,
    timeline_end_activity,
    timeline_context,
    # data helpers
    rank_sharded,
    from_rank_values,
    to_rank_values,
)

from bluefog_tpu.utility import (  # noqa: F401
    broadcast_parameters,
    allreduce_parameters,
    broadcast_optimizer_state,
)

from bluefog_tpu import topology  # noqa: F401
from bluefog_tpu.topology import (  # noqa: F401
    # reference exposes these on the main module (torch/__init__.py:109)
    InferDestinationFromSourceRanks,
    InferSourceFromDestinationRanks,
    # the documented default one-peer schedule for pod torus shapes,
    # picked by machine-counted congestion + mixing score (torus.py)
    default_pod_schedule,
)
from bluefog_tpu import observe  # noqa: F401
from bluefog_tpu import optim  # noqa: F401
from bluefog_tpu import resilience  # noqa: F401
from bluefog_tpu import data  # noqa: F401
from bluefog_tpu.data import (  # noqa: F401
    DataLoader,
    DistributedSampler,
    device_prefetch,
    load_mnist,
    load_cifar10,
)
