"""Federated round engine (DESIGN.md §19): sharded PS plane + partial
participation at 10^6 clients.

The layer ABOVE the hierarchy: ``sharding`` partitions the flat
parameter vector across a PS shard group (the axis orthogonal to MSMW
replication), ``sampler`` prices a Byzantine budget per sampled cohort,
``engine`` runs the round loop (ingest -> per-shard hier-GAR ->
shard broadcast), and ``fleet`` drives simulated client processes
against a target round rate. ``apps/benchmarks/fed_bench.py`` drives them
end to end.
"""

from .engine import FedRoundEngine, ShardServer
from .fleet import ClientFleet, client_command
from .sampler import CohortSampler
from .sharding import (
    MAX_SHARDS,
    ShardSpec,
    plan_shards,
    reassemble,
    restore_sharded,
    save_sharded,
    shard_plane,
)

__all__ = [
    "MAX_SHARDS",
    "ShardSpec",
    "plan_shards",
    "shard_plane",
    "reassemble",
    "save_sharded",
    "restore_sharded",
    "CohortSampler",
    "ShardServer",
    "FedRoundEngine",
    "ClientFleet",
    "client_command",
]
