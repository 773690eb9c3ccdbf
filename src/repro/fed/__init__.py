from repro.fed.client import Client, ClientUpload
from repro.fed.cohort import (
    FamilyBucket,
    partition_fleet,
    split_cohort,
    validate_family_contracts,
)
from repro.fed.engines import (
    BatchedEngine,
    BroadcastState,
    ClientPhase,
    FusedE2EEngine,
    FusedEngine,
    HeteroClientEngine,
    HeteroFusedE2EEngine,
    RoundsTrajectory,
    SequentialEngine,
    make_engine,
)
from repro.fed.rounds import METHODS, FedConfig, FedRun, run_federated
from repro.fed.server import Server

__all__ = [
    "Client",
    "ClientUpload",
    "Server",
    "METHODS",
    "FedConfig",
    "FedRun",
    "run_federated",
    "BatchedEngine",
    "FusedEngine",
    "FusedE2EEngine",
    "HeteroClientEngine",
    "HeteroFusedE2EEngine",
    "SequentialEngine",
    "BroadcastState",
    "ClientPhase",
    "RoundsTrajectory",
    "FamilyBucket",
    "partition_fleet",
    "split_cohort",
    "validate_family_contracts",
    "make_engine",
]
