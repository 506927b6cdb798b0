"""ENEAC core: the paper's contribution as composable JAX/host modules.

* :mod:`repro.core.scheduler` — MultiDynamic heterogeneous chunk scheduler.
* :mod:`repro.core.interrupts` — completion-driven async engine (interrupt
  analogue) + busy-wait baseline.
* :mod:`repro.core.backends` — real backend units (threads, process
  pools, jax device streams) + the event-driven wall-clock engine.
* :mod:`repro.core.transport` — message-level transports (loopback,
  TCP, fault-injecting) and remote shard engines: ``RemoteWorker``
  hosts backend units behind a transport, ``RemoteUnit`` proxies them
  into the runtime as ordinary units.
* :mod:`repro.core.hetero` — throughput-proportional work partitioning.
* :mod:`repro.core.costmodel` — online per-(unit, kernel) cost model:
  EWMA capability descriptors learned from run reports, persisted as a
  versioned JSON store; feeds ``policy="learned"`` splits.
* :mod:`repro.core.straggler` — straggler detection and mitigation.
* :mod:`repro.core.elastic` — node-failure handling / mesh rescale plans.
* :mod:`repro.core.fleet` — fleet membership: heartbeat liveness ledger,
  queue-driven autoscaling, seeded churn simulation, and the wall-clock
  manager that owns ``spawn_worker`` subprocesses.
* :mod:`repro.core.moe_dispatch` — capacity-chunk MoE dispatch with dense
  fallback (the LM-native instantiation of MultiDynamic).
* :mod:`repro.core.parallel_for` — hybrid MXU/VPU executor for irregular
  workloads (SPMM).
* :mod:`repro.core.space` — iteration spaces: flat ranges, 2D kernel
  tile grids, and host-sharded spaces with merged global reports.
* :mod:`repro.core.runtime` — :class:`HeteroRuntime`, the unified front
  door: scheduler policy × completion engine × clock × iteration space
  behind one ``parallel_for`` (the paper's Fig. 2 pipeline end-to-end),
  with elastic unit join/leave under :class:`SimulatedClock`.
"""

from .scheduler import Chunk, MultiDynamicScheduler, OracleStaticScheduler, StaticScheduler, WorkerKind
from .interrupts import CompletionEvent, PollingEngine, RunReport
from .backends import (
    BackendEngine,
    BackendUnit,
    CompletionBus,
    CompletionRecord,
    InlineUnit,
    JaxDeviceUnit,
    ProcessPoolUnit,
    ThreadUnit,
    WorkerDead,
    WorkerLost,
)
from .transport import (
    FlakyTransport,
    LoopbackTransport,
    RemoteUnit,
    RemoteWorker,
    SocketTransport,
    Transport,
    TransportClosed,
    TransportError,
    WorkerServer,
    spawn_worker,
)
from .space import FlatSpace, IterationSpace, ShardedSpace, TiledSpace
from .costmodel import CostEntry, CostModel, CostModelWarning
from .runtime import HeteroRuntime, SimulatedClock, UnitSpec, WallClock, WorkQueue
from .hetero import HeteroPartition, HeterogeneousPartitioner, ThroughputTracker
from .straggler import MitigationPlan, StragglerDetector, StragglerMitigator, StragglerReport
from .elastic import DeviceHealth, ElasticEvent, ElasticMeshManager, ElasticSchedule, RescalePlan
from .parallel_for import HybridExecutor, SplitDecision
from .fleet import (
    Autoscaler,
    FailureTrace,
    FleetManager,
    FleetSimResult,
    HeartbeatBook,
    TraceEvent,
    simulate_fleet,
)

__all__ = [
    "HeteroRuntime",
    "SimulatedClock",
    "UnitSpec",
    "WallClock",
    "WorkQueue",
    "IterationSpace",
    "FlatSpace",
    "TiledSpace",
    "ShardedSpace",
    "ElasticEvent",
    "ElasticSchedule",
    "Chunk",
    "MultiDynamicScheduler",
    "StaticScheduler",
    "OracleStaticScheduler",
    "WorkerKind",
    "PollingEngine",
    "CompletionEvent",
    "RunReport",
    "BackendEngine",
    "BackendUnit",
    "CompletionBus",
    "CompletionRecord",
    "InlineUnit",
    "ThreadUnit",
    "ProcessPoolUnit",
    "JaxDeviceUnit",
    "WorkerLost",
    "WorkerDead",
    "Transport",
    "TransportError",
    "TransportClosed",
    "LoopbackTransport",
    "SocketTransport",
    "FlakyTransport",
    "RemoteUnit",
    "RemoteWorker",
    "WorkerServer",
    "spawn_worker",
    "HeteroPartition",
    "HeterogeneousPartitioner",
    "ThroughputTracker",
    "CostModel",
    "CostEntry",
    "CostModelWarning",
    "StragglerDetector",
    "StragglerMitigator",
    "StragglerReport",
    "MitigationPlan",
    "DeviceHealth",
    "ElasticMeshManager",
    "RescalePlan",
    "HybridExecutor",
    "SplitDecision",
    "HeartbeatBook",
    "Autoscaler",
    "FailureTrace",
    "TraceEvent",
    "FleetSimResult",
    "FleetManager",
    "simulate_fleet",
]
