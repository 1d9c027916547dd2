"""Core paper technique: Swift (workflow DSL + XDTM) / Karajan (futures
engine) / Falkon (multi-level scheduling) adapted to JAX/TPU.

Layered scheduler subsystem (see DESIGN.md): task records
(`repro.core.task`) -> providers (`repro.core.providers`) -> Falkon service
(`repro.core.falkon`) -> sites/load balancing (`repro.core.sites`) ->
engine dataflow + dispatch policy (`repro.core.engine`).

Public API:
    Engine, Workflow, Dataset, mappers, FalkonService, providers,
    RestartLog, FaultInjector, SimClock/RealClock.
"""
from repro.core.datastore import (DataLayer, DataObject, EvictionPolicy,
                                  ExecutorCache, LFUPolicy, LRUPolicy,
                                  ShardDirectory, SharedStore,
                                  SizeAwarePolicy, StagingCostModel)
from repro.core.devicepool import DeviceExecutorPool
from repro.core.engine import Engine
from repro.core.falkon import DRPConfig, FalkonConfig, FalkonService
from repro.core.federation import (FederatedEngine, Mailbox,
                                   MailboxTransport, QueueTransport,
                                   ShardedDataLayer, WorkStealer,
                                   hash_partitioner, inputs_partitioner,
                                   skewed_partitioner)
from repro.core.faults import FaultInjector, RetryPolicy, TaskFailure
from repro.core.futures import (CompletionCounter, DataFuture, Padded,
                                resolved, when_all)
from repro.core.health import (METRICS_STREAM_SCHEMA, HealthConfig,
                               HealthMonitor, RollingStat)
from repro.core.jobstore import (IllegalTransition, JobStore, Journal,
                                 TaskStateMachine, WorkflowState)
from repro.core.metrics import StreamStat
from repro.core.observability import (BoundedLog, MetricsRegistry, RunReport,
                                      Span, Tracer, build_report)
from repro.core.procfed import (ProcessFederation, ProcessTransport, Ref,
                                ShardHost, ShardSpec, SocketTransport)
from repro.core.provenance import VDC, InvocationRecord
from repro.core.providers import (BatchSchedulerProvider, ClusteringProvider,
                                  FalkonProvider, LocalProvider, Provider,
                                  WorkerPoolProvider)
from repro.core.realpool import ProcessExecutorPool, ThreadExecutorPool
from repro.core.restart_log import RestartLog
from repro.core.service import (ResumeView, WorkflowHandle,
                                WorkflowService)
from repro.core.simclock import RealClock, SimClock
from repro.core.sites import LoadBalancer, Site
from repro.core.task import Task, Uneven, task_key
from repro.core.workflow import Procedure, Workflow
from repro.core.xdtm import (ArrayOf, CSVMapper, Dataset, FILE,
                             FileSystemMapper, FLOAT, INT, ListMapper,
                             Mapper, PhysicalRef, Primitive, ShardMapper,
                             STRING, Struct)

__all__ = [
    "Engine", "Workflow", "Procedure", "Task", "task_key", "Uneven",
    "Padded",
    "Provider", "WorkerPoolProvider",
    "LocalProvider", "BatchSchedulerProvider", "FalkonProvider",
    "ClusteringProvider",
    "FalkonService", "FalkonConfig", "DRPConfig",
    "ThreadExecutorPool", "ProcessExecutorPool", "DeviceExecutorPool",
    "DataFuture", "CompletionCounter", "resolved", "when_all",
    "SimClock", "RealClock",
    "RestartLog", "FaultInjector", "RetryPolicy", "TaskFailure",
    "JobStore", "Journal", "TaskStateMachine", "IllegalTransition",
    "WorkflowState", "WorkflowService", "WorkflowHandle", "ResumeView",
    "VDC", "InvocationRecord", "LoadBalancer", "Site", "StreamStat",
    "Tracer", "Span", "BoundedLog", "MetricsRegistry", "RunReport",
    "build_report",
    "HealthMonitor", "HealthConfig", "RollingStat",
    "DataLayer", "DataObject", "SharedStore", "ExecutorCache",
    "StagingCostModel", "EvictionPolicy", "LRUPolicy", "LFUPolicy",
    "SizeAwarePolicy", "ShardDirectory",
    "FederatedEngine", "Mailbox", "MailboxTransport", "QueueTransport",
    "WorkStealer", "ShardedDataLayer",
    "ProcessFederation", "ShardSpec", "ShardHost", "ProcessTransport",
    "SocketTransport", "Ref",
    "hash_partitioner", "skewed_partitioner", "inputs_partitioner",
    "Dataset", "Mapper", "ListMapper", "FileSystemMapper", "CSVMapper",
    "ShardMapper", "PhysicalRef", "Struct", "ArrayOf", "Primitive",
    "INT", "FLOAT", "STRING", "FILE",
]
