"""Dorm: dynamically-partitioned cluster management + utilization-fairness
optimizer (Sun et al., SMARTCOMP 2017) -- the paper's core contribution."""
from .adjustment import (AdjustmentEvent, AdjustmentProtocol, CheckpointHandle,
                         RecordingProtocol)
from .autoscale import (AutoscaleConfig, AutoscalePolicy, LoadSignal,
                        ReplayLoadSignal, SLOMonitor, signals_from_workload)
from .backend import (AutoBackend, Backend, JaxBackend, NumpyBackend,
                      auto_dispatch_report, configure_compile_cache,
                      get_backend)
from .baselines import (MESOS_SCHED_LATENCY_S, DRFScheduler, StaticScheduler,
                        TaskLevelOverheadModel, TetrisScheduler)
from .chaos import (ChaosConfig, ChaosMonitor, chaos_config_hash,
                    chaos_from_csv, chaos_schedule, chaos_to_csv,
                    scale_cluster)
from .drf import (IncrementalDRF, dominant_share, drf_container_counts,
                  drf_container_counts_reference, drf_shares, fairness_loss,
                  saturating_counts)
from .goodput import (GoodputCurve, amdahl_curve, anchored_serial_work,
                      curve_for_model, derive_curve, work_anchor)
from .master import DormMaster
from .metrics import (actual_shares, adjusted_apps, churn_attribution,
                      cluster_fairness_loss, container_churn,
                      forced_churn_attribution, overload_seconds,
                      per_resource_utilization, resource_adjustment_overhead,
                      resource_utilization)
from .optimizer import (AutoOptimizer, GreedyOptimizer, MilpOptimizer,
                        OptimizerConfig, adjust_budget, fairness_budget,
                        make_optimizer, utilization_objective)
from .partition import Partition, TaskExecutor, TaskScheduler
from .replay import REPLAY_CLASS_INDEX, ReplayConfig, replay_trace
from .runtime import (AbsorberConfig, AppRuntime, Arrival, ChaosEvent,
                      ClusterRuntime, Completion, Event, EventBus,
                      MetricSample, Migrate, PolicyTimer, Reallocated,
                      ReallocationResult, Resize, ScaleDecision,
                      SchedulerPolicy, SimResult, SlaveDegraded, SlaveDrained,
                      SlaveFailed, SlaveRestored, Storm, Tick, as_policy)
from .shard import (Coordinator, ShardConfig, ShardedControlPlane,
                    cross_shard_certificate, partition_cluster)
from .simulator import (ClusterSimulator, ReferenceClusterSimulator,
                        speedup_ratios)
from .slave import Container, DormSlave
from .state import ClusterState, LazyAppViews, LazySlaveViews, StateSlaveView
from .telemetry import MetricsLogger
from .types import (Allocation, ApplicationSpec, ClusterSpec, ResourceVector,
                    SlaveSpec, demand_matrix, validate_allocation)
from .workload import (BASELINE_STATIC_CONTAINERS, MEAN_INTERARRIVAL_S,
                       SCALE_CLASSES, SLAVE_FLAVORS, TABLE_II,
                       ServingLoadProfile, TraceConfig, WorkloadApp,
                       generate_trace, generate_workload,
                       heterogeneous_cluster, paper_testbed,
                       sample_app_duration_s, sample_task_duration_s)

__all__ = [
    "AutoBackend", "Backend", "JaxBackend", "NumpyBackend",
    "auto_dispatch_report", "configure_compile_cache", "get_backend",
    "Coordinator", "Migrate", "ShardConfig", "ShardedControlPlane",
    "TetrisScheduler", "cross_shard_certificate", "partition_cluster",
    "utilization_objective",
    "AdjustmentEvent", "AdjustmentProtocol", "CheckpointHandle",
    "RecordingProtocol", "AutoscaleConfig", "AutoscalePolicy", "LoadSignal",
    "ReplayLoadSignal", "SLOMonitor", "signals_from_workload",
    "ScaleDecision", "ServingLoadProfile", "overload_seconds",
    "churn_attribution", "MESOS_SCHED_LATENCY_S", "DRFScheduler",
    "StaticScheduler", "TaskLevelOverheadModel", "IncrementalDRF",
    "dominant_share", "drf_container_counts",
    "drf_container_counts_reference", "drf_shares", "fairness_loss",
    "saturating_counts", "GoodputCurve", "amdahl_curve",
    "anchored_serial_work", "curve_for_model", "derive_curve", "work_anchor",
    "DormMaster", "ReallocationResult",
    "actual_shares", "adjusted_apps", "cluster_fairness_loss",
    "container_churn", "forced_churn_attribution",
    "per_resource_utilization",
    "resource_adjustment_overhead", "resource_utilization", "AutoOptimizer",
    "GreedyOptimizer", "MilpOptimizer",
    "OptimizerConfig", "adjust_budget", "fairness_budget", "make_optimizer",
    "Partition", "TaskExecutor", "TaskScheduler",
    "REPLAY_CLASS_INDEX", "ReplayConfig", "replay_trace",
    "AbsorberConfig", "AppRuntime", "Arrival", "ClusterRuntime", "Completion",
    "Event", "EventBus", "MetricSample", "PolicyTimer", "Reallocated",
    "Resize", "SchedulerPolicy", "SimResult", "Storm", "Tick", "as_policy",
    "ChaosConfig", "ChaosEvent", "ChaosMonitor", "SlaveDegraded",
    "SlaveDrained", "SlaveFailed", "SlaveRestored", "chaos_config_hash",
    "chaos_from_csv", "chaos_schedule", "chaos_to_csv", "scale_cluster",
    "ClusterSimulator", "ReferenceClusterSimulator", "speedup_ratios",
    "Container", "DormSlave",
    "ClusterState", "LazyAppViews", "LazySlaveViews", "StateSlaveView",
    "MetricsLogger", "Allocation", "ApplicationSpec", "ClusterSpec",
    "ResourceVector", "SlaveSpec", "demand_matrix", "validate_allocation",
    "BASELINE_STATIC_CONTAINERS", "MEAN_INTERARRIVAL_S", "SCALE_CLASSES",
    "SLAVE_FLAVORS", "TABLE_II", "TraceConfig",
    "WorkloadApp", "generate_trace", "generate_workload",
    "heterogeneous_cluster", "paper_testbed",
    "sample_app_duration_s", "sample_task_duration_s",
]
