"""Runtime utilities: platform selection, perf counters, config,
tracing — the ``src/common/`` analog layer."""

from .platform import (
    apply_debug_modes,
    enable_compile_cache,
    install_debug_observer,
    require_tpu,
)
from .perf_counters import (
    PerfCounters,
    PerfCountersBuilder,
    PerfCountersCollection,
    perf_collection,
)
from .config import ConfigProxy, Option, config
from .trace import Tracer, tracer
from .optracker import NULL_OP, OpTracker, TrackedOp, op_tracker
from .cluster_log import ClusterLog, cluster_log
from .admin_socket import AdminSocket, admin_socket

__all__ = [
    "apply_debug_modes",
    "enable_compile_cache",
    "install_debug_observer",
    "require_tpu",
    "PerfCounters",
    "PerfCountersBuilder",
    "PerfCountersCollection",
    "perf_collection",
    "ConfigProxy",
    "Option",
    "config",
    "Tracer",
    "tracer",
    "NULL_OP",
    "OpTracker",
    "TrackedOp",
    "op_tracker",
    "ClusterLog",
    "cluster_log",
    "AdminSocket",
    "admin_socket",
]
