"""Online serving: dynamic micro-batching pipeline endpoint with
admission control (the Clipper-layer over frozen keystone_tpu
pipelines; see ``serve/service.py`` for the design), scaled out as a
replica fleet with versioned live model hot-swap (``serve/fleet.py``,
``serve/registry.py``).

Deliberately NOT imported by ``keystone_tpu/__init__`` — the offline
library import path (and every traced program) is byte-identical
whether or not a service exists in the process (pinned by
tests/test_serve.py).
"""

from keystone_tpu.serve.autoscale import (  # noqa: F401
    AutoscalePolicy,
    Autoscaler,
    Signals,
)
from keystone_tpu.serve.fleet import (  # noqa: F401
    FleetUnavailable,
    Replica,
    ReplicaPool,
    ReplicaSupervisor,
)
from keystone_tpu.serve.procfleet import (  # noqa: F401
    ChipOwnershipError,
    ProcessReplica,
    RemoteApplier,
    WorkerCrashed,
    WorkerHandle,
    WorkerSpawnError,
)
from keystone_tpu.serve.net import (  # noqa: F401
    ConnectRetriesExhausted,
    NetReplica,
    NetWorkerHandle,
    WorkerListener,
    run_worker,
)
from keystone_tpu.serve.http import HttpFrontend, serve_http  # noqa: F401
from keystone_tpu.serve.ingress import (  # noqa: F401
    AsyncIngress,
    BinaryClient,
    IngressError,
    serve_ingress,
)
from keystone_tpu.serve.registry import (  # noqa: F401
    ModelRegistry,
    RegistryError,
    RegistryWatcher,
)
from keystone_tpu.serve.rollout import (  # noqa: F401
    CanaryController,
    RollbackGuard,
    RolloutConfig,
    guarded_swap,
)
from keystone_tpu.serve.service import (  # noqa: F401
    Overloaded,
    PipelineService,
    PoisonRequest,
    ServiceClosed,
    default_buckets,
    serve,
)
from keystone_tpu.serve.telemetry import (  # noqa: F401
    ClockSync,
    FleetTelemetry,
    WorkerTelemetry,
    clamp_span,
)
from keystone_tpu.serve.tenants import (  # noqa: F401
    MultiTenantApplier,
    MultiTenantService,
    UnknownTenant,
    serve_multi,
)

__all__ = [
    "AsyncIngress",
    "AutoscalePolicy",
    "Autoscaler",
    "BinaryClient",
    "CanaryController",
    "ClockSync",
    "ConnectRetriesExhausted",
    "FleetTelemetry",
    "FleetUnavailable",
    "HttpFrontend",
    "IngressError",
    "NetReplica",
    "NetWorkerHandle",
    "ProcessReplica",
    "RemoteApplier",
    "WorkerListener",
    "Signals",
    "WorkerCrashed",
    "WorkerHandle",
    "WorkerSpawnError",
    "ModelRegistry",
    "MultiTenantApplier",
    "MultiTenantService",
    "Overloaded",
    "PipelineService",
    "PoisonRequest",
    "Replica",
    "ReplicaPool",
    "ReplicaSupervisor",
    "RegistryError",
    "RegistryWatcher",
    "RollbackGuard",
    "RolloutConfig",
    "ServiceClosed",
    "UnknownTenant",
    "WorkerTelemetry",
    "clamp_span",
    "default_buckets",
    "guarded_swap",
    "run_worker",
    "serve",
    "serve_http",
    "serve_ingress",
    "serve_multi",
]
