"""Continuous-batching serving engine.

The orchestration layer above the jitted decode path: a paged KV cache
(``slots``), a request scheduler with deadlines/cancellation/backpressure
(``engine``), a streaming SSE front end (``server``), the shared
incremental detokenizer (``detok``), and the serving resilience layer
(``resilience``: lifecycle state machine, decode-tick supervision with a
circuit breaker, graceful drain, hot weight reload, deadline-aware load
shedding, serving chaos harness), and the fleet tier above them all
(``router``: replica registry with health probing and ejection,
prefix-aware + least-loaded routing, mid-stream failover, rolling fleet
reload). See docs/DESIGN.md § Serving engine, docs/SERVING.md § Fleet
router, and docs/RESILIENCE.md § Serving resilience.
"""
from zero_transformer_tpu.serving.detok import StreamDecoder
from zero_transformer_tpu.serving.engine import (
    CANCELLED,
    DONE,
    EXPIRED,
    FAILED,
    MIGRATED,
    QUEUED,
    REJECTED,
    ROLES,
    RUNNING,
    Request,
    RequestHandle,
    ServingEngine,
)
from zero_transformer_tpu.serving.prefix_cache import PagedPrefixIndex
from zero_transformer_tpu.serving.qos import (
    BROWNOUT_RUNGS,
    QOS_CLASSES,
    BrownoutController,
    ClassQueue,
    QosClassConfig,
    QosPolicy,
    TenantBuckets,
    TokenBucket,
    rung_at_least,
)
from zero_transformer_tpu.serving.resilience import (
    DEGRADED,
    DRAINING,
    READY,
    STARTING,
    STOPPED,
    CircuitBreaker,
    Lifecycle,
    ReloadError,
    ServeFault,
    ServingChaosMonkey,
)
from zero_transformer_tpu.serving.router import (
    EJECTED,
    PrefixAffinity,
    Replica,
    ReplicaRegistry,
    RouterServer,
    chunk_prefix_key,
    pick_decode_replica,
    pick_replica,
    run_router,
)
from zero_transformer_tpu.serving.server import ServingServer, run_server
from zero_transformer_tpu.serving.slots import (
    PagedKVCache,
    PagePool,
    page_span_from_wire,
    page_span_to_wire,
    vectorize_index,
)

__all__ = [
    "BROWNOUT_RUNGS",
    "BrownoutController",
    "ClassQueue",
    "DEGRADED",
    "DRAINING",
    "EJECTED",
    "QOS_CLASSES",
    "QosClassConfig",
    "QosPolicy",
    "TenantBuckets",
    "TokenBucket",
    "rung_at_least",
    "READY",
    "STARTING",
    "STOPPED",
    "CircuitBreaker",
    "Lifecycle",
    "PrefixAffinity",
    "Replica",
    "ReplicaRegistry",
    "RouterServer",
    "chunk_prefix_key",
    "pick_decode_replica",
    "pick_replica",
    "run_router",
    "PagedKVCache",
    "PagedPrefixIndex",
    "PagePool",
    "ReloadError",
    "ServeFault",
    "ServingChaosMonkey",
    "CANCELLED",
    "DONE",
    "EXPIRED",
    "FAILED",
    "MIGRATED",
    "QUEUED",
    "REJECTED",
    "ROLES",
    "RUNNING",
    "page_span_from_wire",
    "page_span_to_wire",
    "Request",
    "RequestHandle",
    "ServingEngine",
    "ServingServer",
    "StreamDecoder",
    "run_server",
    "vectorize_index",
]
