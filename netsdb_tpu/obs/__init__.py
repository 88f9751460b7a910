"""Unified observability: query-scoped tracing + the central metrics
registry (see ``obs/trace.py`` and ``obs/metrics.py``). The public
surface other layers import::

    from netsdb_tpu import obs

    with obs.span("executor.fold_stream", "executor") as sp: ...
    obs.add("devcache.hits")
    obs.REGISTRY.counter("serve.client.retries").inc()

Spans/counters are no-ops unless a query trace is installed
(``obs.trace(...)`` — the serve dispatch and the wire client do this);
registry instruments are always live. Stdlib-only by design: the
JAX-free wire client imports this module.
"""

from netsdb_tpu.obs import attrib  # noqa: F401 — registers "attribution"
from netsdb_tpu.obs import operators  # noqa: F401 — registers "operators"
from netsdb_tpu.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    registry,
)
from netsdb_tpu.obs.trace import (  # noqa: F401
    DEFAULT_RING,
    QidSampler,
    QueryTrace,
    Span,
    TraceRing,
    add,
    adopt,
    capture,
    current_trace,
    enabled,
    new_query_id,
    record_into,
    sample_qid,
    set_enabled,
    span,
    trace,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "registry", "DEFAULT_RING", "QidSampler", "QueryTrace", "Span",
    "TraceRing", "add", "adopt", "attrib", "capture", "current_trace",
    "enabled", "new_query_id", "operators", "record_into", "sample_qid",
    "set_enabled",
    "span", "trace",
]
