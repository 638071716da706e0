"""``repro.obs`` — tracing, metrics and profiling across the stack.

The paper's environment-adaptive loop re-decides *where* to offload from
measurements of the running system.  This package is the measurement
substrate those decisions (and their operators) consume:

  trace     :class:`Tracer` — typed spans/events on a thread-safe ring
            buffer; Chrome/Perfetto ``trace_event`` JSON export.  Live
            spans of an enabled tracer are also ``jax.profiler``
            annotations, on the device trace's clock.  The serve engine,
            the offload session stages and the metering executors all
            record against the process-default tracer
            (:func:`get_tracer`), disabled — and near-free — until
            enabled.
  metrics   :class:`MetricsRegistry` — counter/gauge/exponential-bucket
            histogram families with a Prometheus text renderer and an
            optional stdlib HTTP ``/metrics`` endpoint
            (:class:`MetricsServer`; ``ServeEngine.serve_metrics(port)``).
  profile   :func:`profile_window` — opt-in ``jax.profiler`` capture
            around N serve steps or one planner round, degrading to a
            no-op when a capture cannot start.
  scopes    :func:`op_scopes` — instruction -> named scope (function
            block, ``kv_write``, ``head``, ...) of a compiled program,
            so device time in a trace can be read per block.
  timeline  ``python -m repro.obs.timeline trace.json`` — terminal span
            summary (p50/p99 per span kind) plus the critical path of the
            worst request.
"""

from repro.obs.metrics import (  # noqa: F401
    MetricsRegistry,
    MetricsServer,
    exponential_buckets,
)
from repro.obs.profile import profile_window  # noqa: F401
from repro.obs.scopes import module_name, op_scopes  # noqa: F401
from repro.obs.trace import (  # noqa: F401
    NULL_SPAN,
    SpanRecord,
    Tracer,
    get_tracer,
    set_tracer,
)
