# Observability spine (DESIGN.md §11): per-query tracing with
# Chrome-trace/Perfetto export, and a process-wide metrics registry
# with fixed-bucket latency histograms.  Zero dependencies; a None
# tracer / absent registry compiles every hook site down to one
# attribute check.
from .metrics import (LATENCY_BUCKETS_MS, REGISTRY,  # noqa: F401
                      SCHEMA_VERSION, Counter, Gauge, Histogram,
                      MetricsRegistry, exp_buckets)
from .trace import (MIRRORED, Tracer, bind_if, span_if,  # noqa: F401
                    validate_chrome_trace)
