"""End-to-end observability layer (ISSUE 15).

The one package every subsystem reports through:

  * `trace`    — distributed request tracing: `X-COS-Trace` ids minted
                 at the client/router, spans for router pick/retry,
                 replica queue-wait, flush assembly, padding, device
                 execution; sampled via COS_TRACE_SAMPLE (0 = inert),
                 spooled as per-process JSONL under COS_TRACE_DIR, and
                 aggregated cross-replica by the router.
  * `recorder` — flight recorder: bounded in-memory ring of structured
                 events (state transitions, drains, reloads,
                 evictions, chaos faults, verdicts), dumped to the
                 COS_RECORDER_DUMP artifact on SIGTERM / fatal
                 exception / fault latch.
  * `prom`     — Prometheus exposition of the PipelineMetrics summary
                 (`/metrics?format=prom` on replica, router, and the
                 training metrics port), plus the round-trip validator.
  * `profiler` — on-demand bounded `jax.profiler` capture
                 (`POST /v1/profile`) on a live process; of a live
                 trainer it shows the train path's own `cos.*` stages
                 (`PipelineMetrics.span`, vocabulary in `metrics.py`)
                 beside the device ops, on one clock.
  * `http`     — the training-side metrics port (COS_METRICS_PORT).

Everything here is HOST-side plumbing: nothing imports jax at module
scope, nothing runs at trace time, and every knob resolves once per
process (coslint COS003 discipline).
"""

from .recorder import (FlightRecorder, dump_path, get_recorder,
                       maybe_dump, record)
from .trace import (NULL_SPAN, TRACE_HEADER, Span, SpanCtx, Tracer,
                    get_tracer, parse_header, span_tree)
from .prom import (PromWriter, counter_values, parse_exposition,
                   render_summary)
from .profiler import ProfilerBusy, capture
from .http import ObsHTTPServer, maybe_start_obs_server

__all__ = [
    "FlightRecorder", "dump_path", "get_recorder", "maybe_dump",
    "record", "NULL_SPAN", "TRACE_HEADER", "Span", "SpanCtx",
    "Tracer", "get_tracer", "parse_header", "span_tree",
    "PromWriter", "counter_values", "parse_exposition",
    "render_summary", "ProfilerBusy", "capture", "ObsHTTPServer",
    "maybe_start_obs_server",
]
