"""Runtime observability: step tracing, compiled-step inspection, drift.

The feedback channel the search stack lacked: costs flow INTO the native
search (flexflow_tpu/search/profile.py measured tables, machine.py
analytic comms), and this package makes what the jitted step actually
does flow back OUT — per-step phase spans (Chrome-trace/Perfetto JSON +
a JSONL event stream), XLA cost/memory analysis and a collective census
of the optimized HLO, and a drift report comparing the search's
predicted step time against the measured one (consumable by
scripts/calibrate.py). The devtrace layer (``--profile-steps``) adds a
windowed ``jax.profiler`` capture attributing each step's DEVICE time
into compute / collective / exposed-comms buckets, merged into the same
Perfetto timeline and joined against the census-priced collectives for
per-kind calibration. Cf. "A Learned Performance Model for TPUs" /
SCALE-Sim (PAPERS.md): a calibrated performance model is only as good
as its feedback loop.

Everything is inert unless a trace dir is set or a session is open
(``start_trace`` / ``stop_trace``, obs/session.py: one tracer for the
whole process, unfenced, optionally with ``jax.profiler`` on the same
clock): ``make_tracer(None)`` returns the shared ``NULL_TRACER`` whose
methods are no-ops, so the training hot path pays nothing when
observability is off.
"""

from flexflow_tpu.obs.artifacts import artifact_header, write_artifact
from flexflow_tpu.obs.devtrace import (
    NULL_CAPTURE,
    DeviceTraceCapture,
    attribution_report,
    make_capture,
    parse_profile_steps,
    record_step_metrics,
)
from flexflow_tpu.obs.drift import collective_drift, drift_report
from flexflow_tpu.obs.inspect import (
    collective_census,
    export_step_summary,
    inspect_compiled,
    inspect_model_step,
    model_context,
)
from flexflow_tpu.obs.registry import CounterRegistry, get_registry
from flexflow_tpu.obs.session import (
    session_tracer,
    start_trace,
    stop_trace,
)
from flexflow_tpu.obs.simtrace import (
    corpus_rows,
    sim_lane_events,
    simtrace_report,
    write_simtrace,
)
from flexflow_tpu.obs.roofline import (
    class_aggregates,
    finish_aggregates,
    format_markdown,
    roofline_report,
)
from flexflow_tpu.obs.tracer import (
    NULL_TRACER,
    NullTracer,
    StepTracer,
    make_tracer,
    merge_host_traces,
)

__all__ = [
    "artifact_header",
    "write_artifact",
    "NULL_CAPTURE",
    "DeviceTraceCapture",
    "attribution_report",
    "make_capture",
    "parse_profile_steps",
    "record_step_metrics",
    "collective_drift",
    "drift_report",
    "collective_census",
    "export_step_summary",
    "inspect_compiled",
    "inspect_model_step",
    "model_context",
    "CounterRegistry",
    "get_registry",
    "corpus_rows",
    "sim_lane_events",
    "simtrace_report",
    "write_simtrace",
    "session_tracer",
    "start_trace",
    "stop_trace",
    "class_aggregates",
    "finish_aggregates",
    "format_markdown",
    "roofline_report",
    "NULL_TRACER",
    "NullTracer",
    "StepTracer",
    "make_tracer",
    "merge_host_traces",
]
