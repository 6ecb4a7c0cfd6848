"""repro.obs — structured span tracing + metrics export.

The time-resolved observability layer over the superstep/I-O/recovery
stack: a bounded ring-buffer :class:`Tracer` (spans, instants, counters;
:data:`NOOP` singleton when disabled), Chrome/Perfetto ``trace_event``
JSON export with one process lane per tracer (:mod:`repro.obs.export`), and a
stdlib report CLI (``python -m repro.obs report <trace>``).

Enable via ``PemsConfig(trace=True, trace_path="/tmp/run.json")`` and
export with ``pems.export_trace()``; see docs/ARCHITECTURE.md
"Observability" for the span taxonomy and lane layout.
"""

from .export import load_trace, trace_events, write_trace
from .report import render, report, summarize
from .tracer import NOOP, NoopTracer, Tracer

__all__ = [
    "Tracer", "NoopTracer", "NOOP",
    "trace_events", "write_trace", "load_trace",
    "summarize", "render", "report",
]
