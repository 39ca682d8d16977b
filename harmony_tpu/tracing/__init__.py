from harmony_tpu.tracing.span import (
    InMemorySpanReceiver,
    LocalFileSpanReceiver,
    Span,
    SpanContext,
    SpanReceiver,
    Tracing,
    current_span,
    get_tracing,
    job_stage,
    open_spans,
    longest_spans,
    record_span,
    set_tracing,
    trace_span,
)
from harmony_tpu.tracing.profiler import profile_session
from harmony_tpu.tracing.flight import FlightRecorder, get_recorder

__all__ = [
    "FlightRecorder",
    "get_recorder",
    "Span",
    "SpanContext",
    "SpanReceiver",
    "InMemorySpanReceiver",
    "LocalFileSpanReceiver",
    "Tracing",
    "trace_span",
    "current_span",
    "job_stage",
    "open_spans",
    "longest_spans",
    "record_span",
    "get_tracing",
    "set_tracing",
    "profile_session",
]
