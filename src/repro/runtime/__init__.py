"""Resilience + observability layer for the adaptive-sampling engine.

``faults`` is the deterministic fault-injection harness (seeded
schedules of kill / shrink / corrupt / truncate / nan / hang events);
``supervisor`` is the :class:`ResilientRunner` that drives
``repro.core.engine.run_adaptive`` through them — bounded retry with
backoff, per-epoch invariant watchdog with rollback, and the elastic
degradation ladder (sharded cooperative → SPMD replicated →
single-device).  See DESIGN.md §Fault tolerance.

``events`` + ``telemetry`` are the structured telemetry bus (typed
event taxonomy, span timers, pluggable sinks, Chrome-trace export)
threaded through the engine, the checkpoint store and the supervisor.
See DESIGN.md §Observability and ``tools/trace_report.py``.
"""
from .events import (EVENT_KINDS, SPAN_NAMES, SUPERVISOR_EVENT_KINDS, Event,
                     read_jsonl, validate_event)
from .faults import (DeviceLoss, FaultContext, FaultSchedule, FaultSpec,
                     InjectedFault, apply_fault, available_faults)
from .supervisor import (EpochTimeoutError, InvariantViolation,
                         ResilienceExhausted, ResilientRunner,
                         ResilientRunResult, RetryPolicy, RunEvent,
                         check_state_invariants, elastic_migrate_state)
from .telemetry import (JSONLSink, NullSink, NULL_TELEMETRY, RingSink,
                        Telemetry, chrome_trace, resolve_telemetry,
                        write_chrome_trace)

__all__ = [
    "DeviceLoss", "FaultContext", "FaultSchedule", "FaultSpec",
    "InjectedFault", "apply_fault", "available_faults",
    "EpochTimeoutError", "InvariantViolation", "ResilienceExhausted",
    "ResilientRunner", "ResilientRunResult", "RetryPolicy", "RunEvent",
    "check_state_invariants", "elastic_migrate_state",
    "EVENT_KINDS", "SPAN_NAMES", "SUPERVISOR_EVENT_KINDS", "Event",
    "read_jsonl", "validate_event",
    "JSONLSink", "NullSink", "NULL_TELEMETRY", "RingSink", "Telemetry",
    "chrome_trace", "resolve_telemetry", "write_chrome_trace",
]
